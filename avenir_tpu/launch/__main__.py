"""Fleet launcher CLI — ``python -m avenir_tpu.launch``.

Three modes, one command line (docs/jobs.md "Fleet launcher"):

- **spawn** (``--nprocs N``): bring up N local worker processes as one
  jax-distributed fleet over a local coordinator, run the worker argv in
  each, merge journal shards, propagate the first non-zero exit;
- **join** (no ``--nprocs``, ``AVENIR_PROCESS_ID`` set): the process was
  provisioned externally (cluster scheduler started every rank) — exec
  the worker argv in place; the worker joins through the same hardened
  coordinator join via its environment;
- **serve** (``--serve --conf serve.properties --nprocs N``): GlobalServe
  (round 20) — bring up N full serving planes (one
  ``python -m avenir_tpu.serving`` process each, a ReplicaPool inside
  when ``pool.*`` is armed) and front them with the tenant-aware
  :class:`~avenir_tpu.serving.global_pool.GlobalRouter` on
  ``fleet.http.port``; on teardown every shard — workers, tenants and the
  router — merges into one ``fleet-<run>.jsonl``
  (docs/deployment.md "Cross-host serving").

On ONE host's TPU chips the spawn and serve modes have not run: every
worker inherits the parent's environment and would claim every chip (the
second dies on the TPU library's lock).  The examples are CPU recipes
(``JAX_PLATFORMS=cpu``); one process driving all of a host's chips is the
``shard.devices`` path (README "Multi-chip").

Examples::

    # 2 workers × 4 virtual CPU devices each, job CLI argv
    python -m avenir_tpu.launch --nprocs 2 --devices-per-proc 4 -- \\
        BayesianDistribution -Dconf.path=churn.properties train.csv out/

    # a benchmark script across 2 workers, journals merged
    python -m avenir_tpu.launch --nprocs 2 --journal-dir /tmp/tel -- \\
        benchmarks/multichip_scan.py --nprocs 2

    # a 2-process serving fleet behind one logical frontend
    python -m avenir_tpu.launch --serve --conf serve.properties --nprocs 2
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List

from avenir_tpu.launch import (LaunchError, launch_local, pod_env,
                               worker_command)


def main(argv: List[str]) -> int:
    if "--" in argv:
        cut = argv.index("--")
        opts, child = argv[:cut], argv[cut + 1:]
    else:
        opts, child = argv, []
    ap = argparse.ArgumentParser(
        prog="python -m avenir_tpu.launch",
        description="Spawn (or join) a jax-distributed worker fleet and "
                    "run a job/pipeline argv in every worker")
    ap.add_argument("--nprocs", type=int, default=0,
                    help="workers to spawn locally (omit inside an "
                         "externally provisioned pod)")
    ap.add_argument("--devices-per-proc", type=int, default=0,
                    help="virtual CPU devices per worker "
                         "(xla_force_host_platform_device_count)")
    ap.add_argument("--coordinator", default=None,
                    help="coordinator host:port (default: localhost on a "
                         "free port)")
    ap.add_argument("--join-timeout-sec", type=float, default=300.0,
                    help="per-attempt coordinator-join timeout (default "
                         "300; a bad address fails typed, never hangs)")
    ap.add_argument("--join-attempts", type=int, default=3,
                    help="coordinator-join attempts under decorrelated "
                         "jitter (default 3)")
    ap.add_argument("--timeout-sec", type=float, default=0.0,
                    help="overall fleet wall deadline (0 = none)")
    ap.add_argument("--journal-dir", default=None,
                    help="trace.journal.dir of the workers; shards are "
                         "merged into fleet-<run>.jsonl on teardown")
    ap.add_argument("--serve", action="store_true",
                    help="GlobalServe mode: front --nprocs serving worker "
                         "processes (built from --conf) with one "
                         "GlobalRouter on fleet.http.port")
    ap.add_argument("--conf", default=None,
                    help="(--serve) serving properties file, shared by "
                         "every worker process")
    ap.add_argument("--http-port", type=int, default=None,
                    help="(--serve) override fleet.http.port for the "
                         "router frontend")
    args = ap.parse_args(opts)

    if args.serve:
        if not args.conf:
            ap.error("--serve requires --conf <serve.properties>")
        if args.nprocs < 1:
            ap.error("--serve requires --nprocs >= 1")
        # lazy import: the launcher module itself stays stdlib-only at
        # import (the join-mode exec path must not pay a jax import)
        from avenir_tpu.serving.global_pool import serve_fleet

        try:
            return serve_fleet(args.conf, args.nprocs,
                               http_port=args.http_port)
        except LaunchError as e:
            print(f"launch error: {e}", file=sys.stderr)
            return 3

    try:
        if not args.nprocs:
            pod = pod_env()
            if pod is None:
                ap.error("--nprocs is required outside an externally "
                         "provisioned pod (AVENIR_PROCESS_ID / "
                         "AVENIR_NUM_PROCESSES unset)")
            # join mode: the environment already names this rank — exec
            # the worker in place (it joins via its env); no double join
            cmd = worker_command(child)
            os.execv(cmd[0], cmd)                      # never returns
        result = launch_local(
            child, args.nprocs,
            devices_per_proc=args.devices_per_proc or None,
            coordinator=args.coordinator,       # None → launch_local picks

            join_timeout_s=args.join_timeout_sec,
            join_attempts=args.join_attempts,
            timeout_s=args.timeout_sec,
            journal_dir=args.journal_dir)
    except LaunchError as e:
        print(f"launch error: {e}", file=sys.stderr)
        return 3
    for w in result.workers:
        print(f"[launch] worker p{w.rank} exit={w.returncode}",
              file=sys.stderr)
    for b in result.bundles:
        print(f"[launch] blackbox bundle: {b['dir']} ({b['reason']})",
              file=sys.stderr)
    if result.merged_journal:
        print(f"[launch] merged fleet journal: {result.merged_journal}",
              file=sys.stderr)
    return result.exit_code


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
