"""Serving CLI — ``python -m avenir_tpu.serving --conf serve.properties``.

Loads every family in ``serve.models`` from the properties file's artifact
paths, warms the (model, bucket) compile cache, and serves.  With
``pool.replicas`` (or ``pool.autoscale.on``) set, the plane is a
FleetServe :class:`~avenir_tpu.serving.pool.ReplicaPool` — N batcher
replicas with health-gated routing, breaker/heartbeat failure detection,
request failover and burn-rate autoscaling — behind the same transports:

- HTTP on ``serve.http.port`` (default 8390): ``POST /score``,
  ``GET /healthz``, ``GET /stats`` — see docs/deployment.md for a
  serve-then-curl walkthrough;
- optionally a RESP list pair on a Redis server when
  ``serve.request.queue`` is set (``serve.redis.host``/``serve.redis.port``,
  responses to ``serve.response.queue``) — the transport the reference's
  own Redis simulators drive.

Runs until interrupted; stats print once on shutdown.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from typing import List

from avenir_tpu.core.config import JobConfig


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m avenir_tpu.serving",
        description="ServeGraft — device-resident online scoring plane")
    ap.add_argument("--conf", required=True,
                    help="properties file (serve.* keys + model artifacts)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="override serve.http.port")
    ap.add_argument("-D", dest="overrides", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="conf override (repeatable) — how the GlobalServe "
                         "launcher pins per-worker keys (trace.run.id, "
                         "split tenant contracts) over a shared conf file")
    args = ap.parse_args(argv)

    from avenir_tpu.serving.batcher import BucketedMicrobatcher
    from avenir_tpu.serving.frontend import (
        ScoreHTTPServer,
        redis_score_frontend,
    )
    from avenir_tpu.serving.pool import ReplicaPool
    from avenir_tpu.serving.registry import ModelRegistry

    # every (model, bucket) program warm() compiles is found again by the
    # next start of the same conf
    from avenir_tpu.utils import compile_cache

    compile_cache.configure()
    conf = JobConfig.from_file(args.conf)
    for item in args.overrides:
        key, eq, value = item.partition("=")
        if not eq or not key.strip():
            ap.error(f"-D expects KEY=VALUE, got {item!r}")
        conf.set(key.strip(), value.strip())
    # wire GraftTrace/GraftProf from the same properties file the models
    # load from (trace.on / profile.on — both default off); a replica
    # pool sets trace.writer.suffix per worker, which names this
    # process's journal shard AND its /metrics `replica` label
    from avenir_tpu.telemetry import spans as tel
    from avenir_tpu.telemetry.export import fleet_identity
    from avenir_tpu.telemetry.slo import SloEvaluator

    tel.configure(conf)
    # GraftPool (round 18): arm the tenant arbiter from tenant.* contracts
    # (no-op without them) — a tenant-owned serving plane (tenant.id) then
    # draws arbitrated dispatch slots and sheds tenant-scoped 429s with
    # Retry-After drain estimates
    from avenir_tpu import tenancy

    tenancy.configure(conf)
    slo = SloEvaluator.from_conf(conf)
    # FleetServe (round 17): any pool.* arming serves a ReplicaPool — N
    # batcher replicas with health-gated routing, breaker/heartbeat
    # failure detection, failover and burn-rate autoscaling — behind the
    # SAME frontends; without it the plane stays one batcher
    if conf.get_int("pool.replicas", 0) or \
            conf.get_bool("pool.autoscale.on", False):
        # the frontend and the pool's autoscaler share ONE evaluator, so
        # its violation latch journals one slo.violation per excursion
        # (the round-15 contract), not one per consumer
        batcher = ReplicaPool.from_conf(conf, slo=slo)
        health = batcher.health()
        names = health["models"]
        pool_note = f" x{len(health['replicas'])} replicas"
    else:
        registry = ModelRegistry.from_conf(conf)
        batcher = BucketedMicrobatcher.from_conf(registry, conf)
        names = registry.names()
        pool_note = ""
    port = (args.http_port if args.http_port is not None
            else conf.get_int("serve.http.port", 8390))
    # GlobalServe (round 20): behind a fleet launcher the writer suffix
    # names this worker PROCESS (w<k> via AVENIR_WRITER_SUFFIX), so the
    # same suffix rides /metrics as the `worker` label — every scrape
    # surface in a fleet is distinguishable even with identical replica
    # sets (the router scrapes as worker="router")
    suffix = (conf.get("trace.writer.suffix")
              or tel.tracer().writer_suffix or None)
    http = ScoreHTTPServer(
        batcher, port=port, slo=slo,
        identity=fleet_identity(
            replica=suffix,
            tenant=conf.get("tenant.id"),
            worker=suffix)).start()
    print(f"serving {names} on "
          f"http://{http.address[0]}:{http.address[1]} "
          f"(buckets {batcher.buckets}){pool_note}"
          + (f" with {len(slo.rules)} SLO rule(s)" if slo else ""),
          flush=True)

    request_queue = conf.get("serve.request.queue")
    if request_queue:
        frontend = redis_score_frontend(
            batcher,
            host=conf.get("serve.redis.host", "localhost"),
            port=conf.get_int("serve.redis.port", 6379),
            request_queue=request_queue,
            response_queue=conf.get("serve.response.queue",
                                    "scoreResponseQueue"))
        threading.Thread(target=frontend.run, daemon=True,
                         name="serve-resp").start()
        print(f"RESP transport polling {request_queue!r}", flush=True)

    # SIGTERM is how an orchestrator stops a replica (the GraftFleet
    # deployment shape): without a handler the default action kills the
    # process mid-write and skips the shutdown snapshot below — treat it
    # exactly like Ctrl-C.  GraftBox first: the forensics bundle latches
    # with the in-flight table as it stood when the signal landed (no-op
    # when blackbox.dir is unset), THEN the graceful drain runs.
    import signal

    from avenir_tpu.telemetry import blackbox

    stop = threading.Event()

    def _on_term(*_):
        blackbox.on_signal("SIGTERM")
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:                       # pragma: no cover - non-main
        pass
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    finally:
        http.stop()
        batcher.close()
        # final counter snapshot into this replica's journal shard (no-op
        # untraced): the post-hoc SLO gate's counter metrics (shed.rate,
        # recompiles.total) and `telemetry metrics` need a snapshot — the
        # serving loop otherwise journals only spans and gauges
        tel.tracer().counters("serving", batcher.counters)
        print(json.dumps(batcher.stats()), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
