"""Subprocess worker for the GlobalServe failover gate
(tests/test_globalserve.py, round 20).

Each invocation is ONE serving worker PROCESS of a GlobalRouter fleet:
it forces the CPU platform (tests never claim a chip — the same
discipline as tests/fleet_worker.py) and then runs the REAL serving
CLI (``python -m avenir_tpu.serving``) with the argv passed through —
conf file, ``--http-port``, and the launcher-style ``-D`` overrides
(``trace.run.id``, per-worker tenant splits).  The journal-shard suffix
arrives via ``AVENIR_WRITER_SUFFIX``, exactly as the
:class:`~avenir_tpu.serving.global_pool.WorkerSpawner` sets it, so the
gate exercises the worker's production bring-up path end to end: env
suffix adoption, ``-D`` overrides, model load + warmup, the HTTP plane,
and — when the conf arms ``fault.serve.dispatch.crash.after`` — the
mid-batch death whose in-flight requests the router must re-score on a
survivor byte-identical to the single-plane oracle.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from avenir_tpu.serving.__main__ import main as serve_main

    raise SystemExit(serve_main(sys.argv[1:]))


if __name__ == "__main__":
    main()
