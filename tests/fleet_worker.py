"""Subprocess worker for the GraftFleet journal-federation gate
(tests/test_fleet.py, round 15).

Each invocation is ONE fleet writer: it configures tracing with a shared
``trace.run.id`` and its own ``trace.writer.suffix`` (so every worker
journals to its own shard of the same run), runs a REAL tiny
BayesianDistribution job — real job/chunk spans and a real counter
snapshot in the shard, not synthetic events — and then either exits
cleanly (``ok``) or dies hard via ``os._exit`` INSIDE an open span
(``crash``): the killed worker's shard must end with a ``span.open``
whose close never lands, which the merged fleet view renders as
``OPEN``.

Args: ``<journal_dir> <run_id> <suffix> <ok|crash> <workdir>``.
Prints ``fleet worker ok`` and exits 0 in ``ok`` mode.
"""

import os
import sys

# tests never claim a chip — same discipline as tests/shard_worker.py
# (forced here, not inherited from pytest's env)
os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    journal_dir, run_id, suffix, mode, workdir = sys.argv[1:6]
    import jax

    jax.config.update("jax_platforms", "cpu")

    import json

    from avenir_tpu.core.csv_io import write_csv
    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.datagen.churn import CHURN_SCHEMA_JSON, generate_churn
    from avenir_tpu.jobs import get_job
    from avenir_tpu.telemetry import spans as tel

    os.makedirs(workdir, exist_ok=True)
    train = os.path.join(workdir, "train.csv")
    schema = os.path.join(workdir, "churn.json")
    write_csv(train, generate_churn(120, seed=3))
    with open(schema, "w") as fh:
        fh.write(json.dumps(CHURN_SCHEMA_JSON)
                 if isinstance(CHURN_SCHEMA_JSON, dict)
                 else CHURN_SCHEMA_JSON)

    conf = JobConfig({
        "trace.on": "true",
        "trace.journal.dir": journal_dir,
        "trace.run.id": run_id,
        "trace.writer.suffix": suffix,
        "feature.schema.file.path": schema,
        "stream.chunk.rows": "60",
    })
    tracer = tel.configure(conf)
    assert tracer.enabled, "configure must enable this fleet writer"
    assert f".proc-0-{suffix}.jsonl" in (tracer.journal_path or ""), \
        tracer.journal_path

    # the job runs as the OUTERMOST traced unit, so its per-process
    # counter snapshot lands in this shard (Job.run skips it when a
    # pipeline stage span encloses it — the driver owns that snapshot)
    get_job("BayesianDistribution").run(
        conf, train, os.path.join(workdir, "nb_model"))
    if mode == "crash":
        with tracer.span("fleet.work", attrs={"writer": suffix}):
            # die INSIDE the span: span.open is journaled, span.close
            # never is — the preempted/killed-worker shape the merge
            # must tolerate and the tree must flag as OPEN
            os._exit(3)
    tracer.disable()
    print("fleet worker ok")


if __name__ == "__main__":
    main()
