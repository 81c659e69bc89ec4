#!/usr/bin/env python
"""Serving-path benchmark: events/sec + action latency through the
ShardedServingFleet (the Storm-topology capacity analog,
ReinforcementLearnerTopology.java:42-85), plus the ServeGraft scoring
plane: QPS + p50/p99 per model family per bucket size through the bucketed
microbatcher, with the zero-steady-state-recompiles invariant ASSERTED
(the compile-cache discipline is the whole point of bucketing — a recompile
on the hot path voids the measurement).  Prints one JSON line; the
scoring-plane section is canary-conditioned per the PR-2 convention (a
fresh matmul canary rides in the artifact so a slow rig indicts itself,
not the kernel).

Workload: G engagement groups, each its own intervalEstimator learner over
5 actions (the reference runs one topology per group); events round-robin
the groups; every event drains that group's reward queue and emits an
action. Reported per worker count (the ``num.bolt.threads`` knob):

- events/sec over the whole stream (dispatch + backpressure + learner
  update + action write);
- p50/p99 per-event latency measured at the single-server level (one
  group, submit → action visible), the serving loop's intrinsic cost.

On the 1-core dev rig thread workers add no parallel speedup (GIL + one
core); the knob exists for capacity parity and is measured honestly —
multi-core hosts scale groups across workers.
"""

import json
import time

import numpy as np

from avenir_tpu.models import online_rl as orl
from avenir_tpu.pipeline import streaming as st
from avenir_tpu.utils.metrics import percentile_of

ACTIONS = [f"a{i}" for i in range(5)]
CONF = {"min.reward.distr.sample": 10}


def make_server(_group: str) -> st.ReinforcementLearnerServer:
    learner = orl.create_learner("intervalEstimator", ACTIONS, CONF, seed=3)
    return st.ReinforcementLearnerServer(
        learner, st.QueueEventSource(st.InProcQueue()),
        st.QueueRewardReader(st.InProcQueue()),
        st.QueueActionWriter(st.InProcQueue()))


def fleet_events_per_sec(num_workers: int, n_groups: int = 32,
                         n_events: int = 40_000) -> float:
    fleet = st.ShardedServingFleet(make_server, num_workers=num_workers,
                                   max_pending=256)
    t0 = time.perf_counter()
    for i in range(n_events):
        fleet.dispatch(f"g{i % n_groups}", f"ev{i}", i)
    fleet.close()
    dt = time.perf_counter() - t0
    assert fleet.processed == n_events
    return n_events / dt


def process_fleet_events_per_sec(num_workers: int, n_groups: int = 32,
                                 n_events: int = 40_000) -> float:
    # The num.workers (multi-process) pool: on a multi-core host this is
    # the knob that scales CPU-bound learners past the GIL; on the 1-core
    # dev rig it measures the IPC overhead honestly.
    fleet = st.ProcessServingFleet(make_server, num_workers=num_workers,
                                   max_pending=256)
    t0 = time.perf_counter()
    for i in range(n_events):
        fleet.dispatch(f"g{i % n_groups}", f"ev{i}", i)
    fleet.close()
    dt = time.perf_counter() - t0
    assert len(fleet.actions()) == n_events
    return n_events / dt


def single_event_latencies(n: int = 20_000):
    srv = make_server("g")
    events = srv.events.queue
    actions = srv.actions.queue
    rewards = srv.rewards.queue
    rng = np.random.default_rng(0)
    lats = []
    for i in range(n):
        t0 = time.perf_counter()
        events.push(f"ev{i},{i}")
        srv.process_one()
        msg = actions.pop()
        lats.append(time.perf_counter() - t0)
        action = msg.split(",")[1]
        rewards.push(f"{action},{max(rng.normal(50, 10), 0.0)}")
    return np.asarray(lats)


class _HeavyWrap:
    """Learner wrapper whose action selection first burns pure-Python CPU
    WHILE HOLDING THE GIL — the worst case for thread workers and the
    justifying case for process workers (round-4 verdict item 7)."""

    def __init__(self, inner, burn_loops: int):
        self._inner = inner
        self._burn = burn_loops

    def next_actions(self, round_num):
        acc = 0
        for i in range(self._burn):          # pure-Python GIL-holding burn
            acc += i & 7
        self._sink = acc
        return self._inner.next_actions(round_num)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def gil_contention_probe(n_events: int = 3000, burn_loops: int = 60_000):
    """Thread vs process fleet at ONE worker each under a GIL-holding
    CPU-bound learner, against the no-fleet per-event cost.

    What this CAN demonstrate on the 1-core dev rig: the measured
    per-event cost of each dispatch path under load — the thread fleet is
    bounded by GIL serialization (≈ the pure cost: dispatcher and worker
    interleave on one lock), the process fleet adds measurable IPC on
    top of OS scheduling.  What it CANNOT demonstrate here: the
    multi-core win — with W cores and W process workers the same
    GIL-holding update scales ~W× while thread workers stay at the pure
    rate; that claim is an EXTRAPOLATION from this measurement."""
    def heavy_server(_group: str) -> st.ReinforcementLearnerServer:
        learner = _HeavyWrap(
            orl.create_learner("intervalEstimator", ACTIONS, CONF, seed=3),
            burn_loops)
        return st.ReinforcementLearnerServer(
            learner, st.QueueEventSource(st.InProcQueue()),
            st.QueueRewardReader(st.InProcQueue()),
            st.QueueActionWriter(st.InProcQueue()))

    # no-fleet reference: the bare serve loop, one event at a time
    srv = heavy_server("g")
    t0 = time.perf_counter()
    for i in range(n_events):
        srv.events.queue.push(f"ev{i},{i}")
        srv.process_one()
        srv.actions.queue.pop()
    pure = n_events / (time.perf_counter() - t0)

    out = {"pure_events_per_sec": round(pure, 1)}
    for label, cls in (("thread", st.ShardedServingFleet),
                       ("process", st.ProcessServingFleet)):
        fleet = cls(heavy_server, num_workers=1, max_pending=256)
        t0 = time.perf_counter()
        for i in range(n_events):
            fleet.dispatch(f"g{i % 8}", f"ev{i}", i)
        fleet.close()
        rate = n_events / (time.perf_counter() - t0)
        out[f"{label}_events_per_sec"] = round(rate, 1)
        out[f"{label}_per_event_overhead_us"] = round(
            (1.0 / rate - 1.0 / pure) * 1e6, 1)
    return out


# ---------------------------------------------------------------------------
# the scoring plane (ServeGraft) — QPS + latency per family per bucket
# ---------------------------------------------------------------------------

SCORE_BUCKETS = (1, 8, 32)


def _build_serving_workspace(root: str):
    """Train every family's artifact with the real jobs (tiny datasets) and
    return {family: (serve conf, request lines)} — the benchmark measures
    the same artifact-handoff path production serving uses."""
    import os

    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.core.csv_io import write_csv
    from avenir_tpu.datagen.churn import CHURN_SCHEMA_JSON, generate_churn
    from avenir_tpu.datagen.retarget import (
        RETARGET_SCHEMA_JSON,
        generate_retarget,
    )
    from avenir_tpu.jobs import get_job
    from avenir_tpu.jobs.base import read_lines

    j = lambda *p: os.path.join(root, *p)
    rows = generate_churn(1200, seed=7)
    write_csv(j("train.csv"), rows[:800])
    write_csv(j("test.csv"), rows[800:])
    with open(j("churn.json"), "w") as fh:
        fh.write(json.dumps(CHURN_SCHEMA_JSON))
    churn = {"feature.schema.file.path": j("churn.json")}
    get_job("BayesianDistribution").run(JobConfig(dict(churn)),
                                        j("train.csv"), j("nb_model"))
    get_job("LogisticRegressionJob").run(
        JobConfig({**churn, "coeff.file.path": j("coeff.txt"),
                   "iteration.limit": "15"}),
        j("train.csv"), j("lr_out"))
    rrows = generate_retarget(1500, seed=3)
    write_csv(j("rdata.csv"), rrows)
    with open(j("retarget.json"), "w") as fh:
        fh.write(json.dumps(RETARGET_SCHEMA_JSON))
    retarget = {"feature.schema.file.path": j("retarget.json")}
    get_job("DecisionTreeBuilder").run(JobConfig(dict(retarget)),
                                       j("rdata.csv"), j("tree_model"))
    os.mkdir(j("tagged"))
    with open(j("tagged", "part-00000"), "w") as fh:
        fh.write("c1,x:A,y:B,x:A\nc2,y:B,y:B,x:A\nc3,x:A,y:B,x:A,x:A\n")
    get_job("HiddenMarkovModelBuilder").run(JobConfig({}), j("tagged"),
                                            j("hmm_model"))

    churn_lines = read_lines(j("test.csv"))
    seq_lines = [f"u{i},{i % 9},{'x,y,x,y'[: 1 + 2 * (i % 4)]}"
                 for i in range(400)]
    return {
        "naiveBayes": (JobConfig({**churn,
                                  "bayesian.model.file.path": j("nb_model"),
                                  "serve.models": "naiveBayes"}),
                       churn_lines),
        "logistic": (JobConfig({**churn, "coeff.file.path": j("coeff.txt"),
                                "serve.models": "logistic"}), churn_lines),
        "tree": (JobConfig({**retarget,
                            "tree.model.file.path": j("tree_model"),
                            "serve.models": "tree"}),
                 read_lines(j("rdata.csv"))),
        "knn": (JobConfig({**churn, "training.data.path": j("train.csv"),
                           "top.match.count": "7",
                           "kernel.function": "gaussian",
                           "serve.models": "knn"}), churn_lines),
        "viterbi": (JobConfig({"hmm.model.file.path": j("hmm_model"),
                               "skip.field.count": "2",
                               "serve.models": "viterbi",
                               "serve.sequence.pad.len": "16"}), seq_lines),
    }


def scoring_plane_section(bursts_per_bucket: int = 40):
    """{family: {bucket: {qps, p50_ms, p99_ms}}, steady_state_recompiles}.

    Per (family, bucket): submit ``bursts_per_bucket`` bucket-sized bursts
    through the warmed microbatcher (submit_nowait the burst, wait all —
    the dispatcher folds each burst into exactly one padded bucket), report
    rows/sec and per-burst p50/p99.  After ALL steady-state traffic the
    recompiles counter must read zero for every family — asserted, and
    published so the artifact carries the proof."""
    import tempfile

    from avenir_tpu.serving.batcher import BucketedMicrobatcher
    from avenir_tpu.serving.registry import ModelRegistry

    out = {}
    total_recompiles = 0
    with tempfile.TemporaryDirectory(prefix="servegraft_bench_") as root:
        families = _build_serving_workspace(root)
        for family, (conf, lines) in families.items():
            conf.set("serve.bucket.sizes",
                     ",".join(str(b) for b in SCORE_BUCKETS))
            conf.set("serve.flush.deadline.ms", "2")
            registry = ModelRegistry.from_conf(conf)
            batcher = BucketedMicrobatcher.from_conf(registry, conf)
            fam_stats = {}
            try:
                for bucket in SCORE_BUCKETS:
                    burst_lat = []
                    rows_done = 0
                    t0 = time.perf_counter()
                    for burst in range(bursts_per_bucket):
                        take = [lines[(burst * bucket + i) % len(lines)]
                                for i in range(bucket)]
                        tb = time.perf_counter()
                        pend = [batcher.submit_nowait(family, ln)
                                for ln in take]
                        for p in pend:
                            p.wait(60.0)
                        burst_lat.append(time.perf_counter() - tb)
                        rows_done += bucket
                    dt = time.perf_counter() - t0
                    lat = np.asarray(burst_lat)
                    fam_stats[str(bucket)] = {
                        "qps": round(rows_done / dt, 1),
                        "p50_ms": round(percentile_of(lat, 50) * 1e3, 3),
                        "p99_ms": round(percentile_of(lat, 99) * 1e3, 3),
                    }
                recompiles = batcher.counters.get(f"Serving.{family}",
                                                  "recompiles")
                if recompiles != 0:
                    # a hot-path compile voids the timings — hard failure
                    # even under python -O (so no `assert`)
                    raise RuntimeError(
                        f"{family}: {recompiles} steady-state recompile(s) "
                        f"— a shape escaped the warmed bucket set")
                fam_stats["steady_state_recompiles"] = recompiles
            finally:
                batcher.close()
            out[family] = fam_stats
            total_recompiles += recompiles
    out["steady_state_recompiles_total"] = total_recompiles
    return out


def main():
    rates = {w: round(fleet_events_per_sec(w), 1) for w in (1, 2, 4)}
    proc_rates = {w: round(process_fleet_events_per_sec(w), 1)
                  for w in (1, 2, 4)}
    lats = single_event_latencies()
    # fresh canary right before the scoring-plane section (PR-2 convention):
    # inflated canary ⇒ the rig was loaded, not the serving plane slow
    from avenir_tpu.utils.rig_canary import matmul_canary_ms
    canary_ms = matmul_canary_ms()
    print(json.dumps({
        "metric": "serving_events_per_sec",
        "value": max(rates.values()),
        "unit": "events/sec",
        "events_per_sec_by_workers": rates,
        "process_events_per_sec_by_workers": proc_rates,
        "p50_latency_us": round(percentile_of(lats, 50) * 1e6, 1),
        "p99_latency_us": round(percentile_of(lats, 99) * 1e6, 1),
        "groups": 32,
        "learner": "intervalEstimator",
        "gil_contention_1worker": gil_contention_probe(),
        "canary_matmul_4096_bf16_ms": round(canary_ms, 2),
        "scoring_plane": scoring_plane_section(),
    }))


if __name__ == "__main__":
    main()
