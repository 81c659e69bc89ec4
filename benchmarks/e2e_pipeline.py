#!/usr/bin/env python
"""End-to-end pipeline benchmark: CSV bytes → native encode → device NB+MI.

The north-star workload (ROADMAP.md) is the hospital-readmission MI +
Naive-Bayes pipeline over CSV with the reference's driver contract. bench.py
measures the device aggregation alone; this measures the whole ingest path:
chunked CSV parsing through the C++ data plane (runtime/native) overlapped
with the jitted count kernels on chip.

Usage: python -m benchmarks.e2e_pipeline [n_rows]   (default 20M)
Prints one JSON line with end-to-end rows/sec, the ingest-only rate, and —
round 7 — the fused-vs-unfused wall for a 3-job (NB + MI + Cramér) pipeline
over the same dataset: unfused pays one full scan per job, the SharedScan
(``pipeline/scan.py``) pays one scan total, with byte-identical models
asserted inline.
"""

import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from avenir_tpu.core.encoding import DatasetEncoder
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.datagen.hosp_readmit import HOSP_SCHEMA_JSON, generate_hosp_readmit
from avenir_tpu.ops import agg
from avenir_tpu.runtime import native


def make_csv_block(n_rows: int, seed: int) -> bytes:
    rows = generate_hosp_readmit(n_rows, seed=seed)
    return ("\n".join(",".join(r) for r in rows) + "\n").encode()


def main():
    n_target = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000_000
    block_rows = min(500_000, max(n_target, 1))   # honor small requests
    block = make_csv_block(block_rows, seed=1)      # one synthesized block,
    n_blocks = max(n_target // block_rows, 1)       # streamed n_blocks times

    enc = DatasetEncoder(FeatureSchema.from_json(HOSP_SCHEMA_JSON))
    sample = generate_hosp_readmit(2000, seed=0)
    ds0 = enc.fit_transform(sample)
    ncols = len(sample[0])
    assert native.is_available(), native.build_error()

    f = ds0.codes.shape[1]
    nb = int(ds0.n_bins.max())
    n_classes = len(ds0.class_values)
    pair_idx = np.array([(i, j) for i in range(f) for j in range(i + 1, f)],
                        np.int32)
    ci, cj = pair_idx[:, 0], pair_idx[:, 1]

    # same device work as bench.py's primary metric, routed by the same
    # shared predicate (the per-job G read-out is host-side and amortized)
    from avenir_tpu.ops import pallas_hist
    device_step, chain_scalar, kernel_path = pallas_hist.chunk_pipeline(
        f, nb, n_classes, ci, cj)

    # warm up compile + native path (sync = one host fetch, device_sync)
    from avenir_tpu.utils.profiling import device_sync
    d = native.encode_bytes(block, enc, ncols=ncols)
    device_sync(device_step(jnp.asarray(d.codes), jnp.asarray(d.labels)))

    # ingest-only rate (best of 3, matching knn_qps.py)
    ingest_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        native.encode_bytes(block, enc, ncols=ncols)
        ingest_dt = min(ingest_dt, time.perf_counter() - t0)

    # end-to-end, serial reference: encode each block on host, dispatch
    # async to device; device work of block i overlaps host encode of
    # block i+1 only through dispatch asynchrony. Best of 3 passes,
    # matching the other benchmarks (dispatch jitter was tens of percent
    # run-to-run).
    dt_serial = float("inf")
    for _ in range(3):
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for _ in range(n_blocks):
            d = native.encode_bytes(block, enc, ncols=ncols)
            # dependency chain via the labels operand: the final fetch then syncs every block
            out = device_step(jnp.asarray(d.codes),
                              jnp.asarray(d.labels) + bias)
            bias = chain_scalar(out)
        device_sync(out)
        dt_serial = min(dt_serial, time.perf_counter() - t0)

    # end-to-end through the DeviceFeeder — the path the streaming jobs use
    # (jobs/base.py encoded_data_source): a worker thread encodes and stages
    # block N+1 while the main thread consumes block N.
    from avenir_tpu.runtime.feeder import DeviceFeeder

    def blocks():
        for _ in range(n_blocks):
            yield native.encode_bytes(block, enc, ncols=ncols)

    def stage(d):
        return jax.device_put(d.codes), jax.device_put(d.labels)

    dt = float("inf")
    for _ in range(3):
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for codes, labels in DeviceFeeder(blocks(), depth=2, stage=stage):
            out = device_step(codes, labels + bias)
            bias = chain_scalar(out)
        device_sync(out)
        dt = min(dt, time.perf_counter() - t0)
    total = n_blocks * block_rows

    # fused-vs-unfused 3-job pipeline (round 7): NB + MI + Cramér over the
    # SAME dataset.  Unfused = the reference's one-Tool-per-statistic shape
    # (each fit re-parses, re-encodes, re-uploads and re-aggregates the
    # stream); fused = pipeline/scan.SharedScan — one encode + one gram
    # pass serving all three consumers.  ``scan_seconds`` is the wall spent
    # scanning (parse+encode+device aggregation), the quantity the fusion
    # divides by K.
    from avenir_tpu.models.correlation import CramerCorrelation
    from avenir_tpu.models.mutual_info import MutualInformation
    from avenir_tpu.models.naive_bayes import NaiveBayes
    from avenir_tpu.pipeline import scan as shared_scan

    fuse_blocks = max(min(n_blocks, 4_000_000 // block_rows), 1)

    def chunk_stream():
        for _ in range(fuse_blocks):
            yield native.encode_bytes(block, enc, ncols=ncols)

    per_job = {}
    t0 = time.perf_counter()
    nb_model = NaiveBayes().fit(chunk_stream())
    per_job["nb"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    mi_result = MutualInformation().fit(chunk_stream())
    per_job["mi"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    cr_result = CramerCorrelation().fit(chunk_stream(), against_class=True)
    per_job["cramer"] = time.perf_counter() - t0
    unfused_s = sum(per_job.values())

    def build_engine(pack_on):
        engine = shared_scan.SharedScan(pack_on=pack_on)
        engine.register(shared_scan.NaiveBayesConsumer(name="nb"))
        engine.register(shared_scan.MutualInfoConsumer(name="mi"))
        engine.register(shared_scan.CorrelationConsumer(name="cramer",
                                                        against_class=True))
        return engine

    def check(results):
        # the fused scan must reproduce the standalone jobs bit-for-bit —
        # asserted BEFORE any rate is reported, for BOTH engines
        assert np.array_equal(results["nb"].bin_counts, nb_model.bin_counts)
        assert np.array_equal(results["mi"].pair_class_counts,
                              mi_result.pair_class_counts)
        assert np.array_equal(results["cramer"].contingency,
                              cr_result.contingency)

    engine = build_engine(pack_on=False)     # the unpacked fused scan
    t0 = time.perf_counter()
    check(engine.run(chunk_stream()))
    fused_s = time.perf_counter() - t0

    # PackGraft (round 16): the default engine routes the same three
    # consumers onto ONE wide block-diagonal gram dispatch per chunk
    packed_engine = build_engine(pack_on=True)
    t0 = time.perf_counter()
    check(packed_engine.run(chunk_stream()))
    packed_s = time.perf_counter() - t0

    # PlanGraft (round 19): planned-vs-staged DRIVER runs.  A realistic
    # pipeline interleaves non-count stages (report/transform steps)
    # between the count jobs, so the staged driver's consecutive-stage
    # fusion pays THREE scans (NB alone, MI alone, Cramér alone); the
    # planner hoists past the interleaved stages and serves all three
    # count stages from ONE scan.  Byte-identity of every artifact is
    # asserted inline BEFORE any rate is published.
    import os
    import shutil
    import tempfile

    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.pipeline import plan as plan_mod
    from avenir_tpu.pipeline.driver import Pipeline, Stage
    from avenir_tpu.utils.metrics import Counters

    plan_root = tempfile.mkdtemp(prefix="e2e_plan_")
    train_csv = os.path.join(plan_root, "train.csv")
    with open(train_csv, "wb") as fh:
        for _ in range(fuse_blocks):
            fh.write(block)
    schema_path = os.path.join(plan_root, "hosp.json")
    with open(schema_path, "w") as fh:
        fh.write(json.dumps(HOSP_SCHEMA_JSON))
    class_ord = FeatureSchema.from_json(HOSP_SCHEMA_JSON).class_field.ordinal

    def report_stage(conf, in_path, out_path):
        os.makedirs(out_path, exist_ok=True)
        with open(os.path.join(out_path, "part-00000"), "w") as out:
            out.write("report\n")
        return Counters()

    def build_pipeline(ws, plan_on):
        conf = JobConfig({"feature.schema.file.path": schema_path,
                          "plan.on": "true" if plan_on else "false"})
        p = Pipeline(os.path.join(plan_root, ws), conf)
        p.bind("data", train_csv)
        p.add(Stage("nb", "BayesianDistribution", "data", "nb_model"))
        p.add(Stage("report", report_stage, "data", "report_out"))
        p.add(Stage("mi", "MutualInformation", "data", "mi_out"))
        p.add(Stage("report2", report_stage, "data", "report2_out"))
        p.add(Stage("cramer", "CramerCorrelation", "data", "cramer_out",
                    props={"dest.attributes": str(class_ord)}))
        return p

    def timed_run(ws, plan_on, passes=2):
        best = float("inf")
        for _ in range(passes):
            shutil.rmtree(os.path.join(plan_root, ws), ignore_errors=True)
            p = build_pipeline(ws, plan_on)
            t0 = time.perf_counter()
            p.run()
            best = min(best, time.perf_counter() - t0)
        return p, best

    staged_p, staged_s = timed_run("ws_staged", plan_on=False)
    planned_p, planned_s = timed_run("ws_planned", plan_on=True)
    for art in ("nb_model", "report_out", "mi_out", "report2_out",
                "cramer_out"):
        a = open(os.path.join(plan_root, "ws_staged", art,
                              "part-00000"), "rb").read()
        b = open(os.path.join(plan_root, "ws_planned", art,
                              "part-00000"), "rb").read()
        assert a == b, f"planned {art} diverged from the staged oracle"
    plan_summary = plan_mod.plan_pipeline(build_pipeline("ws_x",
                                                         True)).summary()
    shutil.rmtree(plan_root, ignore_errors=True)

    print(json.dumps({
        "metric": "e2e_csv_nb_mi_pipeline",
        "value": round(total / dt, 1),
        "unit": "rows/sec/chip",
        "rows": total,
        "serial_rows_per_sec": round(total / dt_serial, 1),
        "ingest_only_rows_per_sec": round(block_rows / ingest_dt, 1),
        "count_path": "pallas_cooc_int8_mxu" if kernel_path else "einsum",
        "fused_pipeline": {
            "jobs": ["nb", "mi", "cramer"],
            "rows": fuse_blocks * block_rows,
            "unfused_scan_seconds": round(unfused_s, 3),
            "unfused_per_job_seconds": {k: round(v, 3)
                                        for k, v in per_job.items()},
            "fused_scan_seconds": round(fused_s, 3),
            "scan_seconds_ratio": round(unfused_s / fused_s, 2),
            "packed_scan_seconds": round(packed_s, 3),
            "packed_speedup_vs_fused": round(fused_s / packed_s, 2),
            "packed_path": packed_engine.count_path,
            "byte_identical": True,
        },
        # plan_speedup is a shared-rig ratio (both runs interleave on the
        # same device seconds apart), so canary fields divide out — the
        # pack_speedup precedent; the absolute walls ride along as
        # optional rows (BASELINE.json sentinel.optional: planned.*)
        "planned": {
            "plan_speedup": {
                "value": round(staged_s / planned_s, 2), "unit": "x"},
            "staged_scan_seconds": {
                "value": round(staged_s, 3), "unit": "seconds"},
            "planned_scan_seconds": {
                "value": round(planned_s, 3), "unit": "seconds"},
            "byte_identical": True,
            "rewrites": plan_summary["rewrites"],
            "plan_source": plan_summary["source"],
        },
    }))


if __name__ == "__main__":
    main()
