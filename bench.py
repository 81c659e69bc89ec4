#!/usr/bin/env python
"""Benchmark: Naive-Bayes + mutual-information pipeline throughput on TPU.

The driver-defined primary metric (BASELINE.json): rows/sec/chip on the
NaiveBayes+MI aggregation pipeline — the rebuild of the reference's
hospital-readmission north-star workload (BayesianDistribution +
MutualInformation MR jobs). Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "rows/sec/chip", "vs_baseline": N}

``vs_baseline`` is the speedup over a single-core numpy implementation of the
same counts (the stand-in for the reference's per-record JVM mapper loop,
measured on a subsample and scaled), since the reference publishes no numbers.

Round 4: the per-chunk device step is the FUSED COLUMNAR MXU co-occurrence
kernel (ops/pallas_hist.py — G = XᵀX over the joint (feature, bin, class)
one-hot, int8 MXU pass, joint+expand fused in-kernel, no transpose/prologue)
when the attached device supports it; the [F,B,C] and [P,B,B,C] tensors are
read out of G once per job on host (microseconds — reported as
``finalize_ms``), exactly how MutualInformation.fit consumes it.  The
einsum/scatter form it replaced measured ~80-113 M rows/s on the same rig
and remains the fallback (and the multi-device path).  The remaining wall
is the W=384 int8 gram's ~30%-of-peak MXU ceiling, cross-validated against
bare XLA (see ops/pallas_hist.py docstring + benchmarks/*_probe.py).

Round 8: this script (and every benchmarks/ probe) is gated by graftlint
in tier-1 — ``python -m avenir_tpu.analysis`` / tests/test_analysis.py —
so a timing loop that regresses into a host-sync-per-iteration pattern
(GL005: .item()/device_get inside the measured loop — the r05 RTT-wall
class the honest-sync discipline here exists to avoid) fails CI before it
can publish an RTT measurement as a kernel number (docs/analysis.md).
"""

import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from avenir_tpu.ops import agg, pallas_hist


def make_data(n_rows: int, n_feat: int, n_bins: int, n_classes: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, n_bins, size=(n_rows, n_feat), dtype=np.int32)
    labels = rng.integers(0, n_classes, size=n_rows, dtype=np.int32)
    return codes, labels


def numpy_reference_rows_per_sec(codes, labels, n_classes, n_bins):
    """Single-core numpy equivalent of the NB+MI count pass (per-record cost model
    of the reference's mapper+reducer). Computes the SAME work as the TPU
    pipeline (all feature pairs) so vs_baseline compares like for like.
    Median of 3 reps: the bench shares its host's cores, and a single rep
    swung vs_baseline by 2× run-to-run."""
    n, f = codes.shape
    pairs = [(i, j) for i in range(f) for j in range(i + 1, f)]
    # Buffers hoisted out of the timed loop (round-5 fix): allocating them
    # per feature/pair inside the timing mildly understated the baseline and
    # thus inflated vs_baseline. The persistent-accumulator shape also
    # matches the reference mapper, which reuses its count maps.
    nb_buf = np.zeros((n_bins, n_classes))
    pair_buf = np.zeros((n_bins, n_bins))
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        # NB: class-conditional counts
        for fi in range(f):
            np.add.at(nb_buf, (codes[:, fi], labels), 1)
        # MI: pairwise joint counts
        for i, j in pairs:
            np.add.at(pair_buf, (codes[:, i], codes[:, j]), 1)
        rates.append(n / (time.perf_counter() - t0))
    return float(np.median(rates))


def main():
    from avenir_tpu.utils.roofline import require_tpu
    require_tpu("bench.py")
    # GraftTrace (round 10): AVENIR_TRACE_DIR opts the bench into the run
    # journal — each pass becomes a span and each canary a journal event,
    # so a regressed artifact ships its own timeline (``trace_artifact``
    # below names it; `python -m avenir_tpu.telemetry <path>` renders it).
    # Unset (the default), the tracer stays disabled: no journal file is
    # created and the timed loop pays one attribute check per span site
    # (benchmarks/telemetry_overhead.py publishes the measured on-state
    # cost).
    import os

    from avenir_tpu.telemetry import profile as prof_mod
    from avenir_tpu.telemetry import spans as tel
    tracer = tel.tracer()
    prof = prof_mod.profiler()
    trace_dir = os.environ.get("AVENIR_TRACE_DIR")
    if trace_dir:
        # GraftProf rides the same opt-in: the journal then carries
        # program.compiled (AOT cost of the chunk program) +
        # program.profile events, so `python -m avenir_tpu.telemetry
        # profile <trace_artifact>` renders this run's roofline table
        tracer.enable(trace_dir)
        prof.enable()

    # Rig-state canary FIRST (round 5): a bare-XLA 4096³ bf16 matmul,
    # measured before any framework kernel touches the chip, so every
    # artifact separates "rig slow" from "kernel regressed"
    # (utils/rig_canary.py).
    from avenir_tpu.utils.rig_canary import matmul_canary_ms
    canary_ms = matmul_canary_ms()
    tracer.event("canary", ms=round(canary_ms, 2), when="pre_run")

    n_classes, n_bins, n_feat = 2, 12, 11      # hosp_readmit-shaped workload
    # 16M-row chunks amortize fixed per-dispatch cost (honest-sync
    # methodology) and stay under the 2^24 exact-count chunk
    # cap shared with the einsum path.
    chunk = 16_000_000
    n_chunks = 4
    codes, labels = make_data(chunk, n_feat, n_bins, n_classes)
    pair_idx = np.array([(i, j) for i in range(n_feat) for j in range(i + 1, n_feat)], np.int32)
    ci, cj = pair_idx[:, 0], pair_idx[:, 1]

    # single source of the kernel-vs-einsum routing (and each path's
    # chain-scalar extractor): ops/pallas_hist.chunk_pipeline — the same
    # predicate MutualInformation.fit and e2e_pipeline use.  The kernel
    # path takes COLUMNAR [F, N] codes (round 4: the fused kernel streams
    # codes with no device transpose — the r3 per-chunk transpose+joint
    # prologue measured ~11 ms of the ~50 ms chunk); the one-time host
    # transpose below is setup, not steady-state work, exactly like the
    # one-time host→device upload.
    pipeline_step, chain_scalar, kernel_path = pallas_hist.chunk_pipeline(
        n_feat, n_bins, n_classes, ci, cj, columnar=True)
    if not kernel_path:
        raise RuntimeError(
            "bench.py measures the MXU count kernel; chunk_pipeline routed "
            "this shape to the einsum path")
    dcodes = jnp.asarray(np.ascontiguousarray(codes.T))
    dlabels = jnp.asarray(labels)

    # register THE program this bench dispatches (AOT cost analysis where
    # the backend supports it; shapes-only otherwise — never raises)
    bench_pkey = None
    if prof.enabled:
        bench_pkey = tel.CompileKeyMonitor.shape_key(dcodes, dlabels) + (
            "nb_mi", kernel_path)
        prof.observe(bench_pkey, site="bench.nb_mi",
                     lowerable=pipeline_step, args=(dcodes, dlabels))

    # Sync discipline: each pass chains the result into the next dispatch
    # and ends in ONE host fetch of a reduced scalar (device_sync) — the
    # barrier every timing in this repo uses.
    from avenir_tpu.utils.profiling import device_sync

    def timed_pass():
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            out = pipeline_step(dcodes, dlabels + bias)
            bias = chain_scalar(out)
        device_sync(out)
        return n_chunks * chunk / (time.perf_counter() - t0), out

    # Warm until steady state: one compile call plus one full untimed
    # chained pass, so no cold/compile pass leaks into the recorded spread
    # (round-2 verdict: the artifact carried a 5.6×-low first pass).
    device_sync(pipeline_step(dcodes, dlabels + jnp.int32(0)))
    timed_pass()

    # ALL recorded passes are reported and the headline is the MEDIAN:
    # dispatch timing jittered run-to-run by tens of percent, so the
    # per-pass list documents the spread and the median resists both
    # tails.  A fresh canary runs before EACH pass
    # (round-6): the r05 artifact's 158–377M rows/s within-run spread was
    # unattributable with only one pre-run canary — the per-pass list
    # separates rig contention (canary inflates with the slow passes)
    # from kernel regression (canary flat while passes sag).
    passes = []
    canary_per_pass = []
    with tracer.span("bench.nb_mi", attrs={"chunk": chunk,
                                           "n_chunks": n_chunks}):
        for i in range(5):
            canary_per_pass.append(matmul_canary_ms())
            tracer.event("canary", ms=round(canary_per_pass[-1], 2),
                         when=f"pass{i}")
            with tracer.span("bench.pass", attrs={"pass": i}) as sp:
                rate, out = timed_pass()
                sp.set("rows_per_sec", round(rate, 1))
            if bench_pkey is not None:
                # one timed pass = n_chunks chained dispatches of the one
                # program — record each so the profile table's per-dispatch
                # math (achieved = flops x dispatches / wall) is exact
                for _ in range(n_chunks):
                    prof.sample(bench_pkey, "bench.nb_mi",
                                chunk / rate)
            passes.append(rate)
    rows_per_sec = float(np.median(passes))

    # Canary-conditioned headline (round 7, closing the r05 verdict item):
    # the published band is anchored to rate-vs-canary PAIRS, not to a raw
    # band widened after every outlier.  A pass whose fresh canary exceeds
    # the healthy threshold (rig_canary interpretation contract: matmul
    # ≲ 7 ms; the contended regime reads 167–428 ms) indicts the RIG, so
    # it documents the spread but is excluded from the conditioned median
    # that regression comparisons use.  ONE constant shared with the
    # sentinel that consumes these fields (round-14): the producer and the
    # gate must agree on what a contended rig is.
    from avenir_tpu.telemetry.sentinel import CANARY_HEALTHY_MS
    canary_healthy_ms = CANARY_HEALTHY_MS
    clean = [r for c_ms, r in zip(canary_per_pass, passes)
             if c_ms <= canary_healthy_ms]
    # an all-contended run publishes NULL, never the contaminated raw
    # median — the conditioned field must only ever carry rig-clean rates
    rows_per_sec_clean = float(np.median(clean)) if clean else None

    # per-job finalization: host read-out of the reference-shaped tensors
    # from G (the jobs path does this once per job via counts_from_cooc)
    finalize_ms = 0.0
    if kernel_path:
        g_host = np.asarray(out, np.int64)
        t0 = time.perf_counter()
        fbc, pair = pallas_hist.counts_from_cooc(
            g_host, n_feat, n_bins, n_classes, ci, cj)
        finalize_ms = (time.perf_counter() - t0) * 1e3
        assert fbc.shape == (n_feat, n_bins, n_classes)
        assert pair.shape == (len(ci), n_bins, n_bins, n_classes)

    # numpy single-core baseline on a subsample
    sub = 200_000
    np_rps = numpy_reference_rows_per_sec(codes[:sub], labels[:sub], n_classes, n_bins)

    # roofline: the kernel is int8-MXU-bound (2·Wp² int8 MACs/row for the
    # XᵀX pass), NOT bandwidth-bound — the 48 B/row input stream is a few
    # GB/s at these rates, so both resources are reported
    from avenir_tpu.utils.roofline import chip_peaks, mfu_fields
    bytes_per_row = 4 * (n_feat + 1)
    mode, _, wp = pallas_hist.plan(n_feat, n_bins, n_classes)
    # the per-class modes perform C sequential wp×wp grams per block →
    # 2·C·wp² MACs per row; the joint modes do one wp×wp gram (2·wp²).
    per_row = (2 * n_classes * wp * wp if mode in ("cls", "clsb")
               else 2 * wp * wp)
    line = {
        "metric": "nb_mi_pipeline_throughput",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec/chip",
        "vs_baseline": round(rows_per_sec / np_rps, 2),
        "passes_rows_per_sec": [round(p, 1) for p in passes],
        "count_path": "pallas_cooc_int8_mxu",
        "finalize_ms": round(finalize_ms, 3),
        "canary_matmul_4096_bf16_ms": round(canary_ms, 2),
        "canary_per_pass_ms": [round(c, 2) for c in canary_per_pass],
        # the band's regression anchor: (canary ms, rows/s) per pass plus
        # the median over canary-clean passes only
        "rate_vs_canary": [[round(c, 2), round(p, 1)]
                           for c, p in zip(canary_per_pass, passes)],
        "value_canary_clean": (round(rows_per_sec_clean, 1)
                               if rows_per_sec_clean is not None else None),
        "canary_clean_passes": len(clean),
        "canary_healthy_threshold_ms": canary_healthy_ms,
        # the run's own timeline when AVENIR_TRACE_DIR opted in (else null
        # — and no journal file exists at all, the off-is-free contract)
        "trace_artifact": tracer.journal_path,
    }
    line.update(mfu_fields(
        bytes_moved=n_chunks * chunk * bytes_per_row,
        int8_ops=n_chunks * chunk * per_row,
        dt=n_chunks * chunk / rows_per_sec,
        peaks=chip_peaks()))

    # secondary driver metric (BASELINE.json): kNN QPS at 1M refs, embedded
    # as a NESTED object so the one-JSON-line driver contract holds. Runs
    # with the on-chip oracle verification; measured after the primary so
    # the primary never inherits kNN warmup state. Free memory first: the
    # NB+MI operands (codes+labels, ~3 GB over two copies) plus the kNN
    # reference set must not coexist on a 16 GB chip.
    del dcodes, dlabels
    from benchmarks.knn_qps import measure as knn_measure
    knn = knn_measure(verify=True, quick=True)
    line["knn"] = {kf: knn[kf] for kf in
                   ("value", "unit", "k", "batch", "n_refs",
                    "pipelined_passes_qps", "single_shot_qps",
                    "verified_vs_oracle", "mfu_pct",
                    "canary_matmul_4096_bf16_ms", "canary_knn_dot_ms")
                   if kf in knn}

    # per-family driver numbers (round-4 item 5): tree (exhaustive),
    # tree_binary (sklearn-comparable binary-threshold mode, round 6),
    # viterbi/lr/cramer at reduced shapes with measured single-core
    # baselines, so the bench artifact — not prose — carries
    # every family's value AND its vs_baseline ratio (same
    # chained-sync discipline); tree rows tag their selection path
    from benchmarks.family_bench import families_summary
    line["families"] = families_summary(passes=2)

    # GraftProf sentinel (round 14): gate this capture against the
    # previous artifact in-process, so every bench artifact carries its
    # own verdict (canary-flagged metrics are skipped with a verdict, not
    # compared — the value_canary_clean convention).  AVENIR_BENCH_BASELINE
    # points at the baseline artifact; a bands-less/missing baseline
    # yields a no_baseline verdict, never a failed capture.
    from avenir_tpu.telemetry import sentinel
    baseline_path = os.environ.get(
        "AVENIR_BENCH_BASELINE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BASELINE.json"))
    line["regression"] = sentinel.bench_verdict(line, baseline_path)
    # GraftFleet SLO gate (round 15): evaluate slo.<name>.* rules from
    # the AVENIR_SLO_CONF properties file over this capture's own
    # journal and embed the verdict next to the sentinel's — no rules
    # configured → "no_rules", rules without a journal (AVENIR_TRACE_DIR
    # unset) → "no_journal"; the capture publishes either way.
    from avenir_tpu.telemetry import slo as slo_mod
    line["slo"] = slo_mod.bench_verdict(tracer.journal_path,
                                        os.environ.get("AVENIR_SLO_CONF"))
    prof.flush()             # cumulative program.profile into the journal
    print(json.dumps(line))


if __name__ == "__main__":
    main()
