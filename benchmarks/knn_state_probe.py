#!/usr/bin/env python
"""kNN slow-mode bisection (round 5): does running the NB+MI pipeline
before the kNN measurement change kNN QPS?

Round 4's driver artifact captured a 103k QPS kNN median where round 3's
driver captured 163-187k on identical kernel code.  In bench.py the kNN
measurement is EMBEDDED: it runs after the ~1.4 GB NB+MI operands are
allocated, used and freed, so the reference set is uploaded into a
post-churn HBM state — a live fragmentation/tiling hypothesis.  This probe
isolates that variable in a fresh process per condition:

- ``--mode fresh``      : canaries + kNN measurement only (standalone).
- ``--mode after_nbmi`` : replicate bench.py's sequence first — upload the
  16M-row codes/labels, run two chained NB+MI kernel passes, free the
  operands — then the identical kNN measurement.

Each run prints one JSON line with the matmul canary (rig state), the bare
distance-dot canary against the actual packed reference buffer (kernel
lower bound), and the pipelined pass list.  Run interleaved
(fresh, after_nbmi, fresh, after_nbmi, ...) so the ±20% rig drift
averages out of the comparison:

    for m in fresh after_nbmi fresh after_nbmi; do
        python benchmarks/knn_state_probe.py --mode $m; done

Interpretation: if after_nbmi's QPS tracks fresh's (given matching
canaries), the round-4 collapse was rig-side; if after_nbmi is
consistently slower with matching matmul canaries, the memory-state
hypothesis is confirmed and the dot canary says whether the dot or the
extraction passes absorb it.
"""

import argparse
import json
import time

import numpy as np


def run_nbmi_phase():
    """bench.py's NB+MI sequence at full operand scale: upload, two
    chained kernel passes, free. Returns the phase's rows/sec for context."""
    import jax.numpy as jnp
    from avenir_tpu.ops import pallas_hist
    from avenir_tpu.utils.profiling import device_sync

    n_classes, n_bins, n_feat = 2, 12, 11
    chunk = 16_000_000
    rng = np.random.default_rng(0)
    codes = rng.integers(0, n_bins, size=(chunk, n_feat), dtype=np.int32)
    labels = rng.integers(0, n_classes, size=chunk, dtype=np.int32)
    pair_idx = np.array([(i, j) for i in range(n_feat)
                         for j in range(i + 1, n_feat)], np.int32)
    step, chain_scalar, kernel_path = pallas_hist.chunk_pipeline(
        n_feat, n_bins, n_classes, pair_idx[:, 0], pair_idx[:, 1],
        columnar=True)
    dcodes = jnp.asarray(np.ascontiguousarray(codes.T)) if kernel_path \
        else jnp.asarray(codes)
    dlabels = jnp.asarray(labels)
    device_sync(step(dcodes, dlabels + jnp.int32(0)))
    t0 = time.perf_counter()
    bias = jnp.int32(0)
    for _ in range(2):
        out = step(dcodes, dlabels + bias)
        bias = chain_scalar(out)
    device_sync(out)
    rate = 2 * chunk / (time.perf_counter() - t0)
    del dcodes, dlabels, out
    return float(rate), bool(kernel_path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["fresh", "after_nbmi"], required=True)
    args = ap.parse_args()

    line = {"probe": "knn_state", "mode": args.mode}
    if args.mode == "after_nbmi":
        nbmi_rate, kp = run_nbmi_phase()
        line["nbmi_rows_per_sec"] = round(nbmi_rate, 1)
        line["nbmi_kernel_path"] = kp

    from benchmarks.knn_qps import measure
    knn = measure(verify=False, quick=True)
    for kf in ("value", "pipelined_passes_qps", "single_shot_qps",
               "canary_matmul_4096_bf16_ms", "canary_knn_dot_ms"):
        line[kf] = knn[kf]
    print(json.dumps(line))


if __name__ == "__main__":
    main()
