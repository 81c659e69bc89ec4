#!/usr/bin/env python
"""Per-level split-selection transport + hist-mode probe — makes the tree
family's RTT and TreeGraft claims reproducible artifacts instead of prose.

Round 5 root-caused tree induction's sub-baseline throughput to per-level
host round-trips: the host fetched the whole [F, B, K, C] level table
(`selection="host"`) and folded candidate splits there, paying one host
round trip per level (records deleted in PR 23, see git history).  Device-resident selection
(`selection="device"`, round 6) keeps histograms, scoring and the
per-node top-k on device and fetches only KB-sized chosen-split
descriptors.  Round 13 attacks the remaining on-device cost with
`tree.hist.mode`: `cumsum` scores every binary threshold from ONE
bin-axis prefix sum of the level table (a B× cut versus the per-split
segment einsum) and `subtract` additionally contracts only the smaller
children per level, deriving each largest sibling by exact parent-slice
subtraction (~half the gram work).  This probe measures:

- the full fit rate under `selection=host` and under `selection=device`
  for EVERY hist mode (direct / cumsum / subtract), on the binary-search
  candidate family (the sklearn-comparable frontier) — with the grown
  trees checked byte-identical across all paths (RuntimeError on
  violation, so `python -O` runs keep the guard);
- a per-level phase breakdown (table-build / score+select / partition
  wall ms) per hist mode, the attribution behind any rate delta;
- the two per-level transports in isolation: ``table_fetch_ms`` (the
  host path's per-level fetch) vs ``select_dispatch_plus_fetch_ms``
  (the device-selection dispatch + KB descriptor fetch that replaces it);
- a fresh matmul canary before each timed section (rig-state
  attribution, per the bench.py convention).

Sync discipline as everywhere in this repo: each timed region ends in
one host fetch.  Run:

  python -m benchmarks.tree_rtt_probe [--rows 1000000] [--passes 3]
      [--search binary|exhaustive]

Prints ONE JSON line.
"""

import argparse
import json
import time

import numpy as np


def measure(rows: int = 1_000_000, passes: int = 3,
            max_depth: int = 4, search: str = "binary") -> dict:
    import jax
    import jax.numpy as jnp

    from avenir_tpu.models import tree as dtree
    from avenir_tpu.utils.rig_canary import matmul_canary_ms
    from benchmarks.family_bench import _tree_data

    ds, is_cat = _tree_data(rows)
    canaries = {}

    def fit_rate(selection: str, hist_mode: str = "direct"):
        builder = dtree.DecisionTree(algorithm="entropy", max_depth=max_depth,
                                     max_split=3, selection=selection,
                                     split_search=search, hist_mode=hist_mode)
        builder.fit(ds, is_categorical=is_cat)          # compile + warm
        canaries[f"{selection}.{hist_mode}"] = round(matmul_canary_ms(), 2)
        vals = []
        for _ in range(passes):
            t0 = time.perf_counter()
            model = builder.fit(ds, is_categorical=is_cat)
            vals.append(rows / (time.perf_counter() - t0))
        return float(np.median(vals)), model

    def phase_breakdown(hist_mode: str):
        probe = dtree.DecisionTree(algorithm="entropy", max_depth=max_depth,
                                   max_split=3, split_search=search,
                                   hist_mode=hist_mode,
                                   collect_phase_stats=True)
        probe.fit(ds, is_categorical=is_cat)
        return probe.level_stats

    host_rate, model_host = fit_rate("host")
    oracle = model_host.to_string()
    # cumsum only engages on an all-binary candidate family — under
    # exhaustive search it would be a re-measurement of direct published
    # under the wrong label, so only the modes that actually differ run
    # (dtree.HIST_MODES is the canonical mode list: a mode added there
    # is automatically covered here)
    modes = (dtree.HIST_MODES if search == "binary"
             else tuple(m for m in dtree.HIST_MODES if m != "cumsum"))
    mode_rates = {}
    mode_phases = {}
    for mode in modes:
        rate, model_dev = fit_rate("device", mode)
        if model_dev.to_string() != oracle:
            # RuntimeError, not assert: the byte-identity oracle must
            # survive `python -O` — a silently divergent fast path would
            # publish a rate for a DIFFERENT tree
            raise RuntimeError(
                f"hist_mode={mode!r} tree diverged from the "
                f"selection='host' oracle (search={search!r})")
        mode_rates[mode] = round(rate, 1)
        mode_phases[mode] = phase_breakdown(mode)

    # isolate the two per-level transports on the root level table
    all_splits = dtree.candidate_splits_for(ds, search, 3, is_cat, 128)
    flat = dtree.flatten_splits(all_splits, ds.max_bins, 128)
    c = ds.num_classes
    table_dev = dtree.node_bin_class_counts(
        jnp.asarray(ds.codes), jnp.zeros(ds.num_rows, jnp.int32),
        jnp.asarray(ds.labels), 1, c, ds.max_bins)
    allow = jnp.asarray(flat.allow_vector(range(ds.num_binned)))

    def select(binary: bool):
        return jax.device_get(dtree._device_select_splits(
            table_dev, flat.seg_tab_dev, flat.attr_dev, flat.nseg_dev,
            allow, flat.thr_dev if binary else None, algorithm="entropy",
            gmax=flat.gmax, top_k=1, chunk=flat.chunk, binary=binary))

    np.asarray(table_dev)                               # warm the fetch path
    select(False)
    cum_ok = flat.all_binary
    if cum_ok:
        select(True)

    def med_ms(fn):
        vals = []
        for _ in range(max(passes, 3)):
            t0 = time.perf_counter()
            fn()
            vals.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(vals)), 3)

    table_fetch_ms = med_ms(lambda: np.asarray(table_dev))
    select_fetch_ms = med_ms(lambda: select(False))
    select_cum_ms = med_ms(lambda: select(True)) if cum_ok else None

    f, b = ds.num_binned, ds.max_bins
    return {
        "metric": "tree_split_selection_rtt_probe",
        "n_rows": rows, "max_depth": max_depth, "split_search": search,
        "table_shape_fbkc": [f, b, 1, c],
        "table_bytes": int(f * b * 1 * c * 4),
        "descriptor_bytes": int(4 + 4 + flat.gmax * c * 4),   # per node·pick
        "host_selection_rows_per_sec": round(host_rate, 1),
        "device_selection_rows_per_sec": dict(mode_rates),
        "device_vs_host": {m: round(mode_rates[m] / host_rate, 2)
                           for m in mode_rates},
        "level_phases_ms": mode_phases,
        "byte_identical_to_host_oracle": True,   # RuntimeError otherwise
        "canary_matmul_4096_bf16_ms": canaries,
        "table_fetch_ms": table_fetch_ms,
        "select_dispatch_plus_fetch_ms": select_fetch_ms,
        "select_cumsum_dispatch_plus_fetch_ms": select_cum_ms,
        "note": "table_fetch_ms is what selection=host pays PER LEVEL on "
                "top of scoring; select_dispatch_plus_fetch_ms replaces "
                "it (device histograms+scores+top-k, KB descriptor "
                "fetch); the cumsum variant scores every binary "
                "threshold from one bin-axis prefix sum of the table",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--max-depth", type=int, default=4)
    ap.add_argument("--search", choices=["binary", "exhaustive"],
                    default="binary")
    args = ap.parse_args()
    print(json.dumps(measure(args.rows, args.passes, args.max_depth,
                             args.search)))


if __name__ == "__main__":
    main()
