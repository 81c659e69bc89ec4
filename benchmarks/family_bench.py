#!/usr/bin/env python
"""Per-family device benchmarks — one measured number AND one measured
single-core baseline for every workload family the framework ships
(round-3 item 6 "no family without a number"; round-4 item 4 "no family
number without a baseline anchor").

Families and shapes (reference-derived):
- ``tree``     decision-tree induction on the retarget shape
               (abandoned-cart retargeting, ``resource/retarget.py`` /
               ``tree/DataPartitioner.java`` two-jobs-per-level ↔ the
               in-memory frontier here); rows/s = rows / full-fit wall.
               Baseline: sklearn ``DecisionTreeClassifier.fit`` (same
               depth cap) on a subsample, single core.  Exhaustive
               multi-way search (the reference's semantics).
- ``tree_binary`` the same fit in ``split.search=binary`` mode —
               sorted-threshold binary splits over ordinal codes, the
               SAME candidate family sklearn scans, so its vs_baseline
               is the apples-to-apples ratio (device-resident split
               selection on both tree rows).  Runs
               ``tree.hist.mode=subtract`` by default (round 13
               TreeGraft: cumulative-histogram scoring +
               sibling-subtraction level tables, byte-identical trees);
               every tree row carries a ``hist_mode`` tag, a fresh
               matmul canary per pass, and a per-level phase breakdown
               so captures stay attributable.
- ``viterbi``  batch Viterbi decode, email-marketing-tutorial shape
               (``resource/tutorial_opt_email_marketing.txt:15-18``):
               80k sequences × 210 observations; seqs/s.  Baseline: the
               classic per-sequence numpy loop (init/iterate/backtrack,
               ``markov/ViterbiDecoder.java:66-143``).
- ``lr``       logistic-regression gradient iterations/s
               (``regress/LogisticRegressionJob.java:279-289`` ran ONE
               MR job per iteration; here one chained device step).
               Baseline: the identical full-batch numpy gradient step at
               the SAME shape, single core.
- ``cramer``   Cramér-index contingency aggregation rows/s
               (``explore/CramerCorrelation.java``).  Baseline:
               ``np.add.at`` scatter into all pair tables on a subsample.
- ``wordcount``host tokenize+count tokens/s (``text/WordCounter.java``).
               Baseline: the same tokenizer feeding ``collections.Counter``
               — BOTH run on host, so the honest ratio is ~1: this family
               has no device compute and says so instead of implying a
               TPU win.

Baselines are median-of-3 like bench.py's numpy NB+MI baseline, with
buffers hoisted out of the timed region.  Sync discipline for the device
side: chain dispatches, fetch once.
Run ONE family per process:

  python -m benchmarks.family_bench --family viterbi
"""

import argparse
import json
import time

import numpy as np


def _median3(fn) -> float:
    vals = []
    for _ in range(3):
        t0 = time.perf_counter()
        n = fn()
        vals.append(n / (time.perf_counter() - t0))
    return float(np.median(vals))


# ---------------------------------------------------------------------------
# tree
# ---------------------------------------------------------------------------

def _tree_data(n: int):
    from avenir_tpu.core.encoding import DatasetEncoder
    from avenir_tpu.core.schema import FeatureSchema
    from avenir_tpu.datagen.retarget import (RETARGET_SCHEMA_JSON,
                                             generate_retarget)

    schema = FeatureSchema.from_json(RETARGET_SCHEMA_JSON)
    rows = generate_retarget(n, seed=9)
    enc = DatasetEncoder(schema)
    ds = enc.fit_transform(rows)
    is_cat = [f.is_categorical for f in schema.binned_feature_fields]
    return ds, is_cat


def bench_tree(passes: int, n: int = 2_000_000, baseline_sub: int = 100_000,
               search: str = "exhaustive", hist_mode: str = "direct"):
    from avenir_tpu.models import tree as dtree
    from avenir_tpu.utils.rig_canary import matmul_canary_ms

    ds, is_cat = _tree_data(n)
    builder = dtree.DecisionTree(algorithm="entropy", max_depth=4,
                                 max_split=3, split_search=search,
                                 hist_mode=hist_mode)
    vals = []
    canary_per_pass = []
    model = builder.fit(ds, is_categorical=is_cat)       # compile + warm
    for _ in range(passes):
        # rig-state canary BEFORE each tree pass (per the bench.py
        # convention): a slow pass with an inflated canary is rig
        # contention, a slow pass with a flat canary is a tree regression
        # — the attribution the hist-mode comparison needs
        canary_per_pass.append(matmul_canary_ms())
        t0 = time.perf_counter()
        model = builder.fit(ds, is_categorical=is_cat)
        vals.append(n / (time.perf_counter() - t0))
    # one extra instrumented fit for the per-level phase breakdown
    # (table-build / score+select / partition wall ms) — separate from the
    # timed passes because honest phase walls need a sync per phase
    probe = dtree.DecisionTree(algorithm="entropy", max_depth=4,
                               max_split=3, split_search=search,
                               hist_mode=hist_mode, collect_phase_stats=True)
    probe.fit(ds, is_categorical=is_cat)
    if search == "binary":
        note = ("apples-to-apples: sorted-threshold binary splits on "
                "ordinal codes — the SAME candidate family sklearn's "
                "DecisionTreeClassifier scans; selection runs on device")
        metric = "tree_binary_induction_rows_per_sec"
    else:
        note = ("this family evaluates the reference's EXHAUSTIVE "
                "multi-way/categorical candidate-split search "
                "(ClassPartitionGenerator.java:280-432) which sklearn "
                "does not perform; tree_binary is the apples-to-apples "
                "row")
        metric = "tree_induction_rows_per_sec"
    return {"metric": metric, "unit": "rows/sec/chip",
            "n_rows": n, "max_depth": 4, "nodes": len(model.nodes),
            "shape": "retarget", "split_search": search,
            "selection_path": builder.selection,
            "hist_mode": hist_mode,
            "canary_per_pass_ms": [round(c, 2) for c in canary_per_pass],
            "level_phases": probe.level_stats,
            "baseline_rows_per_sec": round(baseline_tree(ds, baseline_sub), 1),
            "baseline": f"sklearn DecisionTreeClassifier.fit depth<=4 on "
                        f"{baseline_sub} rows, single core",
            "note": note}, vals


def bench_tree_binary(passes: int, n: int = 2_000_000,
                      baseline_sub: int = 100_000,
                      hist_mode: str = "subtract"):
    """`split.search=binary` benchmarked against the same sklearn anchor —
    both sides search sorted-threshold binary splits over ordinal codes.
    Defaults to `tree.hist.mode=subtract` (cumulative-histogram scoring +
    sibling-subtraction level tables — byte-identical trees, the
    TreeGraft fast path this row exists to measure; the `hist_mode` tag
    keeps every capture attributable)."""
    return bench_tree(passes, n, baseline_sub, search="binary",
                      hist_mode=hist_mode)


def baseline_tree(ds, sub: int) -> float:
    """Single-core sklearn fit rate on the same encoded rows (int codes as
    ordinal features — the standard one-machine counterpart; the reference
    itself had no single-core path, only MR jobs per level).  Returns 0.0
    when sklearn is absent (optional anchor — the expensive device
    measurement must never be lost to a missing baseline dep)."""
    try:
        from sklearn.tree import DecisionTreeClassifier
    except ImportError:                  # pragma: no cover
        return 0.0

    x = np.asarray(ds.codes[:sub], np.float32)
    y = np.asarray(ds.labels[:sub])
    return _median3(lambda: (DecisionTreeClassifier(
        max_depth=4, criterion="entropy").fit(x, y), sub)[1])


# ---------------------------------------------------------------------------
# viterbi
# ---------------------------------------------------------------------------

def _viterbi_model(s: int = 6, o: int = 12):
    rng = np.random.default_rng(0)
    log_a = np.log(rng.dirichlet(np.ones(s), size=s)).astype(np.float32)
    log_b = np.log(rng.dirichlet(np.ones(o), size=s)).astype(np.float32)
    log_pi = np.log(rng.dirichlet(np.ones(s))).astype(np.float32)
    return log_a, log_b, log_pi


def bench_viterbi(passes: int, r: int = 80_000, t: int = 210,
                  baseline_sub: int = 200):
    import jax
    import jax.numpy as jnp

    from avenir_tpu.models import markov as mk

    s, o = 6, 12                                         # email-mktg shape
    rng = np.random.default_rng(0)
    la, lb, lpi = _viterbi_model(s, o)
    log_a, log_b, log_pi = (jnp.asarray(a) for a in (la, lb, lpi))
    obs_np = rng.integers(0, o, size=(r, t), dtype=np.int32)
    obs = jnp.asarray(obs_np)
    decode = jax.jit(mk._viterbi_batch)
    out = decode(log_a, log_b, log_pi, obs)
    np.asarray(out[0, 0])                                # compile + warm
    vals = []
    for _ in range(passes):
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for _ in range(3):                               # chained dispatches
            out = decode(log_a, log_b, log_pi, obs + bias * 0)
            bias = out[0, 0] * 0
        np.asarray(out[0, 0])
        vals.append(3 * r / (time.perf_counter() - t0))
    base = baseline_viterbi(la, lb, lpi, obs_np[:baseline_sub])
    return {"metric": "viterbi_decode_seqs_per_sec", "unit": "seqs/sec/chip",
            "n_seqs": r, "seq_len": t, "n_states": s,
            "shape": "email_marketing_80kx210",
            "baseline_seqs_per_sec": round(base, 1),
            "baseline": f"per-sequence numpy Viterbi loop on {baseline_sub} "
                        f"seqs, single core"}, vals


def baseline_viterbi(log_a, log_b, log_pi, obs) -> float:
    """Classic per-sequence decode: numpy vectorized over states only —
    the per-record loop shape of ViterbiDecoder.java:66-143."""
    def run():
        for o in obs:
            delta = log_pi + log_b[:, o[0]]
            ptrs = np.empty((len(o) - 1, len(log_pi)), np.int64)
            for i in range(1, len(o)):
                cand = delta[:, None] + log_a
                ptrs[i - 1] = np.argmax(cand, axis=0)
                delta = cand[ptrs[i - 1], np.arange(len(log_pi))] \
                    + log_b[:, o[i]]
            state = int(np.argmax(delta))
            for i in range(len(o) - 2, -1, -1):          # backtrack
                state = int(ptrs[i][state])
        return len(obs)

    return _median3(run)


# ---------------------------------------------------------------------------
# lr
# ---------------------------------------------------------------------------

def bench_lr(passes: int, n: int = 4_000_000, d: int = 24, iters: int = 20,
             baseline_iters: int = 3):
    import jax
    import jax.numpy as jnp

    from avenir_tpu.models import logistic as lg

    rng = np.random.default_rng(0)
    x_np = rng.random((n, d), np.float32)
    y_np = (rng.random(n) < 0.5).astype(np.float32)
    x = jnp.asarray(x_np)
    y = jnp.asarray(y_np)
    w = jnp.zeros(d, jnp.float32)
    step = jax.jit(lg._grad_step)
    nn = jnp.float32(n)
    w1 = step(w, x, y, nn, jnp.float32(0.5), jnp.float32(0.01))
    np.asarray(w1[0])                                    # compile + warm
    vals = []
    for _ in range(passes):
        wi = w
        t0 = time.perf_counter()
        for _ in range(iters):                           # natural chain via w
            wi = step(wi, x, y, nn, jnp.float32(0.5), jnp.float32(0.01))
        np.asarray(wi[0])
        vals.append(iters / (time.perf_counter() - t0))
    base = baseline_lr(x_np, y_np, baseline_iters)
    return {"metric": "lr_iterations_per_sec", "unit": "iters/sec/chip",
            "n_rows": n, "n_features": d,
            "baseline_iters_per_sec": round(base, 3),
            "baseline": f"identical full-batch numpy gradient step at the "
                        f"same [{n}, {d}] shape, single core",
            "note": "one iteration == one full-batch gradient step == one "
                    "MR job of the reference"}, vals


def baseline_lr(x: np.ndarray, y: np.ndarray, iters: int) -> float:
    """The SAME full-batch gradient step in single-core numpy at the same
    shape — like-for-like per-iteration cost (the reference additionally
    paid a whole MR job submission per iteration, which this baseline
    charitably omits)."""
    w = np.zeros(x.shape[1], np.float32)

    def run():
        nonlocal w
        for _ in range(iters):
            p = 1.0 / (1.0 + np.exp(-(x @ w)))
            w = w + np.float32(0.5) * ((x.T @ (y - p)) / len(x)
                                       - np.float32(0.01) * w)
        return iters

    return _median3(run)


# ---------------------------------------------------------------------------
# cramer
# ---------------------------------------------------------------------------

def bench_cramer(passes: int, n: int = 16_000_000, f: int = 10, b: int = 20,
                 baseline_sub: int = 200_000):
    import jax.numpy as jnp

    from avenir_tpu.ops import pallas_hist

    rng = np.random.default_rng(0)
    codes_np = rng.integers(0, b, size=(f, n), dtype=np.int32)
    codes_t = jnp.asarray(codes_np)
    zeros = jnp.zeros(n, jnp.int32)
    kernel = pallas_hist.use_kernel(f, b, 1)

    def step(bias):
        # all [B, B] contingency tables at once: the one-class gram —
        # exactly CategoricalCorrelation.fit's single-TPU fast path
        return pallas_hist.cooc_counts_cols(codes_t, zeros + bias, b, 1)

    out = step(jnp.int32(0))
    np.asarray(out[0, 0])
    vals = []
    for _ in range(passes):
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for _ in range(3):
            out = step(bias)
            bias = (out[0, 0] * 0).astype(jnp.int32)
        np.asarray(out[0, 0])
        vals.append(3 * n / (time.perf_counter() - t0))
    base = baseline_cramer(codes_np[:, :baseline_sub], b)
    return {"metric": "cramer_rows_per_sec", "unit": "rows/sec/chip",
            "n_rows": n, "n_features": f, "cardinality": b,
            "n_pairs": f * (f - 1) // 2, "kernel_path": bool(kernel),
            "plan": list(pallas_hist.plan(f, b, 1)),
            "baseline_rows_per_sec": round(base, 1),
            "baseline": f"np.add.at contingency scatter over all "
                        f"{f * (f - 1) // 2} pairs on {baseline_sub} rows, "
                        f"single core",
            "note": "rides the int8-only fmaj gram since round 7: plan() "
                    "routes the one-class shape to the broadcast-expand "
                    "layout that carries NB+MI (wp 384 vs jmaj's 256 — the "
                    "jmaj int32 expand, not the dot, was the r05 wall)"}, vals


def baseline_cramer(codes: np.ndarray, b: int) -> float:
    """Single-core np.add.at scatter into every pair's [B, B] table —
    the per-record hashmap-increment cost model of
    CramerCorrelation.java:161-182 (buffer hoisted)."""
    f, n = codes.shape
    pairs = [(i, j) for i in range(f) for j in range(i + 1, f)]
    buf = np.zeros((b, b))

    def run():
        for i, j in pairs:
            np.add.at(buf, (codes[i], codes[j]), 1)
        return n

    return _median3(run)


# ---------------------------------------------------------------------------
# wordcount
# ---------------------------------------------------------------------------

def bench_wordcount(passes: int):
    from avenir_tpu.text.analyzer import tokenize

    rng = np.random.default_rng(0)
    vocab = [f"word{i}" for i in range(5000)]
    lines = [" ".join(rng.choice(vocab, size=12)) for _ in range(20_000)]
    n_tokens = sum(len(tokenize(s)) for s in lines)
    vals = []
    for _ in range(passes):
        t0 = time.perf_counter()
        counts: dict = {}
        for s in lines:
            for tok in tokenize(s):
                counts[tok] = counts.get(tok, 0) + 1
        vals.append(n_tokens / (time.perf_counter() - t0))
    # baseline: the same tokenizer into collections.Counter — both sides
    # are host code, so the ratio is ~1 BY DESIGN: this family has no
    # device compute and the number says so honestly
    from collections import Counter

    def run():
        c: Counter = Counter()
        for s in lines:
            c.update(tokenize(s))
        return n_tokens

    base = _median3(run)
    return {"metric": "wordcount_tokens_per_sec", "unit": "tokens/sec",
            "n_tokens": n_tokens,
            "baseline_tokens_per_sec": round(base, 1),
            "baseline": "same tokenizer into collections.Counter, single "
                        "core (host-vs-host: ratio ~1 by design)",
            "note": "HOST-bound (tokenizer); 1-core dev rig number is a "
                    "lower bound, scales with host cores"}, vals


FAMILIES = {"tree": bench_tree, "tree_binary": bench_tree_binary,
            "viterbi": bench_viterbi, "lr": bench_lr,
            "cramer": bench_cramer, "wordcount": bench_wordcount}

# reduced shapes for the driver artifact (bench.py embeds these; ~10 s
# budget per family including its baseline, same chained-sync discipline)
REDUCED = {
    # tree keeps 1M rows: per-level dispatch overhead amortizes over N,
    # and at 300k rows it dominated (447k rows/s where the 2M shape
    # measures 1.36M — same dispatch-floor distortion as LR's); with
    # device-resident selection the per-level cost is one dispatch + a
    # KB fetch instead of the full-table fetch + host fold
    "tree": dict(n=1_000_000, baseline_sub=50_000),
    "tree_binary": dict(n=1_000_000, baseline_sub=50_000),
    "viterbi": dict(r=16_000, t=210, baseline_sub=100),
    # LR keeps the full 4M-row shape: at 1M rows the ~11 ms device
    # dispatch floor dominates and the ratio collapses to ~1.2× while the
    # representative full-batch shape measures ~3-5× (upload cost is
    # one-time setup, not per-pass)
    "lr": dict(n=4_000_000, d=24, iters=10, baseline_iters=2),
    "cramer": dict(n=4_000_000, baseline_sub=100_000),
}


def family_line(name: str, passes: int = 4, reduced: bool = False) -> dict:
    """One family's JSON-ready dict: median value, pass list, measured
    single-core baseline and the vs_baseline ratio."""
    kwargs = REDUCED.get(name, {}) if reduced else {}
    line, vals = FAMILIES[name](passes, **kwargs)
    line["value"] = round(float(np.median(vals)), 1)
    line["passes"] = [round(v, 1) for v in vals]
    base_key = next((k for k in line if k.startswith("baseline_")
                     and k.endswith("_per_sec")), None)
    if base_key and line[base_key]:
        line["vs_baseline"] = round(line["value"] / line[base_key], 2)
    return line


def families_summary(passes: int = 2) -> dict:
    """Compact per-family object for bench.py's driver artifact: reduced
    shapes, value + vs_baseline + baseline rate per family (wordcount is
    excluded — host-bound, ratio ~1 by design, see bench_wordcount).
    ``tree`` is the exhaustive multi-way search, ``tree_binary`` the
    sklearn-comparable binary-threshold mode; both tag the selection
    path so artifacts attribute gains to device-resident selection."""
    out = {}
    for name in ("tree", "tree_binary", "viterbi", "lr", "cramer"):
        line = family_line(name, passes=passes, reduced=True)
        # level_phases rides into the driver artifact: the tree rows pay
        # one instrumented fit for it, so dropping it here would waste
        # that fit — and the per-level table/select/partition ms is the
        # attribution the hist-mode comparison needs
        out[name] = {k: line[k] for k in
                     ("metric", "value", "unit", "vs_baseline", "note",
                      "selection_path", "split_search", "hist_mode",
                      "canary_per_pass_ms", "level_phases")
                     if k in line}
        bk = next((k for k in line if k.startswith("baseline_")
                   and k.endswith("_per_sec")), None)
        if bk:
            out[name][bk] = line[bk]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=sorted(FAMILIES), required=True)
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="driver-artifact shapes (bench.py's families object)")
    args = ap.parse_args()
    print(json.dumps(family_line(args.family, args.passes, args.reduced)))


if __name__ == "__main__":
    main()
