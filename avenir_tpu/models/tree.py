"""Decision-tree induction — candidate-split search + frontier growth.

Capability parity with the reference's tree stack:

- candidate-split enumeration (explore/ClassPartitionGenerator.java: numeric =
  all increasing split-point sets on the bucketWidth grid with up to
  maxSplit−1 points :280-311; categorical = all partitions of the value set
  into 2..maxSplit groups :318-432);
- attribute-selection strategies all / userSpecified / random-k
  (Random-Forest-style) (:160-196);
- split quality from per-split segment×class histograms with algorithms
  entropy / gini (gain ratio, util/AttributeSplitStat.java:85-93,179-218),
  hellingerDistance (binary class, :228-284) and classConfidenceRatio
  (:291-339); dataset-level info content for the root
  (util/InfoContentStat.java:55-85);
- tree growth (tree/SplitGenerator.java + tree/DataPartitioner.java): best or
  random-from-top-N split selection (:181-185) and recursive partitioning.

TPU re-design: the reference runs TWO MapReduce jobs per tree node per level
and encodes the tree as an HDFS directory layout (DataPartitioner.java:114-148).
Here the whole frontier grows in memory: records carry a node-id vector, every
candidate split of every active node is scored in one batched einsum
([S, G, K, C] = splits × segments × nodes × classes) per attribute chunk, and
partitioning is a vectorized segment-table gather — no data movement at all.
Prediction compiles the tree into flat arrays (attr / segment-table / child /
leaf-distribution) walked by a fixed-depth jitted gather loop.
"""

from __future__ import annotations

import functools
import json
import time
from dataclasses import dataclass, field as dc_field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from avenir_tpu.core.encoding import EncodedDataset
from avenir_tpu.ops import agg, info
from avenir_tpu.utils.metrics import ConfusionMatrix, Counters

ALGORITHMS = ("entropy", "giniIndex", "hellingerDistance", "classConfidenceRatio")

# level-table / split-histogram strategy (``tree.hist.mode``):
# ``direct``   — today's path: one full contraction per level, per-split
#                histograms via the segment einsum;
# ``cumsum``   — binary-threshold candidates score from ONE bin-axis
#                cumsum of the level table (info.binary_split_histograms;
#                a B× cut in per-level scoring work); non-binary
#                candidate sets keep the einsum;
# ``subtract`` — cumsum scoring PLUS sibling-subtraction level tables:
#                per level only the smaller children of each split are
#                contracted (through the same int8-MXU cross-gram path
#                when applicable) and each largest sibling is derived by
#                exact parent-slice subtraction — roughly halving the
#                per-level gram work for binary trees.
# Every mode grows trees byte-identical to the ``selection="host"``
# oracle: counts are exact integer folds either way and tie-breaking is
# unchanged (asserted across all four algorithms in tests/test_tree.py).
HIST_MODES = ("direct", "cumsum", "subtract")


# ---------------------------------------------------------------------------
# candidate splits
# ---------------------------------------------------------------------------

@dataclass
class CandidateSplit:
    """A way to segment one binned attribute.

    ``seg_of_bin[b]`` maps the attribute's bin code to a segment index —
    the device-friendly compilation of the reference's
    AttributeSplitHandler.Split containers (IntegerSplit: segment = first
    split point ≥ value :135-168; CategoricalSplit: group membership
    :174-234). ``key`` is a human-readable split id in the same spirit as the
    reference's serialized split keys.
    """

    attr: int
    kind: str                    # "numeric" | "categorical"
    seg_of_bin: np.ndarray       # [B] int32
    num_segments: int
    key: str


def enumerate_numeric_splits(
    n_bins: int, max_split: int, pad_bins: int, max_candidates: int = 512,
) -> List[Tuple[Tuple[int, ...], np.ndarray]]:
    """All increasing threshold tuples (1..max_split−1 points) on the bin grid.

    A threshold t means codes < t go left of that point; k thresholds make
    k+1 segments. Mirrors createNumPartitions' recursion over the bucketWidth
    grid (thresholds here are bin indices; bin b ≡ grid value offset+b)."""
    out: List[Tuple[Tuple[int, ...], np.ndarray]] = []

    def seg_map(thresholds: Tuple[int, ...]) -> np.ndarray:
        segs = np.zeros(pad_bins, np.int32)
        arange = np.arange(pad_bins)
        for t in thresholds:
            segs += (arange >= t).astype(np.int32)
        return segs

    def rec(prev: Tuple[int, ...]):
        if len(out) >= max_candidates or len(prev) >= max_split - 1:
            return
        start = (prev[-1] + 1) if prev else 1
        for t in range(start, n_bins):
            cur = prev + (t,)
            out.append((cur, seg_map(cur)))
            if len(out) >= max_candidates:
                return
            rec(cur)

    rec(())
    return out


def enumerate_categorical_partitions(
    n_values: int, max_split: int, pad_bins: int, max_candidates: int = 512,
) -> List[Tuple[Tuple[int, ...], np.ndarray]]:
    """All partitions of value indices into 2..max_split groups, via
    restricted-growth strings (canonical set-partition enumeration — the
    counterpart of createCatPartitions' group shuffling)."""
    out: List[Tuple[Tuple[int, ...], np.ndarray]] = []

    def rec(prefix: List[int], used: int):
        if len(out) >= max_candidates:
            return
        if len(prefix) == n_values:
            groups = used + 1
            if 2 <= groups <= max_split:
                segs = np.zeros(pad_bins, np.int32)
                segs[:n_values] = prefix
                # OOV / padding bins fall into segment 0
                out.append((tuple(prefix), segs))
            return
        for g in range(min(used + 1, max_split - 1) + 1):
            rec(prefix + [g], max(used, g))

    rec([0], 0)   # first value always group 0 (canonical form)
    return out


def generate_candidate_splits(
    ds: EncodedDataset,
    max_split: int = 3,
    is_categorical: Optional[Sequence[bool]] = None,
    max_candidates_per_attr: int = 256,
    attrs: Optional[Sequence[int]] = None,
) -> Dict[int, List[CandidateSplit]]:
    """Enumerate splits for each binned attribute (host-side, tiny)."""
    b = ds.max_bins
    result: Dict[int, List[CandidateSplit]] = {}
    attr_list = list(attrs) if attrs is not None else list(range(ds.num_binned))
    for a in attr_list:
        nb = int(ds.n_bins[a])
        cat = bool(is_categorical[a]) if is_categorical is not None else True
        splits: List[CandidateSplit] = []
        if cat:
            # exclude the reserved OOV slot from the partitioned value set
            for prefix, segs in enumerate_categorical_partitions(
                    max(nb - 1, 1), max_split, b, max_candidates_per_attr):
                key = f"attr{a}:cat:{''.join(map(str, prefix))}"
                splits.append(CandidateSplit(a, "categorical", segs,
                                             int(segs[:max(nb - 1, 1)].max()) + 1, key))
        else:
            for thresholds, segs in enumerate_numeric_splits(
                    nb, max_split, b, max_candidates_per_attr):
                key = f"attr{a}:num:{','.join(map(str, thresholds))}"
                splits.append(CandidateSplit(a, "numeric", segs, len(thresholds) + 1, key))
        result[a] = splits
    return result


def candidate_splits_for(
    ds: EncodedDataset,
    split_search: str,
    max_split: int,
    is_categorical: Optional[Sequence[bool]],
    max_candidates_per_attr: int = 256,
    attrs: Optional[Sequence[int]] = None,
) -> Dict[int, List[CandidateSplit]]:
    """The ONE mapping from ``split_search`` to a candidate family, shared
    by DecisionTree.fit and the ClassPartitionGenerator / DataPartitioner
    jobs — the same enumeration must produce the same keys everywhere or
    DataPartitioner's split-key lookup breaks.  ``binary`` = one sorted
    threshold on the bin-code grid for EVERY attribute (ordinal
    semantics, sklearn's candidate family); ``exhaustive`` = the
    reference's multi-way numeric/categorical enumeration."""
    if split_search == "binary":
        return generate_candidate_splits(
            ds, 2, [False] * ds.num_binned, max_candidates_per_attr,
            attrs=attrs)
    return generate_candidate_splits(
        ds, max_split, is_categorical, max_candidates_per_attr, attrs=attrs)


# ---------------------------------------------------------------------------
# split evaluation on device
# ---------------------------------------------------------------------------

# rows per f32-exact einsum block in node_bin_class_counts; module-level so
# tests can shrink it to exercise the scanned multi-block path cheaply
_EINSUM_BLOCK = 1 << 23


@functools.partial(jax.jit, static_argnames=("num_nodes", "num_classes",
                                             "num_bins"))
def node_bin_class_counts(
    codes: jax.Array,        # [N, F]
    node_ids: jax.Array,     # [N] active-node index (−1 = inactive/settled)
    labels: jax.Array,       # [N]
    num_nodes: int, num_classes: int, num_bins: int,
) -> jax.Array:
    """[F, B, K, C] per-(feature bin, frontier node, class) counts — the
    level's ONE device contraction (an fbc count over the composite
    (node, class) code, i.e. an MXU matmul over one-hots; rows beyond the
    f32-exact einsum block limit are scanned in count-neutral-padded
    blocks with int32 accumulation, so any N is exact).

    Every candidate split's [S, G, K, C] histogram is a tiny host
    contraction of this table with the split's bin→segment one-hot
    (:func:`split_histograms_from_table`) — independent of N.  This
    replaces the round-3 per-split-chunk [N, S] segment-code gather +
    upload, which measured ~8k rows/s on the dev rig because every split
    chunk re-uploaded an N-row operand; the reference pays the analogous
    cost as one MR shuffle per candidate-split evaluation
    (ClassPartitionGenerator.java:199-230)."""
    c = num_classes
    valid = (node_ids >= 0) & (labels >= 0) & (labels < c)
    comp = jnp.where(valid, node_ids * c + labels, -1)
    kc = num_nodes * c

    def block(cd, cp):
        oh_b = agg.one_hot(cd, num_bins)               # [n, F, B]
        oh_k = agg.one_hot(cp, kc)                     # [n, KC]
        return jnp.einsum("nfb,nk->fbk", oh_b, oh_k,
                          precision="highest").astype(jnp.int32)

    n = codes.shape[0]
    lim = _EINSUM_BLOCK            # f32-exact einsum counts per block
    if n <= lim:
        t = block(codes, comp)
    else:
        npad = -(-n // lim) * lim
        cd = jnp.pad(codes, ((0, npad - n), (0, 0)), constant_values=-1)
        cp = jnp.pad(comp, (0, npad - n), constant_values=-1)
        f = codes.shape[1]
        t = jax.lax.scan(
            lambda acc, xs: (acc + block(xs[0], xs[1]), None),
            jnp.zeros((f, num_bins, kc), jnp.int32),
            (cd.reshape(-1, lim, f), cp.reshape(-1, lim)))[0]
    return t.reshape(t.shape[0], t.shape[1], num_nodes, c)


@functools.partial(jax.jit, static_argnames=("num_nodes", "num_classes",
                                             "num_bins", "interpret"))
def _level_table_cross(codes_t: jax.Array, node_ids: jax.Array,
                       labels: jax.Array, num_nodes: int, num_classes: int,
                       num_bins: int, interpret: bool = False) -> jax.Array:
    """The level table via the fused cross-gram kernel
    (``pallas_hist.cross_cooc_counts_cols``): X = (feature, bin) one-hot,
    Y = (node, class) one-hot, table = XᵀY on the int8 MXU with both
    expansions in VMEM — the einsum form's [N, F, B] HBM one-hot
    (~400 B/row/level) becomes a ~24 B/row code stream.  Bit-identical
    counts (int8 0/1 operands, int32 accumulation; invalid codes, settled
    rows and out-of-range labels all drop out exactly as the einsum's
    zero one-hot rows)."""
    from avenir_tpu.ops import pallas_hist

    c = num_classes
    valid = (node_ids >= 0) & (labels >= 0) & (labels < c)
    sel = jnp.where(valid, node_ids * c + labels, -1)
    t = pallas_hist.cross_cooc_counts_cols.__wrapped__(
        codes_t, sel, num_bins, num_nodes * c, interpret=interpret)
    return t.reshape(t.shape[0], t.shape[1], num_nodes, c)


@functools.partial(jax.jit, static_argnames=("pplan", "kernel", "interpret"))
def _level_table_packed(codes_t: jax.Array, node_ids: jax.Array,
                        labels: jax.Array, pplan, kernel: bool,
                        interpret: bool = False) -> jax.Array:
    """The level table via a PackGraft disjoint pack: the K frontier
    nodes' [F, B, C] tables ride ONE wide gram over K bin stripes
    (composite code = code + node·stripe_bins, ``pallas_hist.pack_disjoint``)
    so sibling tables the subtraction plan still contracts one-by-one
    inherit the wide-gram width tier.  The readout is the pack's diagonal
    gather — exact: rows off the frontier (node −1) drop whole and
    out-of-range codes drop per-feature, the same validity
    ``node_bin_class_counts`` masks, and cross-member cells are
    structurally zero (one node per row).  ``kernel`` routes the joint
    shape onto the int8 MXU kernel; off it the exact einsum gram runs
    the same layout.  Returns [F, B, K, C]."""
    from avenir_tpu.ops import pallas_hist

    c = pplan.num_classes
    comp = pallas_hist.packed_codes.__wrapped__(
        codes_t, node_ids, pplan.stripe_bins, pplan.members[0].num_bins)
    if kernel:
        g = pallas_hist.cooc_counts_cols.__wrapped__(
            comp, labels, pplan.num_bins, c, interpret=interpret)
    else:
        g = pallas_hist.gram_counts_cols.__wrapped__(
            comp, labels, pplan.num_bins, c)
    wi = jnp.asarray(pallas_hist.packed_diag_index(pplan))   # [F, B, K, C]
    if g.ndim == 3:                          # cls/clsb: per-class diagonal
        w2 = wi[..., 0]                      # [F, B, K] — same cell per class
        t = jnp.moveaxis(g[:, w2, w2], 0, -1)
    else:                                    # fmaj/jmaj: class rides the cell
        t = g[wi, wi]
    return t.astype(jnp.int32)


@jax.jit
def _remap_nodes(node: jax.Array, remap: jax.Array) -> jax.Array:
    """[N] absolute node ids → frontier-local indices (−1 = settled)."""
    return remap[jnp.maximum(node, 0)]


@jax.jit
def _apply_level_partition(codes: jax.Array, node: jax.Array,
                           remap: jax.Array, attr: jax.Array,
                           child_tab: jax.Array) -> jax.Array:
    """Device-side frontier partition: rows of frontier node ki whose
    level-chosen split routes bin b to child ``child_tab[ki, b]`` move
    there; settled rows and unsplit frontier rows (child −1) keep their
    id.  The [N] node vector thus lives ON DEVICE across levels — per
    level only KB-sized tables travel (remap, per-node split attr, the
    bin→child table), replacing the round-4 host partition + full [N]
    re-upload whose host round trips dominated induction time (and are
    pure waste on any host).

    A −1 (invalid) code indexes the LAST bin — the same semantics the
    host path inherited from numpy's negative indexing, kept so the
    device partition is bit-identical to it."""
    local = _remap_nodes.__wrapped__(node, remap)
    lc = jnp.maximum(local, 0)
    a = attr[lc]                                             # [N]
    code = jnp.take_along_axis(codes, a[:, None], axis=1)[:, 0]
    b = child_tab.shape[1]
    code = jnp.where(code < 0, code + b, code)
    code = jnp.clip(code, 0, b - 1)
    new = child_tab[lc, code]
    return jnp.where((local >= 0) & (new >= 0), new, node)


def split_histograms_from_table(table_a: np.ndarray,
                                chunk: Sequence["CandidateSplit"],
                                gmax: int) -> np.ndarray:
    """table_a [B, K, C] (one attribute's slice of the level table) →
    [S, G, K, C] histograms for a chunk of candidate splits — pure host
    numpy over segment maps; no N-dependent work."""
    seg_tab = np.stack([sp.seg_of_bin for sp in chunk])          # [S, B]
    m = (seg_tab[:, None, :] == np.arange(gmax)[None, :, None])  # [S, G, B]
    return np.einsum("sgb,bkc->sgkc", m, table_a)


def _chunk_seg_mask(chunk: Sequence["CandidateSplit"], gmax: int) -> np.ndarray:
    """[S, G] validity mask: segment g is real for split s iff
    g < num_segments — shared by the host and device scoring paths so
    padded segments never leak into a score (classConfidenceRatio is the
    one algorithm not zero-count-invariant: an empty padded segment would
    contribute confidence (0+1)/(0+1) = 1, making the score depend on
    which splits happened to share a chunk/padding width)."""
    nsegs = np.array([sp.num_segments for sp in chunk], np.int32)
    return nsegs[:, None] > np.arange(gmax, dtype=np.int32)[None, :]


def iter_scored_splits(table: np.ndarray, all_splits, algorithm: str,
                       split_chunk: int, attrs=None, parent_info=None):
    """Yield (attr, chunk, scores [S, K], hist [S, G, K, C]) per candidate
    split chunk, all derived from the level table on the LOCAL host
    backend — the host reference pipeline behind ``selection="host"`` and
    the device-selection equivalence tests.

    Scores go through the JITTED ``split_scores`` (``_split_scores_jit``):
    the compiled graph rounds identically whether it runs standalone here
    or fused inside the device-selection dispatch, and it is invariant to
    chunk shape and zero-segment padding (measured: 0 mismatching bits
    across all four algorithms on the retarget candidate set) — eager
    per-op scoring differs from the fused form in the last float bit,
    which would break the byte-identical-tree contract between paths."""
    with info.on_host():
        for a in (attrs if attrs is not None else sorted(all_splits)):
            splits = all_splits[a]
            if not splits:
                continue
            for s0 in range(0, len(splits), split_chunk):
                chunk = splits[s0:s0 + split_chunk]
                gmax = max(sp.num_segments for sp in chunk)
                hist = split_histograms_from_table(table[a], chunk, gmax)
                scores = np.asarray(_split_scores_jit(
                    jnp.asarray(hist, jnp.float32), algorithm,
                    parent_info=parent_info,
                    seg_mask=jnp.asarray(_chunk_seg_mask(chunk, gmax))))
                yield a, chunk, scores, hist


def split_scores(hist: jax.Array, algorithm: str,
                 parent_info: Optional[float] = None,
                 seg_mask: Optional[jax.Array] = None) -> jax.Array:
    """hist [S, G, K, C] → score [S, K]; higher is better for every algorithm.

    entropy/giniIndex → gain ratio: (parent impurity − weighted child
    impurity) / split info content (AttributeSplitStat.java:85-93,153-218).
    ``parent_info``, when given, substitutes the reference's externally
    supplied ``parent.info`` property (ClassPartitionGenerator.java:510,533
    — produced by the ``at.root`` bootstrap job) for the parent impurity
    computed from the node's own histogram (the self-contained default).
    hellingerDistance → distance between the per-class segment distributions
    (binary class, :228-284). classConfidenceRatio → entropy of the
    normalized per-segment class-confidence ratios (:291-339); lower entropy
    = more skew = better, so the score is negated entropy.

    ``seg_mask`` [S, G] marks which segments are real for each split (the
    histogram may be zero-padded to a common G).  entropy / gini /
    hellinger are bit-invariant to all-zero padded segments (each
    contributes an exact +0.0 term), so the mask only gates
    classConfidenceRatio, whose +1 Laplace smoothing would otherwise count
    phantom segments.  With the mask, scores are independent of chunk
    composition and padding width — the property the device and host
    selection paths rely on for byte-identical trees.
    """
    h = hist.astype(jnp.float32)                          # [S, G, K, C]
    seg_tot = h.sum(-1)                                   # [S, G, K]
    node_tot = jnp.maximum(seg_tot.sum(1), 1e-9)          # [S, K]
    w = seg_tot / node_tot[:, None, :]                    # segment weights
    parent = h.sum(1)                                     # [S, K, C]
    if algorithm in ("entropy", "giniIndex"):
        imp = info.entropy_from_counts if algorithm == "entropy" else info.gini_from_counts
        child = imp(h, axis=-1)                           # [S, G, K]
        weighted = jnp.sum(w * child, axis=1)             # [S, K]
        p_imp = (imp(parent, axis=-1) if parent_info is None
                 else jnp.float32(parent_info))
        gain = p_imp - weighted
        split_info = info.entropy(jnp.swapaxes(w, 1, 2), axis=-1)   # [S, K]
        return gain / jnp.maximum(split_info, 1e-6)
    if algorithm == "hellingerDistance":
        cls_tot = jnp.maximum(h.sum(1, keepdims=True), 1e-9)        # [S, 1, K, C]
        p_seg_given_c = h / cls_tot                                  # [S, G, K, C]
        d = (jnp.sqrt(p_seg_given_c[..., 0]) - jnp.sqrt(p_seg_given_c[..., 1])) ** 2
        return jnp.sqrt(jnp.maximum(d.sum(1), 0.0)) / jnp.sqrt(2.0)  # [S, K]
    if algorithm == "classConfidenceRatio":
        conf = (h[..., 0] + 1.0) / (h[..., 1] + 1.0)                 # [S, G, K]
        if seg_mask is not None:
            conf = jnp.where(seg_mask[:, :, None], conf, 0.0)
        ratio = conf / jnp.maximum(conf.sum(1, keepdims=True), 1e-9)
        return -info.entropy(jnp.swapaxes(ratio, 1, 2), axis=-1)
    raise ValueError(f"unknown algorithm {algorithm!r}; known: {ALGORITHMS}")


# the one compiled scoring graph shared by the host pipeline and (inlined)
# the device-selection dispatch — see iter_scored_splits on why eager
# scoring is not bit-compatible with the fused form
_split_scores_jit = jax.jit(split_scores, static_argnames=("algorithm",))


# ---------------------------------------------------------------------------
# device-resident split selection
# ---------------------------------------------------------------------------

@dataclass
class FlatSplits:
    """Per-fit static candidate-split metadata, compiled once into padded
    device arrays so the per-level selection dispatch is jit-stable across
    levels (only the frontier width K varies).

    ``splits`` holds the CandidateSplit objects in device flat order —
    ascending attribute, then enumeration order within the attribute (the
    same order the host path iterates, so argmax/top-k tie-breaking by
    lowest flat index reproduces the host's stable sort).  The arrays are
    padded to a multiple of ``chunk`` rows; pad rows have ``valid`` False
    and are force-masked to −inf before selection.
    """

    splits: List[CandidateSplit]
    attr_of: np.ndarray                  # [S_pad] int32 (host copy, for masks)
    valid: np.ndarray                    # [S_pad] bool — False on pad rows
    gmax: int
    chunk: int
    seg_tab_dev: jax.Array               # [S_pad, B] int32
    attr_dev: jax.Array                  # [S_pad] int32
    nseg_dev: jax.Array                  # [S_pad] int32
    # binary-threshold metadata for the cumsum fast path: thr_of[s] = the
    # single sorted threshold of split s (0 on pad rows), meaningful only
    # when ``all_binary`` — every real split is a two-segment numeric
    # threshold (codes < t left), i.e. the split.search=binary family
    thr_of: np.ndarray = dc_field(default_factory=lambda: np.zeros(0, np.int32))
    thr_dev: Optional[jax.Array] = None
    all_binary: bool = False

    @property
    def num_real(self) -> int:
        return len(self.splits)

    def allow_vector(self, attrs: Sequence[int]) -> np.ndarray:
        """[S_pad] bool — splits whose attribute the level's strategy
        selected (randomK / userSpecified), excluding pad rows.  A tiny
        per-level host→device upload; everything else is fit-static."""
        return self.valid & np.isin(
            self.attr_of, np.asarray(list(attrs), np.int32))


def flatten_splits(all_splits: Dict[int, List[CandidateSplit]],
                   max_bins: int, split_chunk: int) -> FlatSplits:
    """Compile the per-attr candidate dict into FlatSplits device arrays."""
    flat = [sp for a in sorted(all_splits) for sp in all_splits[a]]
    s = len(flat)
    gmax = max([sp.num_segments for sp in flat] or [1])
    chunk = max(1, min(split_chunk, max(s, 1)))
    s_pad = max(-(-s // chunk) * chunk, chunk)
    seg_tab = np.zeros((s_pad, max_bins), np.int32)
    attr_of = np.zeros(s_pad, np.int32)
    nseg = np.ones(s_pad, np.int32)
    valid = np.zeros(s_pad, bool)
    thr = np.zeros(s_pad, np.int32)
    all_binary = s > 0
    for i, sp in enumerate(flat):
        seg_tab[i] = sp.seg_of_bin
        attr_of[i] = sp.attr
        nseg[i] = sp.num_segments
        valid[i] = True
        t = int(np.argmax(sp.seg_of_bin == 1)) if sp.num_segments == 2 else 0
        if (sp.kind == "numeric" and sp.num_segments == 2 and t > 0
                and np.array_equal(
                    sp.seg_of_bin,
                    (np.arange(len(sp.seg_of_bin)) >= t).astype(np.int32))):
            thr[i] = t
        else:
            all_binary = False
    return FlatSplits(
        splits=flat, attr_of=attr_of, valid=valid, gmax=gmax, chunk=chunk,
        seg_tab_dev=jnp.asarray(seg_tab), attr_dev=jnp.asarray(attr_of),
        nseg_dev=jnp.asarray(nseg), thr_of=thr, thr_dev=jnp.asarray(thr),
        all_binary=all_binary)


def _scored_chunks(table: jax.Array, seg_tab: jax.Array, attr_of: jax.Array,
                   nseg: jax.Array, algorithm: str, gmax: int, chunk: int,
                   parent_info=None, want_hist: bool = False,
                   thr: Optional[jax.Array] = None, binary: bool = False):
    """Score every padded candidate split against the device level table in
    ``chunk``-sized blocks under ``lax.map`` (bounds the [s, B, K, C]
    gather working set).  Returns scores [S_pad, K] and, when
    ``want_hist``, the [S_pad, G, K, C] int32 histograms.

    With ``binary`` (the cumsum fast path, ``tree.hist.mode`` cumsum /
    subtract + an all-binary candidate family), the per-split histogram
    is two gathers against ONE bin-axis cumsum of the table
    (:func:`info.binary_split_histograms`) instead of the per-split
    segment einsum — identical int32 histograms (exact prefix sums), the
    same block structure and the same ``split_scores`` graph on the same
    shapes, so scores stay bit-identical to the einsum form."""
    s_pad, b = seg_tab.shape
    nc = s_pad // chunk
    grange = jnp.arange(gmax, dtype=jnp.int32)
    cum = info.cumulative_level_table(table) if binary else None
    if binary:
        assert gmax == 2, "binary cumsum path requires two-segment splits"

    def block(args):
        if binary:
            th, ao, ns = args                               # [s] [s] [s]
            h = info.binary_split_histograms(cum, ao, th)
        else:
            st, ao, ns = args                               # [s,B] [s] [s]
            h = info.split_segment_histograms(table, st, ao, gmax)
        mask = grange[None, :] < ns[:, None]                # [s, G]
        sc = split_scores(h.astype(jnp.float32), algorithm,
                          parent_info=parent_info, seg_mask=mask)
        return (sc, h) if want_hist else (sc,)

    lead = (thr.reshape(nc, chunk) if binary
            else seg_tab.reshape(nc, chunk, b))
    out = jax.lax.map(block, (lead, attr_of.reshape(nc, chunk),
                              nseg.reshape(nc, chunk)))
    k = table.shape[2]
    scores = out[0].reshape(s_pad, k)
    if want_hist:
        return scores, out[1].reshape(s_pad, gmax, k, table.shape[3])
    return scores, None


@functools.partial(jax.jit, static_argnames=("algorithm", "gmax", "top_k",
                                             "chunk", "binary"))
def _device_select_splits(table: jax.Array, seg_tab: jax.Array,
                          attr_of: jax.Array, nseg: jax.Array,
                          allow: jax.Array, thr: Optional[jax.Array] = None,
                          *, algorithm: str, gmax: int,
                          top_k: int, chunk: int, binary: bool = False):
    """Device-resident split selection for one frontier level: build every
    candidate's segment histogram from the on-device [F, B, K, C] table
    (``info.split_segment_histograms`` — a device einsum, not a host numpy
    pass), score with the ``split_scores`` kernels, and take the top-k
    winners PER FRONTIER NODE on device.  The host fetches only the
    KB-sized descriptors (score, flat split index, [G, C] winner
    histogram) — replacing the full-table fetch + host fold whose
    per-level host round trip dominated induction wall time.

    Returns (vals [K, P], idx [K, P], hist [K, P, G, C] int32), P = top_k,
    sorted best-first; ``lax.top_k`` breaks ties toward the lowest flat
    index, matching the host path's stable sort over its iteration order.
    Disallowed (strategy-masked) and pad candidates come back as −inf.
    """
    scores, _ = _scored_chunks(table, seg_tab, attr_of, nseg,
                               algorithm, gmax, chunk, thr=thr, binary=binary)
    scores = jnp.where(allow[:, None] & ~jnp.isnan(scores), scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores.T, top_k)              # [K, P] each
    k = table.shape[2]
    grange = jnp.arange(gmax, dtype=jnp.int32)
    tt = jnp.transpose(table, (2, 0, 1, 3))                 # [K, F, B, C]
    w_ta = tt[jnp.arange(k)[:, None], attr_of[idx]]         # [K, P, B, C]
    w_m = (seg_tab[idx][:, :, None, :] ==
           grange[None, None, :, None]).astype(jnp.int32)   # [K, P, G, B]
    w_hist = jnp.einsum("kpgb,kpbc->kpgc", w_m, w_ta)       # int32
    return vals, idx, w_hist


@functools.partial(jax.jit, static_argnames=("algorithm", "gmax", "chunk",
                                             "has_parent", "want_hist",
                                             "binary"))
def _device_score_all(table: jax.Array, seg_tab: jax.Array,
                      attr_of: jax.Array, nseg: jax.Array, parent_info,
                      thr: Optional[jax.Array] = None,
                      *, algorithm: str, gmax: int, chunk: int,
                      has_parent: bool, want_hist: bool = False,
                      binary: bool = False):
    """Score EVERY candidate split on device and return (scores [S_pad, K],
    hist [S_pad, G, K, C] or None) — the batched entry behind the
    ClassPartitionGenerator job, whose contract is the full scored list
    rather than a per-node winner.  One dispatch; the fetch is the
    [S, K] score sheet (plus, only when ``want_hist``, the small
    histograms for the optional segment-distribution output columns),
    never the [F, B, K, C] table."""
    return _scored_chunks(table, seg_tab, attr_of, nseg, algorithm, gmax,
                          chunk, parent_info=parent_info if has_parent
                          else None, want_hist=want_hist, thr=thr,
                          binary=binary)


@jax.jit
def _assemble_subtract_table(direct_table: jax.Array, prev_table: jax.Array,
                             dslot: jax.Array, pslot: jax.Array,
                             sib_mat: jax.Array) -> jax.Array:
    """Sibling-subtraction level-table assembly (``tree.hist.mode``
    subtract): the frontier's [F, B, K, C] table from the [F, B, D, C]
    DIRECT table (only the smaller children of each split were
    contracted) plus the parent level's resident table.

    Node k is either direct (``dslot[k]`` ≥ 0 → its own contraction
    slice) or derived: its parent's previous-level slice
    (``pslot[k]``) minus the sum of its directly-contracted siblings
    (``sib_mat[k]`` one-hot over direct slots).  Every row of a split
    parent routes to exactly one child segment and label-invalid rows
    are excluded identically from parent and child counts, so the
    int32 subtraction is EXACT — the derived slice equals the direct
    contraction bit-for-bit (asserted in tests/test_tree.py)."""
    direct_part = direct_table[:, :, jnp.maximum(dslot, 0), :]
    parent_part = prev_table[:, :, jnp.maximum(pslot, 0), :]
    sib_sum = jnp.einsum("fbdc,kd->fbkc", direct_table, sib_mat)
    return jnp.where((dslot >= 0)[None, None, :, None],
                     direct_part, parent_part - sib_sum)


# ---------------------------------------------------------------------------
# tree model
# ---------------------------------------------------------------------------

@dataclass
class TreeNode:
    node_id: int
    depth: int
    class_counts: np.ndarray            # [C]
    split: Optional[CandidateSplit] = None
    children: List[int] = dc_field(default_factory=list)
    score: float = 0.0

    @property
    def is_leaf(self) -> bool:
        return self.split is None


@dataclass
class DecisionTreeModel:
    nodes: List[TreeNode]
    class_values: List[str]
    max_bins: int
    algorithm: str
    # the CONFIGURED depth / segment caps the tree was grown under (None
    # on legacy artifacts).  predict_shape_signature buckets on these,
    # not on what the tree happened to grow, so a retrain at the same
    # caps that grows shallower or narrower still lands in the same
    # compiled-walker bucket
    depth_cap: Optional[int] = None
    split_cap: Optional[int] = None

    # compiled arrays for jitted prediction
    def compile_arrays(self, pad: bool = False):
        """Flat device arrays for the jitted walker.  With ``pad``, the
        node and segment axes round up to power-of-two buckets
        (:func:`_pow2_bucket`): pad node rows are self-loop leaves with a
        zero distribution and are unreachable from the root, so padded
        and unpadded walks are byte-identical — what lets a retrained
        tree of a different size land in the SAME compiled scoring
        program (see :func:`predict_fn`; the StreamGraft
        drift→retrain→hot-swap path relies on it for zero swap
        recompiles)."""
        m = len(self.nodes)
        gmax = max([n.split.num_segments for n in self.nodes if n.split] or [1])
        if pad:
            _dp, mp, gp, _b, _c = predict_shape_signature(self)
        else:
            mp, gp = m, gmax
        attr = np.full(mp, 0, np.int32)
        seg_table = np.zeros((mp, self.max_bins), np.int32)
        child = np.tile(np.arange(mp, dtype=np.int32)[:, None], (1, gp))
        c = len(self.class_values)
        distr = np.zeros((mp, c), np.float32)
        for n in self.nodes:
            tot = max(n.class_counts.sum(), 1.0)
            distr[n.node_id] = n.class_counts / tot
            if n.split is not None:
                attr[n.node_id] = n.split.attr
                seg_table[n.node_id] = n.split.seg_of_bin
                for g, ch in enumerate(n.children):
                    child[n.node_id, g] = ch
        return (jnp.asarray(attr), jnp.asarray(seg_table), jnp.asarray(child),
                jnp.asarray(distr))

    @property
    def max_depth(self) -> int:
        return max(n.depth for n in self.nodes)

    # -- serde ---------------------------------------------------------------
    def to_json(self) -> Dict:
        return {
            "class_values": self.class_values,
            "max_bins": self.max_bins,
            "algorithm": self.algorithm,
            "depth_cap": self.depth_cap,
            "split_cap": self.split_cap,
            "nodes": [
                {
                    "id": n.node_id, "depth": n.depth,
                    "counts": n.class_counts.tolist(),
                    "children": n.children, "score": n.score,
                    "split": None if n.split is None else {
                        "attr": n.split.attr, "kind": n.split.kind,
                        "seg_of_bin": n.split.seg_of_bin.tolist(),
                        "num_segments": n.split.num_segments, "key": n.split.key,
                    },
                }
                for n in self.nodes
            ],
        }

    @classmethod
    def from_json(cls, obj: Dict) -> "DecisionTreeModel":
        nodes = []
        for d in obj["nodes"]:
            sp = d["split"]
            nodes.append(TreeNode(
                node_id=d["id"], depth=d["depth"],
                class_counts=np.asarray(d["counts"], np.float64),
                split=None if sp is None else CandidateSplit(
                    sp["attr"], sp["kind"], np.asarray(sp["seg_of_bin"], np.int32),
                    sp["num_segments"], sp["key"]),
                children=list(d["children"]), score=d["score"],
            ))
        dcap = obj.get("depth_cap")
        scap = obj.get("split_cap")
        return cls(nodes=nodes, class_values=list(obj["class_values"]),
                   max_bins=int(obj["max_bins"]), algorithm=obj["algorithm"],
                   depth_cap=None if dcap is None else int(dcap),
                   split_cap=None if scap is None else int(scap))

    def to_string(self) -> str:
        return json.dumps(self.to_json())

    @classmethod
    def from_string(cls, s: str) -> "DecisionTreeModel":
        return cls.from_json(json.loads(s))


def _pow2_bucket(n: int) -> int:
    """Smallest power of two ≥ n (n ≥ 1 → 1, 2, 4, 8, …)."""
    return 1 << max(n - 1, 0).bit_length()


@functools.partial(jax.jit, static_argnames=("depth",))
def _tree_walk(attr: jax.Array, seg_table: jax.Array, child: jax.Array,
               distr: jax.Array, codes: jax.Array, *, depth: int):
    """The ONE compiled tree walker, shared across models: the tree
    arrays are ARGUMENTS (not closure constants), so the jit cache keys
    on their shapes — two trees with the same padded bucket shapes and
    depth bucket reuse the same executable.  Extra ``depth`` iterations
    past a tree's real depth are identities (leaves self-loop via the
    child table's diagonal default)."""
    node = jnp.zeros(codes.shape[0], jnp.int32)
    for _ in range(depth):
        a = attr[node]                                           # [N]
        code = jnp.take_along_axis(codes, a[:, None], axis=1)[:, 0]
        seg = seg_table[node, code]
        node = child[node, seg]
    d = distr[node]
    return jnp.argmax(d, axis=-1).astype(jnp.int32), d


def predict_shape_signature(model: DecisionTreeModel) -> tuple:
    """The padded compile-shape bucket of :func:`predict_fn`'s walker —
    (depth bucket, node bucket, segment bucket, max_bins, classes).  Two
    models with equal signatures share the compiled scoring program for
    any given batch shape; serving uses this as part of its compile key
    so a hot-swap onto an equal-signature tree provably compiles
    nothing.

    The depth and segment buckets come from the CONFIGURED caps the tree
    was grown under (``depth_cap`` / ``split_cap``; the grown shape only
    on legacy artifacts without them), with the segment bucket floored
    at 4 — a retrained tree that happened to grow shallower or narrower
    (e.g. only binary splits under a 5-way cap) must not land in a
    different bucket than its predecessor.  The node bucket is derived
    from the FULL-tree node bound of the depth/segment buckets (capped
    at 4096 so deep exhaustive trees don't inflate the padded arrays),
    not from this tree's own node count — so a drift→retrain of the same
    family at the same caps lands in the SAME bucket regardless of what
    it happened to grow."""
    m = len(model.nodes)
    gmax = max([n.split.num_segments for n in model.nodes if n.split] or [1])
    dp = _pow2_bucket(max(model.depth_cap or model.max_depth, 1))
    gp = max(_pow2_bucket(max(model.split_cap or 1, gmax)), 4)
    full = (gp ** (dp + 1) - 1) // (gp - 1)
    mp = _pow2_bucket(max(m, min(full, 4096)))
    return (dp, mp, gp, model.max_bins, len(model.class_values))


def predict_fn(model: DecisionTreeModel, pad_shapes: bool = True):
    """Build a jitted [N,F] codes → ([N] class idx, [N,C] distr) walker.

    With ``pad_shapes`` (default) the tree arrays pad to power-of-two
    node/segment buckets and the walk depth rounds up to a power-of-two
    bucket, so a retrained tree of a different depth/size within the
    same buckets REUSES the compiled program (:func:`_tree_walk` keys on
    shapes, not identity) — predictions are byte-identical either way
    (pad nodes unreachable, extra levels identity self-loops)."""
    attr, seg_table, child, distr = model.compile_arrays(pad=pad_shapes)
    if pad_shapes:
        depth = predict_shape_signature(model)[0]
    else:
        depth = max(model.max_depth, 1)

    def walk(codes: jax.Array):
        return _tree_walk(attr, seg_table, child, distr, codes, depth=depth)

    return walk


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------

class DecisionTree:
    """Frontier-growth decision-tree trainer.

    Parameters mirror the reference's job properties:
    ``algorithm`` (split.algorithm), ``max_depth`` (recursion depth of the
    SplitGenerator/DataPartitioner loop), ``min_node_size``, ``min_gain``
    (stopping), ``max_split`` (maxSplit per field), ``attr_strategy``
    all|userSpecified|randomK (split.attribute.selection.strategy),
    ``top_n`` random-from-top-N split selection (custom.base.attribute.ordinals /
    DataPartitioner.java:181-185).

    ``selection`` picks where per-level split selection runs:

    - ``"device"`` (default) — candidate histograms, scores and the
      per-node top-k winner all run on device against the resident level
      table; the host fetches only KB-sized chosen-split descriptors per
      level.  One dispatch + one small fetch per level, composing with the
      device-resident node vector (``_apply_level_partition``).
    - ``"host"`` — the prior pipeline: fetch the whole [F, B, K, C] table
      and fold it on host (``iter_scored_splits``).  Kept as the
      equivalence oracle; both paths grow byte-identical trees (asserted
      in tests across all four algorithms).  For tie-breaks to agree, the
      device flat order assumes ascending-attribute iteration; an
      unsorted ``user_attrs`` list can differ on exact score ties only.
      Byte-identity is a same-backend guarantee (the tier-1 equivalence
      tests run both paths on CPU): on a TPU the device path scores in
      TPU f32 while the host oracle scores on the local CPU backend, so
      candidates whose true scores differ by under ~1 ulp may pick
      differently there — exact ties still agree (lowest flat index).

    ``split_search`` picks the candidate family:

    - ``"exhaustive"`` (default) — the reference's multi-way search: all
      increasing threshold sets for numeric fields and all set partitions
      for categorical fields up to ``max_split`` groups
      (ClassPartitionGenerator.java:280-432).
    - ``"binary"`` — sorted-threshold binary splits only (every attribute
      treated as ordinal over its bin codes, one threshold, two
      segments) — the candidate family sklearn's DecisionTreeClassifier
      searches over ordinal-encoded inputs, scored by the same kernels;
      the apples-to-apples benchmarking mode.

    ``hist_mode`` picks the level-table / split-histogram strategy (see
    :data:`HIST_MODES`): ``direct`` (default, today's path), ``cumsum``
    (binary-threshold candidates score from one bin-axis cumsum of the
    level table — a B× cut in per-level scoring work; exhaustive
    multi-way search keeps its einsum), ``subtract`` (cumsum scoring
    plus sibling-subtraction level tables — only the smaller children
    of each split are contracted, the largest sibling derives by exact
    parent-slice subtraction, roughly halving per-level gram work).
    All three grow byte-identical trees; ``cumsum``/``subtract``
    scoring applies on the device-selection path (the ``host`` oracle
    always folds the direct form).
    """

    def __init__(
        self,
        algorithm: str = "entropy",
        max_depth: int = 4,
        min_node_size: int = 32,
        min_gain: float = 1e-4,
        max_split: int = 3,
        attr_strategy: str = "all",
        user_attrs: Optional[Sequence[int]] = None,
        random_k: Optional[int] = None,
        top_n: int = 1,
        max_candidates_per_attr: int = 128,
        split_chunk: int = 128,
        seed: int = 0,
        mesh=None,
        selection: str = "device",
        split_search: str = "exhaustive",
        hist_mode: str = "direct",
        level_packed: str = "auto",
        collect_phase_stats: bool = False,
    ):
        if algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {algorithm!r}; known: {ALGORITHMS}")
        if selection not in ("device", "host"):
            raise ValueError(
                f"unknown selection {selection!r}; known: device, host")
        if split_search not in ("exhaustive", "binary"):
            raise ValueError(f"unknown split_search {split_search!r}; "
                             "known: exhaustive, binary")
        if hist_mode not in HIST_MODES:
            raise ValueError(f"unknown hist_mode {hist_mode!r}; "
                             f"known: {HIST_MODES}")
        if level_packed not in ("auto", "on", "off"):
            raise ValueError(f"unknown level_packed {level_packed!r}; "
                             "known: auto, on, off")
        self.selection = selection
        self.split_search = split_search
        self.hist_mode = hist_mode
        # PackGraft (round 16): "auto" packs frontier sibling tables into
        # one wide disjoint gram when the joint shape rides the TPU
        # kernel; "on" forces packing (einsum gram off-TPU — the testable
        # attestation path); "off" keeps cross/einsum routing only
        self.level_packed = level_packed
        # per-level phase breakdown (table-build / score+select /
        # partition wall ms) — opt-in because honest phase timings need
        # a device sync per phase; read ``self.level_stats`` after fit
        self.collect_phase_stats = collect_phase_stats
        self.level_stats: List[dict] = []
        self.algorithm = algorithm
        self.max_depth = max_depth
        self.min_node_size = min_node_size
        self.min_gain = min_gain
        self.max_split = max_split
        self.attr_strategy = attr_strategy
        self.user_attrs = list(user_attrs) if user_attrs is not None else None
        self.random_k = random_k
        self.top_n = top_n
        self.max_candidates_per_attr = max_candidates_per_attr
        self.split_chunk = split_chunk
        self.seed = seed
        self.mesh = mesh          # optional data mesh (parallel/mesh.py)

    def _attrs_for_node(self, rng: np.random.Generator, num_attrs: int) -> List[int]:
        if self.attr_strategy == "userSpecified":
            if not self.user_attrs:
                raise ValueError("userSpecified strategy requires user_attrs")
            return self.user_attrs
        if self.attr_strategy == "randomK":
            k = self.random_k or max(1, int(np.sqrt(num_attrs)))
            return sorted(rng.choice(num_attrs, size=min(k, num_attrs), replace=False).tolist())
        if self.attr_strategy == "all":
            return list(range(num_attrs))
        raise ValueError(f"unknown attr_strategy {self.attr_strategy!r}")

    def fit(self, ds: EncodedDataset,
            is_categorical: Optional[Sequence[bool]] = None) -> DecisionTreeModel:
        if ds.labels is None:
            raise ValueError("fit requires labels")
        from avenir_tpu.parallel.mesh import maybe_shard_batch

        rng = np.random.default_rng(self.seed)
        n, c = ds.num_rows, ds.num_classes
        # batch-sharded under a data mesh: pad rows carry -1 labels/node ids,
        # all count-neutral in the level contraction. Codes and labels are
        # uploaded ONCE; per level only the [N] node-id vector travels.
        labels_dev = maybe_shard_batch(self.mesh, ds.labels)[0]
        codes_dev = maybe_shard_batch(self.mesh, ds.codes)[0]
        # single-TPU fast path for the level table: the fused cross-gram
        # kernel streams columnar codes (one device transpose, once)
        from avenir_tpu.ops import pallas_hist
        # the X-side gate (feature/bin width) is level-independent: check
        # it before paying the device transpose + second HBM codes copy
        use_cross = (self.mesh is None and pallas_hist.on_tpu_single_device()
                     and pallas_hist.cross_applicable(
                         ds.num_binned, ds.max_bins, max(c, 1)))
        # PackGraft: may the level fold sibling node tables as one wide
        # disjoint gram?  auto = only where the joint shape would ride the
        # TPU kernel (the width-tier climb is the whole point); "on"
        # forces it (exact einsum gram off-TPU).  The decision per level
        # still goes through pack_disjoint's shape gates in build_table.
        may_pack = self.mesh is None and (
            self.level_packed == "on"
            or (self.level_packed == "auto"
                and pallas_hist.on_tpu_single_device()))
        codes_t_dev = codes_dev.T if (use_cross or may_pack) else None
        all_splits = candidate_splits_for(
            ds, self.split_search, self.max_split, is_categorical,
            self.max_candidates_per_attr)
        flat = (flatten_splits(all_splits, ds.max_bins, self.split_chunk)
                if self.selection == "device" else None)
        use_device_sel = flat is not None and flat.num_real > 0

        # cumsum fast path: every candidate is one sorted threshold on the
        # bin grid (split.search=binary), so per-level scoring runs on the
        # cumulative level table instead of the per-split segment einsum
        use_cum = (use_device_sel and flat.all_binary
                   and self.hist_mode in ("cumsum", "subtract"))

        root_counts = np.bincount(ds.labels, minlength=c).astype(np.float64)
        nodes: List[TreeNode] = [TreeNode(0, 0, root_counts)]
        # the [N] per-row node assignment lives ON DEVICE for the whole
        # fit (round 5): per level only KB-sized tables travel — the
        # round-4 form re-uploaded the remapped [N] vector every level
        # and partitioned on host, paying two N-sized host↔device trips
        # per level that dominated induction wall time
        node_dev = jnp.zeros(labels_dev.shape[0], jnp.int32)
        frontier = [0]
        # sibling-subtraction bookkeeping (hist_mode="subtract"): the
        # previous level's resident table plus the host-side plan mapping
        # each frontier child to a direct contraction slot or a derived
        # (parent − direct siblings) slice
        use_subtract = self.hist_mode == "subtract"
        prev_table_dev = None
        sub_plan = None     # (remap_direct, dslot, pslot, sib_mat, kd)
        collect = self.collect_phase_stats
        self.level_stats = []

        def build_table(local_ids, k_slots):
            """The ONE level contraction entry (shared by the full-frontier
            and direct-slot builds): cross-gram kernel when the selector
            width qualifies, the PackGraft disjoint pack where the pack
            planner accepts the frontier, einsum otherwise.  Returns
            (table, path) with path in ("cross", "packed", "einsum")."""
            cross = use_cross and pallas_hist.cross_applicable(
                ds.num_binned, ds.max_bins, k_slots * c)
            if cross:
                return _level_table_cross(
                    codes_t_dev, local_ids, labels_dev, k_slots, c,
                    ds.max_bins), "cross"
            if may_pack and k_slots > 0:
                pplan = pallas_hist.pack_disjoint(
                    k_slots, ds.num_binned, ds.max_bins, max(c, 1))
                if pplan is not None:
                    kernel = (pallas_hist.packed_applicable(pplan)
                              and pallas_hist.on_tpu_single_device())
                    if kernel or self.level_packed == "on":
                        return _level_table_packed(
                            codes_t_dev, local_ids, labels_dev, pplan,
                            kernel), "packed"
            return node_bin_class_counts(
                codes_dev, local_ids, labels_dev, k_slots, c,
                ds.max_bins), "einsum"

        for depth in range(self.max_depth):
            if not frontier:
                break
            t_lv = time.perf_counter()
            k = len(frontier)
            # remap frontier ids to 0..k-1 for the level contraction
            remap = np.full(len(nodes), -1, np.int32)
            for i, nid in enumerate(frontier):
                remap[nid] = i
            remap_dev = jnp.asarray(remap)
            # the [F, B, K, C] level table stays ON DEVICE; under device
            # selection it is never fetched — only the chosen-split
            # descriptors are
            k_contracted = k
            if use_subtract and sub_plan is not None:
                # contract ONLY the direct (smaller-sibling) slots — for
                # binary trees ~half the gram work — and derive each
                # largest sibling by exact parent-slice subtraction
                remap_direct, dslot, pslot, sib_mat, kd = sub_plan
                k_contracted = kd
                local_direct = _remap_nodes(node_dev,
                                            jnp.asarray(remap_direct))
                direct_dev, path_lv = build_table(local_direct, kd)
                table_dev = _assemble_subtract_table(
                    direct_dev, prev_table_dev, jnp.asarray(dslot),
                    jnp.asarray(pslot), jnp.asarray(sib_mat))
            else:
                local_node_dev = _remap_nodes(node_dev, remap_dev)
                table_dev, path_lv = build_table(local_node_dev, k)
            if use_subtract:
                # only the subtract path ever reads the previous level's
                # table; retaining it otherwise would hold a second dead
                # [F, B, K, C] buffer in HBM per level
                prev_table_dev = table_dev
            if collect:
                # honest per-phase walls need a barrier per phase; this
                # probe mode is opt-in (collect_phase_stats /
                # tree.hist.phase.stats), never the production fit loop
                jax.block_until_ready(table_dev)   # graftlint: disable=GL005
                t_tab = time.perf_counter()

            attrs_lv = self._attrs_for_node(rng, ds.num_binned)
            best_per_node: List[List[Tuple[float, CandidateSplit, np.ndarray]]] = [
                [] for _ in range(k)]
            if use_device_sel:
                # one dispatch (histograms + scores + per-node top-k on
                # device), one KB-sized fetch — this sync IS the designed
                # once-per-level descriptor transfer that replaced the
                # full-table fetch (the r05 RTT wall this rule encodes)
                top_k = min(max(self.top_n, 1), flat.seg_tab_dev.shape[0])
                allow_dev = jnp.asarray(flat.allow_vector(attrs_lv))
                thr_dev = flat.thr_dev if use_cum else None
                statics = dict(algorithm=self.algorithm, gmax=flat.gmax,
                               top_k=top_k, chunk=flat.chunk,
                               binary=use_cum)
                from avenir_tpu.telemetry import profile as _profile

                prof = _profile.profiler()
                pkey = None
                if prof.enabled:
                    # GraftProf: the level-selection program, keyed on
                    # the dispatch shapes + statics; the jitted callable
                    # itself is the AOT cost probe (one extra compile
                    # per distinct key — the opt-in price of the table)
                    from avenir_tpu.telemetry.spans import CompileKeyMonitor
                    pkey = CompileKeyMonitor.shape_key(
                        table_dev, flat.seg_tab_dev, thr_dev) + (
                        tuple(sorted(statics.items())),)
                    prof.observe(
                        pkey, site="tree.level",
                        lowerable=_device_select_splits,
                        args=(table_dev, flat.seg_tab_dev, flat.attr_dev,
                              flat.nseg_dev, allow_dev, thr_dev),
                        kwargs=statics)
                    t_disp = time.perf_counter()
                # graftlint: disable=GL005
                vals, idx, whist = jax.device_get(_device_select_splits(
                    table_dev, flat.seg_tab_dev, flat.attr_dev,
                    flat.nseg_dev, allow_dev, thr_dev, **statics))
                if pkey is not None:
                    prof.sample(pkey, "tree.level",
                                time.perf_counter() - t_disp)
                for ki in range(k):
                    for p in range(top_k):
                        s = float(vals[ki, p])
                        if s == -np.inf:        # pad / strategy-masked slot
                            continue
                        best_per_node[ki].append(
                            (s, flat.splits[int(idx[ki, p])], whist[ki, p]))
            else:
                table = np.asarray(table_dev)
                for _a, chunk, scores, hist in iter_scored_splits(
                        table, all_splits, self.algorithm, self.split_chunk,
                        attrs=attrs_lv):
                    for si, sp in enumerate(chunk):
                        for ki in range(k):
                            best_per_node[ki].append(
                                (float(scores[si, ki]), sp,
                                 hist[si, :, ki, :]))
            # select per node: best or random among top_n
            new_frontier: List[int] = []
            attr_arr = np.zeros(k, np.int32)
            child_tab = np.full((k, ds.max_bins), -1, np.int32)
            split_records: List[Tuple[int, List[int], np.ndarray]] = []
            for ki, nid in enumerate(frontier):
                node = nodes[nid]
                cands = sorted(best_per_node[ki], key=lambda t: -t[0])[:max(self.top_n, 1)]
                if not cands:
                    continue
                pick = cands[0] if len(cands) == 1 or self.top_n <= 1 else \
                    cands[int(rng.integers(len(cands)))]
                score, sp, hist = pick
                # stopping rules (DataPartitioner recursion guards)
                if not np.isfinite(score) or score < self.min_gain:
                    continue
                seg_counts = hist.sum(-1)
                live_segs = seg_counts > 0
                if live_segs.sum() < 2 or node.class_counts.sum() < self.min_node_size:
                    continue
                if (node.class_counts > 0).sum() < 2:   # pure node
                    continue
                node.split = sp
                node.score = score
                for g in range(sp.num_segments):
                    ch = TreeNode(len(nodes), depth + 1, hist[g].astype(np.float64))
                    node.children.append(ch.node_id)
                    nodes.append(ch)
                    if seg_counts[g] >= self.min_node_size and depth + 1 < self.max_depth:
                        new_frontier.append(ch.node_id)
                # partition: routed through the device-resident node
                # vector (replaces the one-reducer-per-segment MR job +
                # HDFS renames of DataPartitioner.java:95-129)
                child_ids = np.asarray(node.children, np.int32)
                attr_arr[ki] = sp.attr
                child_tab[ki] = child_ids[sp.seg_of_bin]
                split_records.append((ki, list(node.children), seg_counts))
            if collect:
                t_sel = time.perf_counter()
            # no next level (or nothing split) → the updated vector would
            # never be read; skip the dispatch
            if new_frontier and (child_tab >= 0).any():
                node_dev = _apply_level_partition(
                    codes_dev, node_dev, remap_dev,
                    jnp.asarray(attr_arr), jnp.asarray(child_tab))
                if collect:
                    # see the table-phase barrier note above
                    jax.block_until_ready(node_dev)  # graftlint: disable=GL005
            sub_plan = (self._subtract_plan(split_records, new_frontier,
                                            len(nodes))
                        if use_subtract and new_frontier else None)
            if collect:
                t_end = time.perf_counter()
                self.level_stats.append({
                    "level": depth, "frontier": k,
                    "contracted_slots": k_contracted,
                    "path": path_lv,
                    # the contraction's true dot width ON THE PATH THIS
                    # LEVEL TOOK: the cross kernel pads the selector to
                    # 128-lane tiles, a packed level pays the joint pack
                    # width (pack_disjoint is pure — same plan it built),
                    # the einsum fallback scales with K·C directly
                    "sel_width": (
                        pallas_hist.cross_sel_width(k_contracted * c)
                        if path_lv == "cross" else
                        pallas_hist.pack_disjoint(
                            k_contracted, ds.num_binned, ds.max_bins,
                            max(c, 1)).wp if path_lv == "packed" else
                        k_contracted * c),
                    "table_ms": round((t_tab - t_lv) * 1e3, 3),
                    "select_ms": round((t_sel - t_tab) * 1e3, 3),
                    "partition_ms": round((t_end - t_sel) * 1e3, 3)})
            frontier = new_frontier
        return DecisionTreeModel(nodes=nodes, class_values=list(ds.class_values),
                                 max_bins=ds.max_bins, algorithm=self.algorithm,
                                 depth_cap=self.max_depth,
                                 split_cap=(2 if self.split_search == "binary"
                                            else self.max_split))

    @staticmethod
    def _subtract_plan(split_records, new_frontier, num_nodes: int):
        """Host-side plan (tiny) for the next level's sibling-subtraction
        table: per split parent with frontier children, pick the
        largest-mass segment g* (stable: lowest g on ties) as the DERIVED
        child and mark every other segment's child a DIRECT contraction
        slot (settled siblings included — the subtraction needs them);
        when the g* child itself is settled, only the frontier children
        are contracted (nothing needs deriving there).  Returns
        (remap_direct [num_nodes] abs id → slot, dslot [K] (−1 =
        derived), pslot [K] parent's previous-level local index,
        sib_mat [K, D] direct-sibling one-hot, D)."""
        fs = set(new_frontier)
        direct_ids: List[int] = []
        dslot_of: Dict[int, int] = {}
        derived_info: Dict[int, Tuple[int, List[int]]] = {}
        for ki, child_ids, masses in split_records:
            in_f = [cid for cid in child_ids if cid in fs]
            if not in_f:
                continue
            gstar = int(np.argmax(np.asarray(masses)))
            gstar_child = child_ids[gstar]
            if gstar_child in fs:
                members = [cid for g, cid in enumerate(child_ids)
                           if g != gstar]
                derived_info[gstar_child] = (ki, members)
            else:
                members = in_f
            for cid in members:
                dslot_of[cid] = len(direct_ids)
                direct_ids.append(cid)
        kd = len(direct_ids)
        kf = len(new_frontier)
        remap_direct = np.full(num_nodes, -1, np.int32)
        for cid, sl in dslot_of.items():
            remap_direct[cid] = sl
        dslot = np.full(kf, -1, np.int32)
        pslot = np.zeros(kf, np.int32)
        sib_mat = np.zeros((kf, kd), np.int32)
        for k2, cid in enumerate(new_frontier):
            if cid in derived_info:
                kp, members = derived_info[cid]
                pslot[k2] = kp
                for m in members:
                    sib_mat[k2, dslot_of[m]] = 1
            else:
                dslot[k2] = dslot_of[cid]
        return remap_direct, dslot, pslot, sib_mat, kd

    def predict(self, model: DecisionTreeModel, ds: EncodedDataset,
                validate: bool = False, pos_class: Optional[str] = None):
        walk = predict_fn(model)
        pred, distr = walk(jnp.asarray(ds.codes))
        pred, distr = np.asarray(pred), np.asarray(distr)
        counters = Counters()
        cm = None
        if validate:
            if ds.labels is None:
                raise ValueError("validation requires labels")
            cm = ConfusionMatrix(model.class_values, pos_class=pos_class)
            cm.add_batch(ds.labels, pred)
            cm.publish(counters)
        return pred, distr, cm, counters


class RandomForest:
    """Bagged ensemble of randomK trees (the composition the reference
    gestures at via its random attribute-selection strategy + BaggingSampler)."""

    def __init__(self, num_trees: int = 10, seed: int = 0, **tree_kwargs):
        tree_kwargs.setdefault("attr_strategy", "randomK")
        self.num_trees = num_trees
        self.seed = seed
        self.tree_kwargs = tree_kwargs

    def fit(self, ds: EncodedDataset,
            is_categorical: Optional[Sequence[bool]] = None) -> List[DecisionTreeModel]:
        from avenir_tpu.models.samplers import bagging_sample
        models = []
        for t in range(self.num_trees):
            sample = bagging_sample(jax.random.PRNGKey(self.seed * 1000 + t), ds)
            tree = DecisionTree(seed=self.seed * 1000 + t, **self.tree_kwargs)
            models.append(tree.fit(sample, is_categorical))
        return models

    def predict(self, models: List[DecisionTreeModel], ds: EncodedDataset):
        votes = np.zeros((ds.num_rows, len(models[0].class_values)), np.float32)
        for m in models:
            _, distr, _, _ = DecisionTree().predict(m, ds)
            votes += distr
        votes /= len(models)
        return np.argmax(votes, axis=1).astype(np.int32), votes
