"""MXU co-occurrence histogram — the Pallas count kernel behind NB+MI.

The count tables of the flagship pipeline (the rebuild of the reference's
``explore/MutualInformation.java:236-403`` combiner/reducer and
``bayesian/BayesianDistribution.java:203-328`` shuffle) were previously
one-hot einsums that XLA lowers to scatter-adds — measured wall of
~7 G updates/s (66 updates/row on the hosp_readmit shape, <1% of any
hardware peak; round-2 perf notes, git history).  This kernel replaces the
scatter lowering entirely:

    every NB/MI count table is a sub-block of  G = Xᵀ X,
    where X is the [N, W] one-hot of the joint (feature, bin, class) code.

X is never materialized in HBM.  The round-4 kernel is FULLY FUSED and
COLUMNAR: it streams the [F, N] int32 code array and the [1, N] labels
through VMEM in column blocks, computes the joint code, expands the block
to Xᵀ int8 in VMEM, and accumulates G = XᵀX on the int8 MXU path in int32.
Nothing but the raw codes ever crosses HBM — no XLA transpose, no joint
materialization (round 4 measured the round-3 prologue at ~11 ms of the
~50 ms 16M-row chunk; benchmarks/cooc_expand_sweep.py).

Three expansion layouts, routed statically by :func:`plan`:

- ``fmaj`` (primary): a 3-D broadcast compare
  ``(joint[:, None, :] == iota_jc32)`` producing int8 directly — jc is
  padded to 32 so the int8 (32, 128) tiling is clean and the reshape to
  [F·jc32, BN] is a no-op tile collapse.  Row w = f·jc32 + (bin·C + cls).
  Used whenever the jc padding does not inflate the padded gram width.
- ``jmaj`` (fallback for shapes where it would): the round-3 tile-
  concatenate + iota//F compare; row w = (bin·C + cls)·F + f.
- ``cls`` (wide schemas, F·B·C beyond MAX_W): G [C, Wcp, Wcp] as C
  per-class grams over w = bin·F + f — the cross-class blocks of the
  joint gram are zero by construction, so the split cuts the dot work
  C× where 2-D blocking of the joint gram would merely repartition the
  same W² work.  This closes the round-3 wide-schema gap (the reference
  handles any cardinality via lazily-sparse reducer maps,
  ``explore/MutualInformation.java:421-432``; here wide shapes
  previously fell silently to the 80-113M rows/s scatter einsum).
- ``clsb`` (round 5, wider still: Wc up to MAX_W_CLSB, C up to
  MAX_C_CLSB): the same per-class gram banded over G's rows — only a
  [C, TR, Wp] accumulator band and one expansion block live in VMEM per
  grid step, so e.g. 100 features × 20 bins × 2 classes (Wc=2048) stays
  on the MXU two tiers past the einsum fallback.

Round-4 bisection (TPU v5 lite, fresh process per variant, chained-
dispatch host-fetch sync, 16M-row chunks, hosp_readmit shape F=11 B=12
C=2, Wp=384 — benchmarks/cooc_expand_sweep.py, dot_orient_probe.py,
xla_gram_probe.py):

- round-3 shipped kernel (XLA transpose + joint prologue + j-major
  in-VMEM expand) vs the fused columnar fmaj kernel, measured
  BACK-TO-BACK in one session: 319M → **381M rows/s median
  (+19%)**, insensitive to block_cols 49k→98k.  Absolute rates on this
  rig drift ±20% on ~30-minute scales (the identical fused config
  re-measured 333M half an hour later; r3's driver artifact captured
  366M for the old kernel) — only same-session A/B deltas are
  comparable;
- zero-expand floor (dot + streaming only): 37.8 ms/chunk — i.e. the
  expand costs ~4 ms (~10%), NOT the ~60% round 3 estimated;
- the governing wall is the W=384 int8 gram itself: ~115-125 effective
  TOPS (~30% of the 394 int8 peak) in BOTH Mosaic and bare XLA (bare-XLA
  dot on a pre-materialized HBM one-hot: 43.5 ms per 16M rows — slower
  than this whole kernel).  bf16 (83 int8-equiv TOPS), int4 (emulated,
  21 TOPS), batched-gram and distinct-operand forms all measure worse;
  XLA's gram efficiency rises with W (255 TOPS at W=1152), so the
  small-output gram is the documented compiler/hardware ceiling at this
  schema width.

Exactness: int8 operands are 0/1, int32 accumulation — per-chunk counts
are exact up to 2^31 rows (the einsum path's f32 accumulation capped
chunks at 2^24; callers keep that cap so both paths stay interchangeable).
Out-of-range codes produce joint codes outside [0, B·C) and drop out, and
out-of-range labels invalidate the whole row — bit-identical semantics to
``ops/agg.py::pair_class_counts``'s drop-invalid contract.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# joint-code marker for invalid rows / padding: never equals a selector
# value (selectors are in [0, B·C) plus the pad marker below)
_INVALID = -(1 << 20)
_PAD_SEL = -(1 << 20) - 1

# The XᵀX pass costs ~2·Wp² int8-MXU FLOP per row; past Wp≈768 the joint
# gram loses ground, so wider shapes switch to the per-class mode below
# (and past its gates, to the scatter einsum).
MAX_W = 768

# Per-class mode ("cls", round 4): cross-class blocks of G are zero by
# construction, so C grams of width Wc = F·B cost 2·C·Wc² = 2·W²/C per
# row — a C× FLOP cut that no 2-D blocking of the joint gram can match
# (blocking repartitions the same W² work).  Routed for shapes the joint
# gram can't take; per-class width and class count are gated so the
# [C, Wcp, Wcp] accumulator and the expansion block stay in VMEM.
MAX_W_CLS = 1536
MAX_C_CLS = 8
MAX_G_BYTES_CLS = 25 * 1024 * 1024

# Blocked per-class mode ("clsb", round 5): same per-class gram math as
# "cls", but G [C, wp, wp] lives in HBM and the kernel accumulates one
# [C, TR, wp] ROW BAND per grid step — only the band (≤ the budget below),
# the expansion block and the codes block occupy VMEM, so the per-class
# width extends to MAX_W_CLSB.  The expansion is recomputed once per
# (band, column-block); that costs ~3·wp·BN ops against the band's
# 2·C·TR·wp·BN MAC dot — a ~3/(2·C·TR) ≈ 0.1% overhead, which is why
# banding the OUTPUT (not re-tiling the input) is the right split.
MAX_W_CLSB = 6144
MAX_C_CLSB = 16
_ACC_BYTES_CLSB = 35 * 1024 * 1024

# column-block default for the fmaj (int8-only-VMEM) expand; the jmaj
# fallback materializes an int32 [Wp, BN] block and scales down harder
_DEFAULT_BN = 98304


def _ru(x: int, m: int) -> int:
    return -(-x // m) * m


# Width-slack factor, shared by two routing decisions that trade a wider
# gram against a cheaper program:
#
# - fmaj-vs-jmaj (round 7): the fmaj broadcast expand keeps only int8 in
#   VMEM, while jmaj materializes an int32 [Wp, BN] block — measured
#   round 4 at +19% for fmaj at EQUAL width, and the one-class Cramér
#   gram (jmaj, wp=256) ran at ~33 effective TOPS against the 115-125
#   TOPS the fmaj W=384 gram sustains, i.e. jmaj's expand overhead
#   dwarfs a ≤1.5× wider dot at these widths.  So fmaj is preferred
#   unless its padding widens the gram by MORE than this factor (the
#   Cramér family shape 10×20×1 — wp 384 vs 256 — now rides fmaj).
# - the PackGraft cost model (round 16, :func:`pack_tables`): one joint
#   gram dispatch replaces the chunked-einsum fold's per-table one-hot
#   contractions when the padded gram width stays within this slack of
#   the unpacked fold's per-row cell volume — the same "a modestly wider
#   dot beats a cheaper-on-paper but scatter-lowered program" judgment,
#   anchored by the measured packed-vs-unpacked fold A/B
#   (benchmarks/wide_schema_bench.py --path pack).
WIDTH_SLACK = 1.5


def plan(num_feat: int, num_bins: int, num_classes: int):
    """Static layout plan → (mode, jcp, wp).

    ``fmaj``: w = f·jcp + (bin·C + cls), jcp = jc rounded up to 32 (clean
    int8 tiling for the broadcast expand).  Chosen unless that padding
    would widen the padded gram (wp) by more than ``WIDTH_SLACK`` versus
    the j-major packing — the dot is the dominant cost at large widths,
    but at kernel-eligible widths the int8-only expand buys back a
    modestly wider gram (see WIDTH_SLACK).

    ``cls`` (wide shapes): G is [C, wp, wp] with per-class row index
    w = bin·F + f (j-major within the class) — the per-class gram split
    that cuts the dot work C× versus the joint gram.
    """
    jc = num_bins * num_classes
    jcp32 = _ru(jc, 32)
    wp32 = _ru(num_feat * jcp32, 128)
    wpj = _ru(num_feat * jc, 128)
    if wp32 <= wpj or (wp32 <= MAX_W and wp32 <= WIDTH_SLACK * wpj):
        narrow = ("fmaj", jcp32, wp32)
    else:
        narrow = ("jmaj", jc, wpj)
    if narrow[2] <= MAX_W:
        return narrow
    wcp = _ru(num_feat * num_bins, 128)
    if (wcp <= MAX_W_CLS and 2 <= num_classes <= MAX_C_CLS
            and num_classes * wcp * wcp * 4 <= MAX_G_BYTES_CLS):
        return "cls", num_bins, wcp
    tile = clsb_tile(num_feat, num_bins, num_classes)
    if tile is not None:
        return "clsb", num_bins, tile[1]
    return narrow          # too wide for any kernel; applicable() rejects


def clsb_tile(num_feat: int, num_bins: int, num_classes: int):
    """(row-band height TR, padded per-class width wp) for the blocked
    per-class mode, or None when the shape is outside its gates.

    A band is a WHOLE NUMBER OF BINS (TR = F·k): in the j-major layout
    w = bin·F + f, a bin-aligned band's rows are ``code[i % F]`` compared
    against ``r·k + i//F`` — constructible in-kernel from static concats
    plus the scalar band offset (Mosaic has no dynamic_slice, so the band
    CANNOT be sliced out of a full-width expansion).  k is the largest
    power-of-2 scale with TR ≈ 512 whose [C, TR, wp] int32 accumulator
    band fits the VMEM budget; wp pads the BIN count to a multiple of k
    (pad bins select ``_PAD_SEL`` and stay exactly zero in G).  Pure
    function of the shape — plan(), the kernel and the tests must all
    derive the identical tiling."""
    wcp = _ru(num_feat * num_bins, 128)
    if not (MAX_W_CLS < wcp or num_classes > MAX_C_CLS
            or num_classes * wcp * wcp * 4 > MAX_G_BYTES_CLS):
        return None                      # plain cls mode serves it
    if wcp > MAX_W_CLSB or not 2 <= num_classes <= MAX_C_CLSB:
        return None
    import math

    # Mosaic block rule: the band (second-to-last out dim) must be
    # divisible by 8 — so k must be a multiple of 8/gcd(F, 8).  Among the
    # VMEM-feasible k, prefer the one minimizing the padded width (bin
    # padding inflates the dot work quadratically), then the largest k
    # (fewer bands → less expansion recompute).
    m = 8 // math.gcd(num_feat, 8)
    kmax = _ru(max(512 // num_feat, 1), m) + m
    best = None
    for k in range(m, kmax + 1, m):
        tr = num_feat * k
        wp = num_feat * _ru(num_bins, k)
        if wp > MAX_W_CLSB or num_classes * tr * wp * 4 > _ACC_BYTES_CLSB:
            continue
        key = (wp, -k)
        if best is None or key < best[0]:
            best = (key, (tr, wp))
    return best[1] if best else None


def g_key(num_feat: int, num_bins: int, num_classes: int) -> str:
    """Accumulator/checkpoint key for a G matrix of this shape's layout.
    Layout-qualified so a snapshot written under a DIFFERENT kernel layout
    (e.g. the round-3 j-major key ``"g"``) can never be silently summed
    with this layout's counts — resume code must detect and reject it.
    The w_index layout is a pure function of (F, B, C), so the key carries
    all three: keying on derived quantities alone (mode, jcp, wp) collides
    for distinct schemas — e.g. (F=11,B=12,C=2) and (F=11,B=8,C=4) share
    ('fmaj', 32, 384) but place j = bin·C + cls differently."""
    mode, _, _ = plan(num_feat, num_bins, num_classes)
    return f"g:{mode}:f{num_feat}:b{num_bins}:c{num_classes}"


def w_index(num_feat: int, num_bins: int, num_classes: int) -> np.ndarray:
    """[F, B, C] int64 array of each cell's row/col index in G (layout per
    :func:`plan`) — the single source of truth for G readout and tests.
    In ``cls`` mode the index is within class c's [wp, wp] gram (G is
    [C, wp, wp]); it is the same for every c."""
    mode, jcp, _ = plan(num_feat, num_bins, num_classes)
    if mode in ("cls", "clsb"):
        w2 = np.arange(num_bins)[None, :] * num_feat \
            + np.arange(num_feat)[:, None]
        return np.repeat(w2[:, :, None], num_classes, axis=2).astype(np.int64)
    j = np.arange(num_bins)[:, None] * num_classes + np.arange(num_classes)
    if mode == "fmaj":
        return (np.arange(num_feat)[:, None, None] * jcp + j[None]).astype(
            np.int64)
    return (j[None] * num_feat
            + np.arange(num_feat)[:, None, None]).astype(np.int64)


def default_block_cols(wp: int, mode: str = "fmaj") -> int:
    """Column block sized so the expansion stays inside the ~110 MB VMEM
    budget the kernel compiles against.  fmaj materializes only the int8
    [wp, BN] one-hot; jmaj/cls also hold an int32 [wp, BN] block (cls
    further keeps the [C, wp, wp] accumulator resident)."""
    if mode == "fmaj":
        bn = min(_DEFAULT_BN, (72 * 1024 * 1024) // max(wp, 128))
    elif mode == "cls":
        bn = min(49152, (64 * 1024 * 1024) // (5 * max(wp, 128)))
    elif mode == "clsb":
        # int32 jrept (4 B) + bool hit + int8 xt ≈ 6 B per (w, col) cell,
        # beside the [C, TR, wp] band the budget in clsb_tile reserves
        bn = (50 * 1024 * 1024) // (6 * max(wp, 128))
    else:
        bn = 49152 * 384 // max(wp, 128)
    return max(128, (bn // 128) * 128)


def _cooc_kernel(codes_ref, labels_ref, out_ref, *, f: int, jc: int,
                 jcp: int, wp: int, n: int, nclass: int, mode: str):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ct = codes_ref[:]                                  # [F, BN] int32
    y = labels_ref[:]                                  # [1, BN] int32
    bn = ct.shape[1]
    valid = (y >= 0) & (y < nclass)
    # ragged tail: lanes past the true row count read garbage from the
    # out-of-bounds block — neutralize them here instead of paying a
    # full-array jnp.pad copy outside (~10 ms/chunk at 16M rows)
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        valid &= lane < n - i * bn
    joint = jnp.where(valid, ct * nclass + y, _INVALID)
    # out-of-range codes (≥ B) must drop out, not land on fmaj pad cells
    # (jc ≤ iota < jcp): one [F, BN] clamp keeps G's outside-the-index-set
    # cells exactly zero in both modes
    joint = jnp.where(joint < jc, joint, _INVALID)
    if mode == "fmaj":
        # broadcast compare straight to int8 — no int32 [W, BN] copy; the
        # [F, jc32, BN] → [F·jc32, BN] reshape is a no-op tile collapse
        # because jc32 is a whole number of int8 sublane tiles
        jv = jax.lax.broadcasted_iota(jnp.int32, (1, jcp, 1), 1)
        xt = (joint[:, None, :] == jv).astype(jnp.int8)
        xt = xt.reshape(f * jcp, bn)
        if wp > f * jcp:
            xt = jnp.concatenate(
                [xt, jnp.zeros((wp - f * jcp, bn), jnp.int8)], axis=0)
    else:
        # j-major tile-expand: row w of the result is joint[w mod F]
        w = f * jc
        jrept = jnp.concatenate([joint] * jc, axis=0)  # [W, BN]
        if wp > w:
            jrept = jnp.concatenate(
                [jrept, jnp.full((wp - w, bn), _INVALID, jnp.int32)], axis=0)
        jw = jax.lax.broadcasted_iota(jnp.int32, (wp, 1), 0)
        jsel = jnp.where(jw < w, jw // f, _PAD_SEL)
        # int8 one-hot straight from the int32 compare: int8 compare/select
        # is not lowerable (Mosaic), int32→int8 select is
        xt = (jrept == jsel).astype(jnp.int8)          # [Wp, BN] = Xᵀ block
    acc = jax.lax.dot_general(xt, xt, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out_ref[:] += acc


def _cooc_cls_kernel(codes_ref, labels_ref, out_ref, *, f: int, b: int,
                     wp: int, n: int, nclass: int):
    """Per-class gram: one shared j-major expansion compare per block, a
    class mask folded into the one-hot select, C sequential int8 dots."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ct = codes_ref[:]                                  # [F, BN] int32
    y = labels_ref[:]                                  # [1, BN] int32
    bn = ct.shape[1]
    code = jnp.where((ct >= 0) & (ct < b), ct, _INVALID)
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        code = jnp.where(lane < n - i * bn, code, _INVALID)
    w = f * b
    jrept = jnp.concatenate([code] * b, axis=0)        # [W, BN]
    if wp > w:
        jrept = jnp.concatenate(
            [jrept, jnp.full((wp - w, bn), _INVALID, jnp.int32)], axis=0)
    jw = jax.lax.broadcasted_iota(jnp.int32, (wp, 1), 0)
    jsel = jnp.where(jw < w, jw // f, _PAD_SEL)
    hit = jrept == jsel                                # class-independent
    for c in range(nclass):
        xt = (hit & (y == c)).astype(jnp.int8)         # [Wp, BN]
        acc = jax.lax.dot_general(xt, xt, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        out_ref[c] += acc


def _cooc_clsb_kernel(codes_ref, labels_ref, out_ref, *, f: int, b: int,
                      wp: int, tr: int, n: int, nclass: int):
    """Blocked per-class gram: grid (row-band, column-block), band outer.
    Each step builds the full-width expansion for the column block plus a
    BAND-LOCAL expansion of the band's TR = F·k rows (a whole number of
    bins — Mosaic has no dynamic_slice, so the band is reconstructed from
    the same static concat with its bin offset ``r·k`` folded into the
    selector; both expansions together are negligible against the band
    dot), then accumulates [C, TR, wp] into the HBM-resident G's band
    (revisited across column blocks, initialized at block 0)."""
    r = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ct = codes_ref[:]                                  # [F, BN] int32
    y = labels_ref[:]                                  # [1, BN] int32
    bn = ct.shape[1]
    k = tr // f                                        # bins per band
    nb_pad = wp // f                                   # padded bin count
    code = jnp.where((ct >= 0) & (ct < b), ct, _INVALID)
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        code = jnp.where(lane < n - i * bn, code, _INVALID)
    # full-width expansion: row w holds (code[w % f] == w // f)
    jrept = jnp.concatenate([code] * nb_pad, axis=0)   # [Wp, BN]
    jw = jax.lax.broadcasted_iota(jnp.int32, (wp, 1), 0)
    jsel = jnp.where(jw // f < b, jw // f, _PAD_SEL)
    hit = jrept == jsel                                # [Wp, BN]
    # band-local expansion: bins [r·k, (r+1)·k), same static concat with
    # the scalar bin offset folded into the selector
    brept = jnp.concatenate([code] * k, axis=0)        # [TR, BN]
    bw = jax.lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
    bbin = r * k + bw // f
    bsel = jnp.where(bbin < b, bbin, _PAD_SEL)
    bhit = brept == bsel                               # [TR, BN]
    for c in range(nclass):
        xb = (bhit & (y == c)).astype(jnp.int8)        # [TR, BN]
        xt = (hit & (y == c)).astype(jnp.int8)         # [Wp, BN]
        acc = jax.lax.dot_general(xb, xt, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        out_ref[c] += acc                              # [TR, Wp] band


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_cols", "interpret"))
def cooc_counts_cols(codes_t: jax.Array, labels: jax.Array, num_bins: int,
                     num_classes: int, *, block_cols: int | None = None,
                     interpret: bool = False) -> jax.Array:
    """codes_t [F, N] int (columnar), labels [N] int → G [Wp, Wp] int32
    co-occurrence counts (row/col index per :func:`w_index`).

    G[w1, w2] = #rows whose feature f1 falls in (b1, c) and f2 in (b2, c)
    — all NB/MI count tables at once.  Cross-class blocks are zero by
    construction (a row has one label).  This is the primary entry: it
    streams the codes exactly as stored, with no transpose and no joint
    materialization anywhere (fused into the kernel)."""
    f, n = codes_t.shape
    mode, jcp, wp = plan(f, num_bins, num_classes)
    out_shape = ((num_classes, wp, wp) if mode in ("cls", "clsb")
                 else (wp, wp))
    if n == 0:
        # empty chunk (e.g. a stream's empty final block): zero counts,
        # matching the einsum path — the kernel's OOB block read would
        # not even trace on a zero-row operand
        return jnp.zeros(out_shape, jnp.int32)
    jc = num_bins * num_classes
    bn = block_cols or default_block_cols(wp, mode)
    ct = codes_t.astype(jnp.int32)
    y2 = labels.reshape(1, n).astype(jnp.int32)
    npad = _ru(max(n, bn), bn)
    if mode == "clsb":
        tr, _wp2 = clsb_tile(f, num_bins, num_classes)
        kernel = functools.partial(_cooc_clsb_kernel, f=f, b=num_bins,
                                   wp=wp, tr=tr, n=n, nclass=num_classes)
        return pl.pallas_call(
            kernel,
            grid=(wp // tr, npad // bn),
            in_specs=[pl.BlockSpec((f, bn), lambda r, i: (0, i),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, bn), lambda r, i: (0, i),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((num_classes, tr, wp),
                                   lambda r, i: (0, r, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=110 * 1024 * 1024),
            interpret=interpret,
        )(ct, y2)
    if mode == "cls":
        kernel = functools.partial(_cooc_cls_kernel, f=f, b=num_bins,
                                   wp=wp, n=n, nclass=num_classes)
        out_specs = pl.BlockSpec((num_classes, wp, wp), lambda i: (0, 0, 0),
                                 memory_space=pltpu.VMEM)
    else:
        kernel = functools.partial(_cooc_kernel, f=f, jc=jc, jcp=jcp, wp=wp,
                                   n=n, nclass=num_classes, mode=mode)
        out_specs = pl.BlockSpec((wp, wp), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=(npad // bn,),
        in_specs=[pl.BlockSpec((f, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=110 * 1024 * 1024),
        interpret=interpret,
    )(ct, y2)


def _cross_kernel(codes_ref, sel_ref, out_ref, *, f: int, b: int, jcp: int,
                  wp: int, sp_dim: int, n: int, nsel: int):
    """Cross co-occurrence XᵀY: X = the (feature, bin) one-hot (fmaj
    broadcast expansion, exactly the count kernel's), Y = the one-hot of
    an arbitrary selector code (e.g. node·C + class for the decision
    tree's level table).  Both expansions live only in VMEM."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ct = codes_ref[:]                                  # [F, BN] int32
    s = sel_ref[:]                                     # [1, BN] int32
    bn = ct.shape[1]
    code = jnp.where((ct >= 0) & (ct < b), ct, _INVALID)
    sel = jnp.where((s >= 0) & (s < nsel), s, _INVALID)
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        live = lane < n - i * bn
        code = jnp.where(live, code, _INVALID)
        sel = jnp.where(live, sel, _INVALID)
    jv = jax.lax.broadcasted_iota(jnp.int32, (1, jcp, 1), 1)
    xt = (code[:, None, :] == jv).astype(jnp.int8).reshape(f * jcp, bn)
    if wp > f * jcp:
        xt = jnp.concatenate(
            [xt, jnp.zeros((wp - f * jcp, bn), jnp.int8)], axis=0)
    sv = jax.lax.broadcasted_iota(jnp.int32, (sp_dim, 1), 0)
    yt = (sel == sv).astype(jnp.int8)                  # [Sp, BN]
    out_ref[:] += jax.lax.dot_general(xt, yt, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)


MAX_SEL_CROSS = 1024


def cross_sel_width(num_sel: int) -> int:
    """Padded selector lane width of the cross gram's dot (the Y side of
    XᵀY pads to whole 128-lane tiles).  The dot work scales linearly
    with this, which is what makes it the honest unit for the decision
    tree's sibling-subtraction accounting (round 13): halving the
    contracted frontier slots only shrinks the kernel dot when K·C
    crosses a 128-lane boundary — the per-level ``sel_width`` in
    ``DecisionTree.level_stats`` reports exactly that."""
    return _ru(max(num_sel, 1), 128)


def cross_applicable(num_feat: int, num_bins: int, num_sel: int) -> bool:
    """Gate for the cross kernel: the X side obeys the joint-gram width
    cap and the selector side stays small (its padded lane width scales
    the dot work linearly)."""
    if num_feat * num_bins <= 0 or num_sel <= 0:
        return False
    jcp = _ru(num_bins, 32)
    wp = _ru(num_feat * jcp, 128)
    return wp <= MAX_W and num_sel <= MAX_SEL_CROSS


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_sel", "block_cols", "interpret"))
def cross_cooc_counts_cols(codes_t: jax.Array, sel: jax.Array,
                           num_bins: int, num_sel: int, *,
                           block_cols: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """codes_t [F, N] int (columnar), sel [N] int (−1/out-of-range rows
    drop out) → [F, B, num_sel] int32 counts of each (feature, bin,
    selector) co-occurrence — computed as the int8-MXU cross gram XᵀY
    with both one-hots expanded in VMEM (never in HBM).

    The decision tree's per-level [F, B, K, C] table is this with
    sel = node·C + class (``models/tree.py::node_bin_class_counts``):
    the einsum form it replaces materializes the [N, F, B] one-hot in
    HBM (~400 B/row/level at the retarget shape vs the ~24 B/row the
    kernel streams)."""
    f, n = codes_t.shape
    jcp = _ru(num_bins, 32)
    wp = _ru(f * jcp, 128)
    sp_dim = _ru(num_sel, 128)
    if n == 0:
        return jnp.zeros((f, num_bins, num_sel), jnp.int32)
    # budget BOTH int8 expansions ([wp, BN] X and [sp_dim, BN] Y) against
    # the VMEM limit — the fmaj budget alone ignores Y and a large padded
    # selector width could push past vmem_limit_bytes at compile time
    bn = block_cols or max(128, min(
        _DEFAULT_BN,
        (72 * 1024 * 1024) // max(wp + sp_dim, 128)) // 128 * 128)
    ct = codes_t.astype(jnp.int32)
    s2 = sel.reshape(1, n).astype(jnp.int32)
    npad = _ru(max(n, bn), bn)
    kernel = functools.partial(_cross_kernel, f=f, b=num_bins, jcp=jcp,
                               wp=wp, sp_dim=sp_dim, n=n, nsel=num_sel)
    g = pl.pallas_call(
        kernel,
        grid=(npad // bn,),
        in_specs=[pl.BlockSpec((f, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((wp, sp_dim), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((wp, sp_dim), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=110 * 1024 * 1024),
        interpret=interpret,
    )(ct, s2)
    # [Wp, Sp] → [F, B, num_sel]: row f·jcp + b (wp padding dropped), col s
    return g[:f * jcp].reshape(f, jcp, sp_dim)[:, :num_bins, :num_sel]


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_cols", "interpret"))
def cooc_counts(codes: jax.Array, labels: jax.Array, num_bins: int,
                num_classes: int, *, block_cols: int | None = None,
                interpret: bool = False) -> jax.Array:
    """Row-major convenience wrapper: codes [N, F] → one XLA transpose
    (HBM-bound, ~11 ms per 16M rows on the dev rig) then the fused
    columnar kernel.  Callers that hold columnar codes should use
    :func:`cooc_counts_cols` and skip the transpose entirely."""
    return cooc_counts_cols.__wrapped__(
        codes.T, labels, num_bins, num_classes, block_cols=block_cols,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_cols", "interpret"))
def gram_moments(codes: jax.Array, labels: jax.Array, cont: jax.Array,
                 num_bins: int, num_classes: int, *,
                 block_cols: int | None = None,
                 interpret: bool = False):
    """Single-dispatch SharedScan step (round 7): the co-occurrence gram G
    of the chunk's binned codes PLUS the class-conditional (count, Σx, Σx²)
    moments of the SAME device-resident continuous block, as ONE compiled
    program — so a scan serving NB + MI + Cramér + Fisher/NumericalAttrStats
    consumers (``pipeline/scan.py``) pays one dispatch per chunk, exactly
    like the single-job fast path.

    codes [N, F] int, labels [N] int, cont [N, Fc] float →
    (G, cnt [C], s1 [C, Fc], s2 [C, Fc]).  G and the count tensors derived
    from it are bit-identical to :func:`cooc_counts`; the moment sums are
    the same ``agg.class_moments`` contraction the standalone fits run."""
    from avenir_tpu.ops import agg

    g = cooc_counts_cols.__wrapped__(codes.T, labels, num_bins, num_classes,
                                     block_cols=block_cols,
                                     interpret=interpret)
    cnt, s1, s2 = agg.class_moments.__wrapped__(cont, labels, num_classes)
    return g, cnt, s1, s2


def _gram_block_rows(num_feat: int, depth: int, wp: int) -> int:
    """Row block for the einsum gram: bounded by a ~64 MB f32 intermediate
    budget (the [br, F, depth] one-hot plus the [br, wp] layout view and
    its dot operand copy) AND by 2^16 so every per-block f32 matmul sum is
    integer-exact with margin (counts ≤ br « 2^24)."""
    per_row = 4 * max(num_feat * depth + 2 * wp, 1)
    return max(256, min(1 << 16, (1 << 26) // per_row) // 128 * 128)


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_rows"))
def gram_counts_cols(codes_t: jax.Array, labels: jax.Array, num_bins: int,
                     num_classes: int, *,
                     block_rows: int | None = None) -> jax.Array:
    """The co-occurrence gram G as ONE exact einsum dispatch — the packed
    fold's device program (PackGraft, round 16) for hosts where the Pallas
    kernel doesn't run (the chunked-einsum routing's territory).

    Bit-identical to :func:`cooc_counts_cols` for EVERY plan mode: the
    one-hot X is laid out per :func:`plan`/:func:`w_index` (fmaj
    w = f·jcp + (bin·C + cls); jmaj w = (bin·C + cls)·F + f; cls/clsb
    per-class w = bin·F + f with G [C, wp, wp]), pad cells stay exactly
    zero, out-of-range codes drop per-feature and out-of-range labels
    drop the whole row — the drop-invalid contract.  Rows are processed
    in f32-exact blocks with int32 accumulation (the same exactness
    argument as ``models/tree.py::node_bin_class_counts``), so any N is
    exact.

    Versus the chunked-einsum fold this ONE [br, wp]ᵀ[br, wp] matmul
    replaces the per-table one-hot contractions XLA lowers to
    scatter-adds — the packing planner (:func:`pack_tables`) decides when
    that trade pays."""
    f, n = codes_t.shape
    mode, jcp, wp = plan(f, num_bins, num_classes)
    cls_mode = mode in ("cls", "clsb")
    out_shape = (num_classes, wp, wp) if cls_mode else (wp, wp)
    if n == 0:
        return jnp.zeros(out_shape, jnp.int32)
    jc = num_bins * num_classes
    depth = (wp // f if mode == "clsb" else
             num_bins if mode == "cls" else
             jcp if mode == "fmaj" else jc)
    br = block_rows or _gram_block_rows(f, depth, wp)
    ct = codes_t.astype(jnp.int32)
    y = labels.astype(jnp.int32)
    npad = _ru(n, br)
    if npad > n:
        # pad rows carry label −1: the row-validity mask below drops them
        # from every mode, so padding is pure shape ballast
        ct = jnp.pad(ct, ((0, 0), (0, npad - n)), constant_values=_INVALID)
        y = jnp.pad(y, (0, npad - n), constant_values=-1)
    lanes = jnp.arange(depth)

    def block_joint(cb, yb):
        # joint code j = bin·C + cls; invalid labels kill the whole row,
        # out-of-range codes kill the cell — the compare against the lane
        # iota then leaves those one-hot rows all-zero (j = −1)
        ok = ((yb >= 0) & (yb < num_classes))[None, :] \
            & (cb >= 0) & (cb < num_bins)
        j = jnp.where(ok, cb * num_classes + yb[None, :], -1)   # [F, br]
        oh = (j[:, :, None] == lanes).astype(jnp.float32)       # [F, br, d]
        if mode == "fmaj":
            x = oh.transpose(1, 0, 2).reshape(br, f * depth)
        else:
            x = oh.transpose(1, 2, 0).reshape(br, depth * f)
        if wp > x.shape[1]:
            x = jnp.pad(x, ((0, 0), (0, wp - x.shape[1])))
        return jnp.dot(x.T, x, precision="highest").astype(jnp.int32)

    def block_cls(cb, yb):
        code = jnp.where((cb >= 0) & (cb < num_bins), cb, -1)   # [F, br]
        oh = (code[:, :, None] == lanes).astype(jnp.float32)    # [F, br, d]
        x = oh.transpose(1, 2, 0).reshape(br, depth * f)        # w = b·F + f
        if wp > x.shape[1]:                    # cls pads past F·B, at the end
            x = jnp.pad(x, ((0, 0), (0, wp - x.shape[1])))
        gs = []
        for c in range(num_classes):
            xc = x * (yb == c).astype(jnp.float32)[:, None]
            gs.append(jnp.dot(xc.T, xc,
                              precision="highest").astype(jnp.int32))
        return jnp.stack(gs)

    block = block_cls if cls_mode else block_joint
    g, _ = jax.lax.scan(
        lambda acc, xs: (acc + block(xs[0], xs[1]), None),
        jnp.zeros(out_shape, jnp.int32),
        (ct.reshape(f, npad // br, br).transpose(1, 0, 2),
         y.reshape(npad // br, br)))
    return g


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_rows"))
def gram_counts(codes: jax.Array, labels: jax.Array, num_bins: int,
                num_classes: int, *,
                block_rows: int | None = None) -> jax.Array:
    """Row-major wrapper of :func:`gram_counts_cols` (codes [N, F]) — the
    packed ChunkFolder step's entry, mirroring :func:`cooc_counts`."""
    return gram_counts_cols.__wrapped__(codes.T, labels, num_bins,
                                        num_classes, block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "block_rows"))
def gram_counts_moments(codes: jax.Array, labels: jax.Array,
                        cont: jax.Array, num_bins: int, num_classes: int, *,
                        block_rows: int | None = None):
    """Packed-fold analog of :func:`gram_moments`: the einsum gram PLUS
    the class-conditional continuous moments of the same resident chunk,
    one compiled program — so a packed SharedScan chunk pays one dispatch
    exactly like the kernel fast path does."""
    from avenir_tpu.ops import agg

    g = gram_counts_cols.__wrapped__(codes.T, labels, num_bins, num_classes,
                                     block_rows=block_rows)
    cnt, s1, s2 = agg.class_moments.__wrapped__(cont, labels, num_classes)
    return g, cnt, s1, s2


def counts_from_cooc(g, num_feat: int, num_bins: int, num_classes: int,
                     ci, cj):
    """Host-side (numpy) read-out of the reference-shaped count tensors
    from G:  → (fbc [F, B, C], pair [P, B, B, C]), dtype preserved.

    This runs ONCE per job on a ~100 KB–1 MB matrix (microseconds of
    numpy) — on-device extraction was measured at 20-30 ms/call on the
    dev TPU (every gather / diagonal / batched-einsum formulation lowers
    to scalar loops or pathological small batched GEMMs), i.e. slower
    than the count kernel itself, so the device hands back G and the host
    does the indexing."""
    g = np.asarray(g)
    b, c = num_bins, num_classes
    wf = w_index(num_feat, b, c)                             # [F, B, C]
    ci = np.asarray(ci, np.int64)
    cj = np.asarray(cj, np.int64)
    p = len(ci)
    if g.ndim == 3:                                          # cls mode
        w2 = wf[:, :, 0]                                     # [F, B]
        fbc = np.stack([g[k][w2, w2] for k in range(c)], axis=-1)
        wi = np.broadcast_to(w2[ci][:, :, None], (p, b, b))
        wj = np.broadcast_to(w2[cj][:, None, :], (p, b, b))
        pair = np.stack([g[k][wi, wj] for k in range(c)], axis=-1)
        return fbc, pair
    fbc = g[wf, wf]
    wi = wf[ci][:, :, None, :]                               # [P, B, 1, C]
    wj = wf[cj][:, None, :, :]                               # [P, 1, B, C]
    pair = g[np.broadcast_to(wi, (p, b, b, c)),
             np.broadcast_to(wj, (p, b, b, c))]
    return fbc, pair


# ---------------------------------------------------------------------------
# PackGraft (round 16): block-diagonal gram packing.
#
# The efficiency-vs-width curve (wide-schema tier as measured in 2026-07: ~77% of int8
# peak at per-class widths ≥ 2000 vs 18-30% at the flagship W=384) makes
# joint width the biggest single-chip lever.  A pack descriptor lays several
# INDEPENDENT narrow tables' one-hot blocks along ONE joint width so all of
# them ride a single wide gram dispatch:
#
#   · cross pack (pack_tables): the members are the FEATURES of one dataset
#     — i.e. the ordinary joint gram G over all features at once, whose
#     off-diagonal blocks are exactly the MI pair tables and whose diagonal
#     blocks are the NB / against-class tables.  "Packing" NB + MI +
#     correlation is then just routing the fold onto ONE G instead of the
#     per-table scatter einsums; byte-identity is by construction
#     (counts_from_cooc reads the same cells the per-table einsums build).
#   · disjoint pack (pack_disjoint): the members are ROW-DISJOINT selectors
#     (e.g. one tree-frontier node per row).  Each member gets a bin STRIPE
#     of the joint bin axis (offset = m·stripe_bins); composite codes
#     code + offset keep every cross-member block structurally zero because
#     no row carries two members.  On clsb the stripe is rounded up to whole
#     bands so members never straddle a band.
#
# The planners return a PackPlan (hashable — usable as a jit static) and the
# pack either routes onto the EXISTING kernels (cooc_counts_cols — the
# joint shape picks its own fmaj/cls/clsb mode, including the banded clsb
# tier) or onto gram_counts_cols, the exact einsum gram, off-TPU.  Packed
# g_keys share the kernel g_key's byte layout but carry a "packed" base so
# checkpoint provenance stays visible to ChunkFolder's foreign-key refusal;
# mesh suffixes attach behind the base exactly as for kernel keys.
# ---------------------------------------------------------------------------


class PackMember(NamedTuple):
    """One table riding a pack: its (F, B, C) shape plus where its block
    starts — a width offset (first w cell) for a cross pack, a bin-stripe
    offset (joint bin = offset + local bin) for a disjoint pack."""
    key: str
    num_feat: int
    num_bins: int
    num_classes: int
    offset: int


class PackPlan(NamedTuple):
    """Descriptor of one packed dispatch: the members plus the JOINT
    (F, B, C) shape handed to plan()/the kernels.  Hashable by
    construction so it can ride jit static_argnames."""
    members: Tuple[PackMember, ...]
    num_feat: int
    num_bins: int          # JOINT bins (disjoint: n_members · stripe_bins)
    num_classes: int
    mode: str              # plan() mode of the joint shape
    wp: int                # padded joint width
    band_bins: int         # clsb band size in bins (0 otherwise)
    stripe_bins: int       # disjoint packs: per-member bin stride, else 0
    disjoint: bool

    @property
    def signature(self) -> str:
        """Composite pack identity for telemetry program registration:
        (site, signature) attributes roofline MFU to THIS packed shape."""
        tag = "d" if self.disjoint else "x"
        return (f"{self.mode}:{tag}{len(self.members)}:f{self.num_feat}"
                f":b{self.num_bins}:c{self.num_classes}:w{self.wp}")

    @property
    def g_key(self) -> str:
        """Checkpoint key of the packed G accumulator — same byte layout
        as g_key(joint shape) (same plan(), same w_index cells), distinct
        base so provenance survives kill-packed → resume-unpacked."""
        return (f"g:packed:{self.mode}:f{self.num_feat}"
                f":b{self.num_bins}:c{self.num_classes}")


def packed_g_key(num_feat: int, num_bins: int, num_classes: int) -> str:
    """The packed-provenance g_key for a joint shape — what a packed
    ChunkFolder writes where an unpacked gram folder writes g_key().
    Byte layout is IDENTICAL to g_key(F, B, C) (both are plan()'s G for
    the same joint shape); only the base string differs, so adopt_state
    can normalize between the two while foreign LAYOUTS still refuse."""
    mode, _, _ = plan(num_feat, num_bins, num_classes)
    return f"g:packed:{mode}:f{num_feat}:b{num_bins}:c{num_classes}"


def pack_tables(num_feat: int, num_bins: int, num_classes: int,
                num_pairs: int, max_width: Optional[int] = None
                ) -> Optional[PackPlan]:
    """Cross-pack planner: fold NB ([F, B, C]) + P MI pair tables
    ([B, B, C] each) + against-class stacks as ONE joint gram, or None
    when the pack does not pay.

    Cost model (shares WIDTH_SLACK with plan()'s fmaj routing): the
    unpacked fold builds F·B + P·B·(1+C) one-hot-contracted cells per
    class-expanded row; the packed gram pays wp² but rides the wide-gram
    MXU tier, so pack iff  wp ≤ WIDTH_SLACK · (F·B + P·B·(1+C))  and wp
    fits the clsb ceiling (the widest tier the kernel attests).  The
    measured CPU einsum crossover (hosp 11×12×2, 55 pairs: 7.2×) sits
    far above this gate; the gate's job is refusing packs where pad
    cells dominate (e.g. pair-poor consumer sets)."""
    if num_feat * num_bins * num_classes <= 0:
        return None
    mode, jcp, wp = plan(num_feat, num_bins, num_classes)
    cap = min(max_width or MAX_W_CLSB, MAX_W_CLSB)
    if wp > cap:
        return None
    cells = num_feat * num_bins + num_pairs * num_bins * (1 + num_classes)
    if wp > WIDTH_SLACK * cells:
        return None
    wf = w_index(num_feat, num_bins, num_classes)
    members = tuple(
        PackMember(key=f"f{i}", num_feat=1, num_bins=num_bins,
                   num_classes=num_classes, offset=int(wf[i].min()))
        for i in range(num_feat))
    band = clsb_tile(num_feat, num_bins, num_classes) if mode == "clsb" \
        else None
    return PackPlan(members=members, num_feat=num_feat, num_bins=num_bins,
                    num_classes=num_classes, mode=mode, wp=wp,
                    band_bins=(band[0] // num_feat if band else 0),
                    stripe_bins=0, disjoint=False)


def pack_disjoint(num_members: int, num_feat: int, num_bins: int,
                  num_classes: int, max_width: Optional[int] = None
                  ) -> Optional[PackPlan]:
    """Disjoint-pack planner: M row-disjoint members (tree sibling nodes),
    each an [F, B, C] table, as one joint gram over M·Bp bins where Bp is
    B rounded up so clsb bands hold WHOLE members (a member never
    straddles a band — its diagonal block stays inside one band and every
    cross-member cell the banded kernel materializes is structurally
    zero).  Returns None when the joint shape exceeds every tier or the
    fixpoint between stripe rounding and clsb's tile choice diverges.

    NOTE the FLOP trade: the joint gram pays ~M× the cells of M separate
    grams (each member's rows also multiply the other members' all-zero
    stripes) — worth it only to reach a faster width tier; callers gate
    on packed_applicable()/platform (architecture.md "when packing does
    NOT pay")."""
    if num_members <= 0 or num_feat * num_bins * num_classes <= 0:
        return None
    bp = num_bins
    mode = wp = None
    for _ in range(4):                       # stripe↔band fixpoint, ≤4 hops
        mode, _, wp = plan(num_feat, num_members * bp, num_classes)
        if mode != "clsb":
            break
        tile = clsb_tile(num_feat, num_members * bp, num_classes)
        if tile is None:
            return None
        k = tile[0] // num_feat              # band size in bins
        bp2 = _ru(num_bins, k)
        if bp2 == bp:
            break
        bp = bp2
    else:
        return None
    cap = min(max_width or MAX_W_CLSB, MAX_W_CLSB)
    if wp > cap or not (mode in ("cls", "clsb") or wp <= MAX_W):
        return None
    members = tuple(
        PackMember(key=f"m{i}", num_feat=num_feat, num_bins=num_bins,
                   num_classes=num_classes, offset=i * bp)
        for i in range(num_members))
    band = clsb_tile(num_feat, num_members * bp, num_classes) \
        if mode == "clsb" else None
    return PackPlan(members=members, num_feat=num_feat,
                    num_bins=num_members * bp, num_classes=num_classes,
                    mode=mode, wp=wp,
                    band_bins=(band[0] // num_feat if band else 0),
                    stripe_bins=bp, disjoint=True)


@functools.partial(jax.jit, static_argnames=("stripe_bins", "member_bins"))
def packed_codes(codes_t: jax.Array, member: jax.Array, stripe_bins: int,
                 member_bins: int) -> jax.Array:
    """Composite codes for a disjoint pack: joint bin = code + m·stripe.

    The mask is against the member's OWN bin count, not the stripe: an
    out-of-range local code must become −1 (dropped by the kernels'
    drop-invalid contract), never bleed into the next member's stripe.
    Rows with member −1 (e.g. tree rows not on the frontier) drop whole."""
    ct = codes_t.astype(jnp.int32)
    mem = member.astype(jnp.int32)
    off = jnp.where(mem >= 0, mem * stripe_bins, 0)[None, :]
    ok = (mem >= 0)[None, :] & (ct >= 0) & (ct < member_bins)
    return jnp.where(ok, ct + off, -1)


def packed_diag_index(pplan: PackPlan) -> np.ndarray:
    """Host-side unpack index for a DISJOINT pack: w cells [F, B, M, C]
    such that G[w, w] (per class for cls modes) is member m's [F, B, C]
    table — the counts_from_cooc-style read-out at joint bin
    offset_m + b."""
    wf = w_index(pplan.num_feat, pplan.num_bins, pplan.num_classes)
    b = pplan.members[0].num_bins
    offs = np.array([mb.offset for mb in pplan.members], np.int64)
    sel = offs[None, :] + np.arange(b)[:, None]              # [B, M]
    return wf[:, sel, :]                                     # [F, B, M, C]


def packed_applicable(pplan: PackPlan) -> bool:
    """Kernel eligibility of the JOINT shape — the packed analog of
    applicable(); routing also needs use_kernel()'s platform gates."""
    return applicable(pplan.num_feat, pplan.num_bins, pplan.num_classes)


def nb_mi_step(codes: jax.Array, labels: jax.Array, ci, cj,
               num_classes: int, num_bins: int, *, interpret: bool = False):
    """Kernel-backed equivalent of
    :func:`avenir_tpu.ops.agg.nb_mi_pipeline_step`:
    → (fbc [F, B, C] int32, pair [P, B, B, C] int32) as numpy arrays.

    Synchronizes (fetches G) — callers that need async chaining should
    run :func:`cooc_counts` per chunk and :func:`counts_from_cooc` once at
    the end, which is how MutualInformation.fit and bench.py use it."""
    g = cooc_counts(codes, labels, num_bins, num_classes,
                    interpret=interpret)
    return counts_from_cooc(g, codes.shape[1], num_bins, num_classes, ci, cj)


def applicable(num_feat: int, num_bins: int, num_classes: int) -> bool:
    """Static shape gate: is some Xᵀ·X form profitable/compilable here?"""
    if num_feat * num_bins * num_classes <= 0:
        return False
    mode, _, wp = plan(num_feat, num_bins, num_classes)
    # the per-class modes are only ever returned with their gates passed
    return mode in ("cls", "clsb") or wp <= MAX_W


def use_kernel(num_feat: int, num_bins: int, num_classes: int,
               mesh=None) -> bool:
    """THE routing predicate for the NB+MI count fast path — single source
    of truth for MutualInformation.fit, bench.py and e2e_pipeline: shape
    applicable, no mesh (the sharded einsum's psum is the attested
    collective), and a single TPU device attached."""
    return (mesh is None and applicable(num_feat, num_bins, num_classes)
            and on_tpu_single_device())


def chunk_pipeline(num_feat: int, num_bins: int, num_classes: int, ci, cj,
                   columnar: bool = False):
    """(step, chain_scalar, is_kernel) for the per-chunk NB+MI device step.

    ``step(codes, labels)`` returns the chunk's count object (G on the
    kernel path, (fbc, pair) on the einsum path); ``chain_scalar(out)``
    extracts the zero int32 scalar benchmarks feed into the next chunk's
    labels operand so one final fetch syncs the whole chain.  Keeping both
    paths' plumbing here means bench.py and e2e_pipeline cannot drift from
    the routing the library itself uses.  With ``columnar=True`` (kernel
    path only) ``step`` takes codes in [F, N] layout and skips the
    transpose."""
    if use_kernel(num_feat, num_bins, num_classes):
        kernel = cooc_counts_cols if columnar else cooc_counts

        def step(codes, labels):
            return kernel(codes, labels, num_bins, num_classes)

        def chain_scalar(out):
            return (out[(0,) * out.ndim] * 0).astype(jnp.int32)

        return step, chain_scalar, True

    from avenir_tpu.ops import agg

    def step(codes, labels):
        return agg.nb_mi_pipeline_step(codes, labels, ci, cj,
                                       num_classes, num_bins)

    def chain_scalar(out):
        return (out[0][0, 0, 0] * 0).astype(jnp.int32)

    return step, chain_scalar, False


def mesh_on_tpu(mesh) -> bool:
    """True when every device of ``mesh`` is a TPU — the gate for running
    the compiled kernel under ``shard_map``
    (``parallel/collectives.sharded_cooc_step``); CPU meshes (tests,
    dryrun) run the same step with ``interpret=True`` instead."""
    if mesh is None:
        return False
    devices = list(np.asarray(mesh.devices).flat)
    return bool(devices) and all(d.platform == "tpu" for d in devices)


def on_tpu_single_device(*arrays) -> bool:
    """Runtime gate: default backend is a TPU and no operand is sharded
    across devices (the sharded einsum path owns multi-device execution —
    its psum-over-data collective is what the mesh tests attest)."""
    if jax.devices()[0].platform != "tpu":
        return False
    for x in arrays:
        sharding = getattr(x, "sharding", None)
        if sharding is not None and len(getattr(sharding, "device_set", ())) > 1:
            return False
    return True
