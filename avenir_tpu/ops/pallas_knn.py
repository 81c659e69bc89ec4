"""Fused kNN distance + exact running top-k as a Pallas TPU kernel.

The XLA scan path (models/knn.py::_topk_over_tiles) materializes a
[test_tile, ref_tile] distance block in HBM each scan step and runs a
full-width ``lax.top_k`` over it — measured on-chip that is ~147 ms of
HBM-bound distance traffic plus ~210 ms of sort work for 4096 queries × 1M
references. This kernel keeps everything in VMEM and feeds the MXU exactly
one bf16 pass per tile:

- The whole squared distance collapses into ONE bf16 matmul,
  d² = −2·(A·Bᵀ), by packing into the contraction axis: the flattened
  categorical one-hots (0/1 and 0/0.5 — mismatch counts are exact in bf16),
  the continuous coordinates split into three bf16 limbs (hi/lo/lo2 with
  cross-limb product columns, so the f32 product is reproduced to ~2⁻²⁶
  relative — Mosaic's native f32 dot costs ~6 MXU passes, measured 6×
  slower than this), and the ‖x‖²/‖y‖² norm terms as limb-split side
  columns. Reference pad rows bake a huge finite norm term (never ±inf: a
  zero padding lane times inf is NaN, and NaN poisons every compare).
- The candidate kernel is the SEGMENT KEY-TOURNAMENT sweep (see its
  section below): int32 packed sort keys + a tournament of min/max merges,
  per-2048-ref-segment top-2 + truncated third-min bound, no
  data-dependent control.  It is the only candidate kernel:
  :func:`fused_serves` says which indexes it can serve (enough real
  segments to fill the candidate pool: over ~16 k rows at k = 10), and
  the routes of models/knn.py send every other index to the exact XLA
  scan, where one scan tile covers the whole set.
- The same program then re-ranks the k' candidates with exact f32
  arithmetic and checks an exactness certificate (k-th exact candidate
  distance vs the nearest thing the sweep can hide, minus the limb error
  bound); rows that fail fall back to the exact XLA scan, so results are
  exact top-k, not approximate.

Replaces the O(N²) all-pairs distance job the reference outsources to
sifarish ``SameTypeSimilarity`` (resource/knn.sh:47-60) and the secondary-
sort top-k of knn/NearestNeighbor.java:317-349, as one on-chip pass.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Block shapes. One query tile is resident per grid row — query_tile(m) rows,
# the smallest of 128, 256 and TM that holds the block, so a 64-row serve
# dispatch sweeps 128 query rows and not 512; TM is the LARGEST tile, and
# every block above it takes whole TM-row tiles.  TB reference rows stream
# through VMEM per grid step, SEG-row segment by segment.  The re-rank keeps
# at most SLOTS candidates a row.
TM = 512
# What a caller that packs rows behind a short block should pad to, cheapest
# first: up to 256 query rows a grid step is bound by the DMA of its TB-row
# reference block, so rows ride for the one read of the index the block pays
# anyway; beyond that the step is the dot's and grows with the tile, and a
# full TM-row tile is the cheapest ROW the kernel sells (PERF.md §5 has the
# step at each tile).  Each is a tile of query_tile's ladder.
FILL_TILES = (256, TM)
TB = 16384             # reference rows per grid step (one DMA, 8 segments)
SEG = 2048             # certificate granularity: top-2 + third-min bound
SLOTS = 128
MARGIN = 8             # extra candidates kept beyond k for the exact re-rank
# Large finite sentinels — true infinities must never reach the MXU.
_BIG = 3.0e30          # distance of a candidate slot with no reference
_PADC = 1.0e30         # reference pad-row norm term: dominates any real d²
# Absolute d² error bound of the limb-split dot (see _limbs): each of the
# ~20 contributing terms is reproduced to ~2^-26 relative, magnitudes ≤ ~32.
D2_EPS = 1e-4


def fused_serves(n_real: int, k: int) -> bool:
    """Can the fused search serve an index — or every shard of one, asked of
    the shortest — of ``n_real`` real rows at ``k``?  The one place that
    decides it: ``k`` and a row past it fit the re-rank's slots, the rows
    hold ``k`` neighbours, and the real segments' top-2 fill the candidate
    pool (a pool filled up with pad rows bounds nothing and every
    certificate fails): over 16384 rows at k = 10.  What it refuses takes
    the exact scan, which needs no certificate."""
    return (k + 1 <= SLOTS and n_real >= k
            and 2 * -(-n_real // SEG) >= min(k + MARGIN, SLOTS))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bf16_round(x: np.ndarray) -> np.ndarray:
    """Round f32 → nearest-even bf16, returned as f32 (numpy lacks bf16)."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return rounded.view(np.float32)


def _limbs(v: np.ndarray, n: int = 3):
    """Split f32 values into n bf16 limbs: v ≈ Σ limbs (each exactly
    representable in bf16), residual ~2^(-9n)·|v|."""
    out = []
    rem = v.astype(np.float32)
    for _ in range(n):
        hi = _bf16_round(rem)
        out.append(hi)
        rem = rem - hi
    return out


def _width(f: int, num_bins: int, fc: int) -> int:
    # cat | 6 cross-limb cont groups | 3+3 norm columns
    return _round_up(max(f * num_bins + 6 * fc + 6, 1), 128)


def _pack(codes: np.ndarray, cont01: np.ndarray, num_bins: int,
          rows: int, is_ref: bool, extra_norm: float | np.ndarray):
    """Build the packed bf16 operand matrix (see module doc for layout)."""
    n, f = codes.shape
    fc = cont01.shape[1]
    width = _width(f, num_bins, fc)
    mat = np.zeros((rows, width), np.float32)

    if f:
        r = np.repeat(np.arange(n), f)
        c = (np.arange(f) * num_bins)[None, :] + codes
        mat[r, c.ravel()] = 0.5 if is_ref else 1.0

    base = f * num_bins
    hi, lo, lo2 = _limbs(cont01) if fc else (None, None, None)
    norm = (cont01.astype(np.float64) ** 2).sum(axis=1).astype(np.float32)
    if fc:
        if is_ref:      # pairs: (hi,hi) (hi,lo) (lo,hi) (lo,lo) (hi,lo2) (lo2,hi)
            groups = [hi, lo, hi, lo, lo2, hi]
        else:
            groups = [hi, hi, lo, lo, hi, lo2]
        for g, arr in enumerate(groups):
            mat[:n, base + g * fc: base + (g + 1) * fc] = arr
    nb_ = base + 6 * fc

    if is_ref:
        colc = np.full(rows, np.float32(extra_norm), np.float32)
        colc[:n] = norm
        ch, cl, cl2 = _limbs(-0.5 * colc)
        mat[:, nb_ + 0] = ch
        mat[:, nb_ + 1] = cl
        mat[:, nb_ + 2] = cl2
        mat[:, nb_ + 3] = -0.5
        mat[:, nb_ + 4] = -0.5
        mat[:, nb_ + 5] = -0.5
        # fold the norm-expansion's −2 into the reference operand: ×−2 is
        # exact for every entry (one-hots, bf16 limbs, −0.5 constants), so
        # the kernel's dot IS d² with no per-block scale pass
        mat *= -2.0
    else:
        rowc = np.zeros(rows, np.float32)
        rowc[:n] = np.float32(extra_norm) + norm
        mat[:, nb_ + 0] = 1.0
        mat[:, nb_ + 1] = 1.0
        mat[:, nb_ + 2] = 1.0
        rh, rl, rl2 = _limbs(rowc)
        mat[:, nb_ + 3] = rh
        mat[:, nb_ + 4] = rl
        mat[:, nb_ + 5] = rl2
    return jnp.asarray(mat, jnp.bfloat16)


def prepare_refs(codes: np.ndarray, cont01: np.ndarray, num_bins: int
                 ) -> Tuple[jax.Array, int]:
    """Packed reference operand [operand_rows(N), K] bf16 built on the host,
    and N.  No route calls it: it is the tests' oracle for pack_refs_dev."""
    n = codes.shape[0]
    return _pack(codes, cont01, num_bins, operand_rows(n), True, _PADC), n


# ---------------------------------------------------------------------------
# segmented key-tournament sweep — the candidate kernel
# ---------------------------------------------------------------------------
# Each distance becomes ONE int32 sort key,
# (bitcast(max(d2,0)) & ~(SEG-1)) | col — positive-float bitcast is
# order-preserving, so min-of-key IS argmin and the column rides in the low
# 11 bits — and every 2048-ref segment yields its smallest two keys, plus
# the third as the non-candidate bound, by a TOURNAMENT of sorted (m1,m2,m3)
# triples: min/max merges only, no data-dependent control.  Refs stream in
# 16K-row blocks (8 segments a DMA; vmem_limit_bytes admits the block at
# packed widths >= 256, which the 16 MiB default refuses).
# Exact: true top-k ⊆ candidates unless a segment hides ≥3 of it; key
# truncation only LOWERS a segment's bound (≤ 2⁻¹² relative): sound.
#
# Dot and tournament both scale with the tile's height; the step's DMA of
# one TB-row reference block (4.2 MB at width 128) does not.  Under 256 query
# rows a step is bound by reading the index, ~5.8 us on a v5e: a 128-row
# tile's weights are latched in all four MXUs and a segment's rows dealt
# over them, 512 pops a unit and ~4.3k bundles a step by the compiler's own
# count (PERF.md §5-§6, PR 35).  At a TM-row tile a step is bound by its
# dot: 8192 result vregs popped from four MXUs, ~16.4k cycles.  The
# tournament hides beside it (a step is ~16.9k bundles; PERF.md and
# docs/architecture.md have the measured decomposition) because of three
# choices, each of which costs the overlap if undone:
#   - the keys are merged as FLOATS.  The v5e vector unit has no int32
#     min/max (a compare AND a select), vmin/vmax.f32 are one operation.
#     key + 2^23 (one exponent step, folded into the column constant) is a
#     normal positive float whose order is the key's; the bias comes off
#     the 8 x 3 results of a step;
#   - the QUERY tile is the stationary MXU operand and the references
#     stream, so a result vreg is [8 refs, 128 queries] and a query group's
#     running triple is three vregs (a shorter tile has fewer groups, each
#     the same).  The other way round a [512, 128] column block is 64
#     vregs, the whole register file, and every tree level goes through
#     VMEM on the one store slot;
#   - the tree pairs 8-row groups that leave the MXU next to each other, so
#     a result is merged out of registers while the MXU delivers the next
#     (pairing row v with row v + SEG/2 keeps half a segment in flight).
# The outputs come out [refs/2048, M]; _tourney_keys transposes them, which
# XLA turns into a layout of the consumers, not a copy.

# pad-lane key: the int32 bit pattern of _BIG (finite; NEVER 0x7fffffff,
# whose truncated bitcast is NaN and would poison every downstream min)
_PAD_KEY = int(np.float32(_BIG).view(np.int32))
# added to every key before it is compared as a float: the exponent field
# of a key of d2 < 2^-126 (every clamped d2) is 0, a denormal, which the
# vector unit may flush; one exponent step up every key is a normal float
# and the order of the keys is unchanged
_KEY_BIAS = 1 << 23


def _merge_triples(a, b):
    """Sorted triples (a1<=a2<=a3), (b1<=b2<=b3) of disjoint key sets ->
    the sorted three smallest of their union, elementwise.  Seven
    operations: max(a2, b2) is never among the three."""
    a1, a2, a3 = a
    b1, b2, b3 = b
    hi1 = jnp.maximum(a1, b1)
    lo2 = jnp.minimum(a2, b2)
    return (jnp.minimum(a1, b1), jnp.minimum(hi1, lo2),
            jnp.minimum(jnp.maximum(hi1, lo2), jnp.minimum(a3, b3)))


def _segment_keys(d2t):
    """d2ᵀ of one segment, [SEG refs, queries] f32 -> its biased sort keys,
    bitcast to f32 (see ``_KEY_BIAS``)."""
    col = (jax.lax.broadcasted_iota(jnp.int32, d2t.shape, 0)
           + jnp.int32(_KEY_BIAS))
    # max(d2, 0): the limb-split dot can go ~eps negative for near-identical
    # points; negative-float bitcast would invert the int ordering
    di = jax.lax.bitcast_convert_type(jnp.maximum(d2t, 0.0), jnp.int32)
    # the low 11 bits are clear after the mask: + col is | col
    return jax.lax.bitcast_convert_type(
        (di & jnp.int32(~(SEG - 1))) + col, jnp.float32)


def _rows_top3(key):
    """[rows, queries] keys -> the sorted triple of each column's three
    smallest among the rows of each residue mod 8: three [8, queries]
    arrays (a sublane a residue)."""
    # every reshape splits or merges leading dims of whole [8, 128] tiles:
    # no data moves
    n, q = key.shape[0] // 16, key.shape[1]
    # round 1: the two 8-row groups of a 16-row MXU push -> sorted pairs
    r = key.reshape(n, 16, q)
    lo, hi = r[:, :8], r[:, 8:]
    m1 = jnp.minimum(lo, hi).reshape(n // 2, 2, 8, q)
    m2 = jnp.maximum(lo, hi).reshape(n // 2, 2, 8, q)
    # round 2: two neighbouring sorted pairs -> sorted triple of 4
    hi1 = jnp.maximum(m1[:, 0], m1[:, 1])
    lo2 = jnp.minimum(m2[:, 0], m2[:, 1])
    tri = (jnp.minimum(m1[:, 0], m1[:, 1]), jnp.minimum(hi1, lo2),
           jnp.maximum(hi1, lo2))
    # then neighbouring triples merge, down to one
    n //= 2
    while n > 1:
        n //= 2
        halves = [t.reshape(n, 2, 8, q) for t in tri]
        tri = _merge_triples([h[:, 0] for h in halves],
                             [h[:, 1] for h in halves])
    return [t[0] for t in tri]


def _sublanes_top3(tri):
    """A sorted [8, queries] triple of eight disjoint key sets, a sublane a
    set -> the triple of their union, in every sublane: after rotations by
    4, 2 and 1 each sublane has met all eight."""
    for shift in (4, 2, 1):
        tri = _merge_triples(tri, [pltpu.roll(t, shift, 0) for t in tri])
    return tri


def _knn_tourney_kernel(a_ref, b_ref, k1_out, k2_out, k3_out):
    nseg = TB // SEG
    a = a_ref[:]
    tile = a_ref.shape[0]       # query_tile of the block: 128, 256 or TM
    seg_row = jax.lax.broadcasted_iota(jnp.int32, (nseg, tile), 0)
    outs = [jnp.zeros((nseg, tile), jnp.float32)] * 3
    for s in range(nseg):
        # d2ᵀ of one segment, [refs, queries]: the one bf16 MXU pass (the −2
        # of the norm expansion is folded into the reference operand)
        d2t = jax.lax.dot_general(
            b_ref[s * SEG:(s + 1) * SEG, :], a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        tri = _sublanes_top3(_rows_top3(_segment_keys(d2t)))
        outs = [jnp.where(seg_row == s, t, o) for t, o in zip(tri, outs)]
    for out, o in zip((k1_out, k2_out, k3_out), outs):
        out[:] = (jax.lax.bitcast_convert_type(o, jnp.int32)
                  - jnp.int32(_KEY_BIAS))


def _tourney_keys(a_mat, b_mat):
    """The sweep's raw output: three int32 arrays, a lane a segment (rounded
    up to 128s): its three smallest keys, ``_PAD_KEY`` past the last one."""
    m, n = a_mat.shape[0], b_mat.shape[0]
    nseg = n // SEG
    tile = query_tile(m)        # m is query_rows of the block: whole tiles
    spec = pl.BlockSpec((TB // SEG, tile), lambda i, j: (j, i),
                        memory_space=pltpu.VMEM)
    keys = pl.pallas_call(
        _knn_tourney_kernel,
        grid=(m // tile, n // TB),
        in_specs=[
            pl.BlockSpec((tile, a_mat.shape[1]), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((TB, b_mat.shape[1]), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((nseg, m), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(a_mat, b_mat)
    # the kernel's rows are segments: a lane a segment is their transpose
    return [jnp.pad(kk_.T, ((0, 0), (0, _round_up(nseg, 128) - nseg)),
                    constant_values=_PAD_KEY) for kk_ in keys]


def _topk_tourney_traced(a_mat, b_mat, k: int):
    """Segment-tournament candidate generation + XLA assembly.

    Returns ([Mpad, k] approx (truncated-key) d² ascending, [Mpad, k] ref
    indices, [Mpad] non-candidate lower bound = min over segments of the
    segment's truncated third-smallest distance).
    Requires n % TB == 0 (operand_rows) and, for a bound worth having,
    :func:`fused_serves`."""
    k1, k2, k3 = _tourney_keys(a_mat, b_mat)
    segmask = jnp.int32(~(SEG - 1))
    seg_base = jnp.arange(k1.shape[1], dtype=jnp.int32) * SEG

    def unpack(kk_):
        d = jax.lax.bitcast_convert_type(kk_ & segmask, jnp.float32)
        return d, seg_base[None, :] + (kk_ & jnp.int32(SEG - 1))

    d1, i1 = unpack(k1)
    d2, i2 = unpack(k2)
    b3 = jax.lax.bitcast_convert_type(k3 & segmask, jnp.float32)
    cand_d = jnp.concatenate([d1, d2], axis=1)
    cand_i = jnp.concatenate([i1, i2], axis=1)
    neg, pos = jax.lax.top_k(-cand_d, k)
    idx = jnp.take_along_axis(cand_i, pos, axis=1)
    return -neg, idx, jnp.min(b3, axis=1)


# ---------------------------------------------------------------------------
# fused single-dispatch path: device-side query pack + kernel + exact re-rank
# ---------------------------------------------------------------------------
# pack → pallas → re-rank run as ONE jitted program: per batch the host
# transfers only the raw codes/cont arrays (~120 KB) and receives [M,k]
# results + a per-row certificate, so batches pipeline back-to-back and
# the round-trip latency amortizes away.

def _limbs_dev(v: jax.Array, n: int = 3):
    """Device-side bf16 limb split (matches :func:`_limbs`: rounds to
    nearest-even exactly like _bf16_round).

    The rounding is ``lax.reduce_precision``, never
    ``astype(bf16).astype(f32)``: under jit the TPU compiler may drop that
    round trip as excess precision, the remainder is then 0 and the low
    limbs vanish — d² came back with ~2⁻⁸ relative error instead of 2⁻²⁶
    and the certificate passed rows that were not exact (chip run, PR 23).
    """
    out = []
    rem = v.astype(jnp.float32)
    for _ in range(n):
        hi = jax.lax.reduce_precision(rem, exponent_bits=8, mantissa_bits=7)
        out.append(hi)
        rem = rem - hi
    return out


def _pack_queries_dev(codes: jax.Array, cont01: jax.Array, num_bins: int,
                      rows: int, extra_norm: float) -> jax.Array:
    """Device-side equivalent of ``_pack(..., is_ref=False)``: [rows, W] bf16.
    ``codes``/``cont01`` may be shorter than ``rows``; the tail is zero
    (pad queries — their results are discarded by the caller)."""
    n, f = codes.shape
    fc = cont01.shape[1]
    width = _width(f, num_bins, fc)
    parts = []
    if f:
        onehot = (codes[:, :, None] ==
                  jnp.arange(num_bins, dtype=codes.dtype)).astype(jnp.float32)
        parts.append(onehot.reshape(n, f * num_bins))
    if fc:
        hi, lo, lo2 = _limbs_dev(cont01)
        parts.extend([hi, hi, lo, lo, hi, lo2])
    norm = (cont01.astype(jnp.float32) ** 2).sum(axis=1)
    rowc = jnp.float32(extra_norm) + norm
    rh, rl, rl2 = _limbs_dev(rowc)
    ones = jnp.ones((n,), jnp.float32)
    parts.append(jnp.stack([ones, ones, ones, rh, rl, rl2], axis=1))
    mat = jnp.concatenate(parts, axis=1)
    mat = jnp.pad(mat, ((0, rows - n), (0, width - mat.shape[1])))
    return mat.astype(jnp.bfloat16)


def fused_candidates(codes_q, cont01_q, r_mat, codes_r, cont01_r, n_real, *,
                     num_bins: int, rows: int, extra_norm: float, k: int,
                     kk: int, eps: float):
    """Pack queries, run the pallas kernel, re-rank its kk candidates in
    exact f32: (d2s [M, kk] d² ascending, idxs [M, kk], kth [M] = d2s's
    k-th, limit [M]).  No reference outside the candidates is nearer than
    ``limit``: the kk-th approx candidate and every segment's
    third-smallest, less 2·eps of limb error — THE certificate's bound,
    compared with ``kth`` by :func:`_search_fused` on one chip and with the
    merged k-th by parallel/collectives.py::merge_shard_topk over shards."""
    m = codes_q.shape[0]
    q_mat = _pack_queries_dev(codes_q, cont01_q, num_bins, rows, extra_norm)
    cand_d2, cand_idx, bound3 = _topk_tourney_traced(q_mat, r_mat, kk)
    cand_d2, cand_idx, bound3 = cand_d2[:m], cand_idx[:m], bound3[:m]
    # pad reference rows (index ≥ n_real) would gather out of bounds: mark
    # unseen.  A pad among the candidates proves nothing: segments still
    # hide non-candidates, limit decides.
    cand_idx = jnp.where(cand_idx >= n_real, -1, cand_idx)
    safe_idx = jnp.maximum(cand_idx, 0)
    mism = (codes_q[:, None, :] != codes_r[safe_idx]).sum(-1).astype(jnp.float32)
    diff = cont01_q[:, None, :] - cont01_r[safe_idx]
    d2 = mism + (diff * diff).sum(-1)
    d2 = jnp.where(cand_idx < 0, _BIG, d2)
    neg, order = jax.lax.top_k(-d2, kk)
    d2s, idxs = -neg, jnp.take_along_axis(cand_idx, order, axis=1)
    kth = d2s[:, min(k, kk) - 1]
    limit = jnp.minimum(cand_d2[:, -1], bound3) - 2 * eps
    return d2s, idxs, kth, limit


@functools.partial(jax.jit, static_argnames=("num_bins", "rows", "extra_norm",
                                             "k", "kk", "total_attrs", "eps"))
def _search_fused(*operands, k: int, total_attrs: int, **statics):
    """One dispatch → ([M, k] distances in [0,1], [M, k] indices, [M] cert)."""
    d2s, idxs, kth, limit = fused_candidates(*operands, k=k, **statics)
    cert = kth <= limit     # nothing outside the candidates beats the k-th
    return unit_distances(d2s[:, :k], total_attrs), idxs[:, :k], cert


def search_fused(codes_q: np.ndarray, cont01_q: np.ndarray, r_mat: jax.Array,
                 codes_r_dev: jax.Array, cont01_r_dev: jax.Array, n_real: int,
                 num_bins: int, k: int, total_attrs: int):
    """Single-dispatch exact search over an index :func:`fused_serves`
    admits (the route's business, not asked again here). Returns device
    arrays ([M,k] dist, [M,k] idx, [M] cert) — the caller syncs (or
    pipelines)."""
    return _search_fused(
        jnp.asarray(codes_q), jnp.asarray(cont01_q, jnp.float32), r_mat,
        codes_r_dev, cont01_r_dev, n_real,
        num_bins=num_bins, total_attrs=total_attrs,
        **fused_statics(*codes_q.shape, cont01_q.shape[1], k))


# ---------------------------------------------------------------------------
# the reference operand packed on the device that holds the rows
# ---------------------------------------------------------------------------
# An index is packed where it lives: on one chip (models/knn.py::KNNModel.
# device_packed, :func:`pack_refs`) and on every shard of a row-sharded one
# (KNNModel.device_sharded, under shard_map), from the rows uploaded for the
# re-rank.  The host pack (:func:`_pack`: one core's numpy over 7 GB of f32
# staging at 13 x 2^20 rows) is the tests' oracle: the operand equals it
# value for value, a pad column's sign of zero aside (tests/
# test_knn_sharded.py), so a shard's search is the one-chip search over the
# same rows.

def query_tile(m: int) -> int:
    """Height of the kernel's query tile for a block of ``m`` rows: the
    smallest of 128, 256 and TM that holds the block, TM for every larger
    one.  The one place that decides it: the one-chip and the sharded search
    both take their ``rows`` from :func:`fused_statics`."""
    return next((t for t in (128, 256) if m <= t), TM)


def query_rows(m: int) -> int:
    """Query rows the kernel sweeps for a block of ``m``: whole tiles of the
    block's :func:`query_tile`."""
    return _round_up(max(m, 1), query_tile(m))


def fused_statics(m: int, f: int, fc: int, k: int) -> dict:
    """The static arguments of ``_search_fused`` that follow from a query
    block's shape ([m, f] codes, [m, fc] continuous) and ``k``."""
    return dict(rows=query_rows(m), extra_norm=float(f), k=k,
                kk=min(k + MARGIN, SLOTS), eps=D2_EPS if fc else 0.0)


def unit_distances(d2: jax.Array, total_attrs: int) -> jax.Array:
    """Squared distances summed over the attributes → distances in [0, 1]."""
    return jnp.clip(jnp.sqrt(jnp.maximum(d2, 0.0) / max(total_attrs, 1)),
                    0.0, 1.0)


def operand_rows(n: int) -> int:
    """Rows of the packed operand of ``n`` references: whole TB-row blocks,
    the tournament's grid step."""
    return _round_up(max(n, 1), TB)


def pack_refs_dev(codes: jax.Array, cont01: jax.Array, norm: jax.Array,
                  n_real: jax.Array, num_bins: int) -> jax.Array:
    """Device-side equivalent of ``_pack(..., is_ref=True)``: the packed
    ``[operand_rows(n), W]`` bf16 operand of the first ``n_real`` of the
    ``n`` rows given (the rest are pad rows, as every row past ``n``).
    ``norm`` is each row's squared norm as the host pack computes it (summed
    in float64, then f32).  Built a chunk of rows at a time into the
    operand's own buffer: the f32 staging of a whole 13 x 2^20-row shard
    would be 7 GB."""
    n, f = codes.shape
    fc = cont01.shape[1]
    rows, width = operand_rows(n), _width(f, num_bins, fc)
    chunk = int(np.gcd(rows, 1 << 17))
    pad = ((0, rows - n), (0, 0))
    codes, cont01 = jnp.pad(codes, pad), jnp.pad(cont01, pad)
    norm = jnp.pad(norm, (0, rows - n))

    def body(c, out):
        at = c * chunk
        real = (at + jnp.arange(chunk, dtype=jnp.int32) < n_real)[:, None]
        parts = []
        if f:
            blk = jax.lax.dynamic_slice(codes, (at, 0), (chunk, f))
            onehot = (blk[:, :, None] ==
                      jnp.arange(num_bins, dtype=blk.dtype)) & real[:, :, None]
            parts.append(-onehot.astype(jnp.float32).reshape(chunk,
                                                             f * num_bins))
        if fc:
            hi, lo, lo2 = _limbs_dev(jnp.where(
                real, jax.lax.dynamic_slice(cont01, (at, 0), (chunk, fc)),
                0.0))
            parts.extend(-2.0 * g for g in (hi, lo, hi, lo, lo2, hi))
        colc = jnp.where(real[:, 0],
                         jax.lax.dynamic_slice(norm, (at,), (chunk,)), _PADC)
        ones = jnp.ones((chunk,), jnp.float32)
        parts.append(jnp.stack(
            [-2.0 * limb for limb in _limbs_dev(-0.5 * colc)]
            + [ones, ones, ones], axis=1))
        mat = jnp.concatenate(parts, axis=1)
        mat = jnp.pad(mat, ((0, 0), (0, width - mat.shape[1])))
        return jax.lax.dynamic_update_slice(out, mat.astype(jnp.bfloat16),
                                            (at, 0))

    return jax.lax.fori_loop(0, rows // chunk, body,
                             jnp.zeros((rows, width), jnp.bfloat16))


@functools.partial(jax.jit, static_argnames="num_bins")
def pack_refs(codes: jax.Array, cont01: jax.Array, norm: jax.Array,
              num_bins: int) -> jax.Array:
    """The packed operand of an index on one device, built there from its
    rows: :func:`pack_refs_dev` with every row given real."""
    return pack_refs_dev(codes, cont01, norm, codes.shape[0], num_bins)
