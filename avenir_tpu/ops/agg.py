"""Sharded aggregation primitives — the rebuild's communication backend.

Every reducer/shuffle pattern in the reference lowers to one of the kernels
here (see SURVEY.md §2.12): class-conditional count tensors (the Naive-Bayes
shuffle, reference bayesian/BayesianDistribution.java:137-328), contingency
matrices (explore/CramerCorrelation.java:161-235), feature-pair joint
distributions (explore/MutualInformation.java:136-403), per-class moment sums
(discriminant via chombo NumericalAttrStats), split histograms
(explore/ClassPartitionGenerator.java:199-230), gradient partial sums
(regress/LogisticRegressionJob.java:169-176), and state-transition counts
(markov/MarkovStateTransitionModel.java:98-125).

Design: counts are computed as one-hot einsums — dense matmuls that XLA tiles
onto the MXU — in float32 (exact for per-chunk counts < 2^24), then cast to
int32 and accumulated across chunks. Under a sharded ``jax.jit`` the batch
axis is sharded over the mesh's ``data`` axis and XLA inserts the
``psum``-equivalent all-reduce over ICI automatically; the reference's
combiner (map-side pre-aggregation) corresponds exactly to the per-device
partial einsum, and the shuffle to the collective.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# float32 one-hot sums are exact only while every cell stays below 2^24; the
# batch axis bounds any cell, so cap chunk size (checked at trace time).
MAX_EXACT_CHUNK_ROWS = 1 << 24


def _check_chunk(x: jax.Array) -> None:
    if x.shape[0] >= MAX_EXACT_CHUNK_ROWS:
        raise ValueError(
            f"chunk of {x.shape[0]} rows exceeds float32-exact count limit "
            f"{MAX_EXACT_CHUNK_ROWS}; split the stream into smaller chunks")


def one_hot(x: jax.Array, k: int, dtype=jnp.float32) -> jax.Array:
    """One-hot encode; out-of-range indices (e.g. -1) produce all-zero rows."""
    return jax.nn.one_hot(x, k, dtype=dtype)


# ---------------------------------------------------------------------------
# count tensors
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_classes",))
def class_counts(labels: jax.Array, num_classes: int) -> jax.Array:
    """[C] — class-prior counts."""
    _check_chunk(labels)
    return jnp.sum(one_hot(labels, num_classes), axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_bins",))
def feature_counts(codes: jax.Array, num_bins: int) -> jax.Array:
    """codes [N, F] → [F, B] per-feature bin histograms (feature priors)."""
    _check_chunk(codes)
    return jnp.sum(one_hot(codes, num_bins), axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes", "num_bins"))
def feature_class_counts(
    codes: jax.Array, labels: jax.Array, num_classes: int, num_bins: int
) -> jax.Array:
    """codes [N, F], labels [N] → [F, B, C] class-conditional bin counts.

    This is the Naive-Bayes training shuffle: the reference emits one
    (classVal, featureOrdinal, bin) → 1 record per feature per row and sums in
    the reducer; here it is a single [N,F,B]×[N,C] contraction.
    """
    _check_chunk(codes)
    oh_b = one_hot(codes, num_bins)            # [N, F, B]
    oh_c = one_hot(labels, num_classes)        # [N, C]
    return jnp.einsum("nfb,nc->fbc", oh_b, oh_c, precision="highest").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_bins",))
def pair_counts(
    codes_i: jax.Array, codes_j: jax.Array, num_bins: int
) -> jax.Array:
    """codes_i [N, P], codes_j [N, P] → [P, B, B] joint histograms for P
    feature pairs evaluated in lockstep (feature-pair distributions of the MI
    job; Cramér contingency matrices)."""
    _check_chunk(codes_i)
    oh_i = one_hot(codes_i, num_bins)          # [N, P, B]
    oh_j = one_hot(codes_j, num_bins)          # [N, P, B]
    return jnp.einsum("npa,npb->pab", oh_i, oh_j, precision="highest").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes", "num_bins"))
def nb_mi_pipeline_step(codes, labels, ci, cj, num_classes: int, num_bins: int):
    """The NB+MI aggregation step in its einsum form: class-conditional bin
    counts plus all feature-pair-class joint counts in ONE einsum dispatch.

    Round 3: on a single TPU device with a small joint table this is no
    longer the primary path — ``ops/pallas_hist.cooc_counts`` (G = XᵀX over
    the joint (feature, bin, class) one-hot, built in VMEM, int8 MXU pass)
    measures ~4-5× faster, and MutualInformation.fit / bench.py /
    benchmarks/e2e_pipeline.py route to it explicitly (host-side read-out
    of the same tensors via ``pallas_hist.counts_from_cooc``;
    bit-identical int32 counts).  This form remains the multi-device path
    (its data-axis psum is the attested collective), the wide-table path
    (F·B·C > pallas_hist.MAX_W), and the CPU/test path.

    The F diagonal "pairs" (f, f) are appended to the P requested pairs: the
    [a, a, c] diagonal of a (f, f) joint IS the class-conditional bin count,
    so NB's tensor falls out of the same kernel instead of costing a second
    full pass over the chunk (measured ~2.3× total on-chip time as two
    separate einsums — see pair_class_counts for the two-operand form)."""
    f = codes.shape[1]
    diag = jnp.arange(f, dtype=jnp.int32)
    cia = jnp.concatenate([jnp.asarray(ci, jnp.int32), diag])
    cja = jnp.concatenate([jnp.asarray(cj, jnp.int32), diag])
    all_counts = pair_class_counts(codes[:, cia], codes[:, cja], labels,
                                   num_classes, num_bins)
    pair = all_counts[:len(ci)]
    ar = jnp.arange(num_bins)
    fbc = all_counts[len(ci):, ar, ar, :]          # [F, B, C] diagonal
    return fbc, pair


@functools.partial(jax.jit, static_argnames=("num_classes", "num_bins"))
def pair_class_counts(
    codes_i: jax.Array, codes_j: jax.Array, labels: jax.Array,
    num_classes: int, num_bins: int,
) -> jax.Array:
    """→ [P, B, B, C] feature-pair × class joint counts (MI job's pair-class
    and pair-class-conditional distributions come from this one tensor).

    Two-operand form: the second operand one-hots the JOINT (bin_j, class)
    code so the contraction is "npa,npk->pak" — measured 2.3× faster
    on-chip than the three-operand "npa,npb,nc->pabc" (both lower to
    scatter-adds; the joint form scatters once per (row, pair) instead of
    expanding the class axis separately)."""
    _check_chunk(codes_i)
    oh_i = one_hot(codes_i, num_bins)                       # [N, P, B]
    # preserve one_hot's drop-invalid contract for the JOINT code: an
    # out-of-range label (e.g. -1 mesh padding on a partially-labeled
    # stream) would otherwise alias into a valid (bin_j, class) cell
    bad = (labels < 0) | (labels >= num_classes)
    joint = jnp.where(bad[:, None], -1,
                      codes_j * num_classes + labels[:, None])
    oh_jc = one_hot(joint, num_bins * num_classes)          # [N, P, B*C]
    pak = jnp.einsum("npa,npk->pak", oh_i, oh_jc, precision="highest")
    return pak.reshape(*pak.shape[:2], num_bins, num_classes).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_classes",))
def class_moments(
    values: jax.Array, labels: jax.Array, num_classes: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """values [N, F] float, labels [N] → (count [C], sum [C,F], sumsq [C,F]).

    The per-(attr, class) count/Σx/Σx² accumulation backing Gaussian Naive
    Bayes and the Fisher discriminant (reference reuses chombo
    NumericalAttrStats for this)."""
    _check_chunk(values)
    oh_c = one_hot(labels, num_classes)        # [N, C]
    cnt = jnp.sum(oh_c, axis=0)
    s1 = jnp.einsum("nc,nf->cf", oh_c, values, precision="highest")
    s2 = jnp.einsum("nc,nf->cf", oh_c, values * values, precision="highest")
    return cnt, s1, s2


@functools.partial(jax.jit, static_argnames=("num_segments",))
def segment_count(segments: jax.Array, num_segments: int) -> jax.Array:
    """Generic 1-D histogram by segment id."""
    _check_chunk(segments)
    return jnp.sum(one_hot(segments, num_segments), axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_a", "num_b"))
def transition_counts(a: jax.Array, b: jax.Array, num_a: int, num_b: int) -> jax.Array:
    """a [M], b [M] paired codes → [num_a, num_b] co-occurrence counts
    (Markov state-transition counts; also any 2-way contingency off the
    lockstep-pair path)."""
    _check_chunk(a)
    return jnp.einsum("ma,mb->ab", one_hot(a, num_a), one_hot(b, num_b), precision="highest").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_a", "num_b"))
def weighted_transition_counts(
    a: jax.Array, b: jax.Array, w: jax.Array, num_a: int, num_b: int
) -> jax.Array:
    """Weighted co-occurrence sums (float) — partially-tagged HMM windows.
    −1 codes are count-neutral (zero one-hot rows), so mesh pad rows with
    w=0 contribute nothing either way."""
    _check_chunk(a)
    return jnp.einsum("ma,mb,m->ab", one_hot(a, num_a), one_hot(b, num_b), w, precision="highest")


# ---------------------------------------------------------------------------
# host-side accumulation across chunks
# ---------------------------------------------------------------------------

class Accumulator:
    """Sums per-chunk device results into int64/float64 numpy totals.

    Per-chunk kernels are exact (float32 one-hot sums below 2^24 per bucket);
    cross-chunk accumulation happens here in 64-bit on host so 100M+ row
    streams cannot overflow or lose counts.
    """

    def __init__(self):
        self._totals = {}

    def add(self, name: str, value: jax.Array) -> None:
        arr = np.asarray(value)
        arr = arr.astype(np.int64) if np.issubdtype(arr.dtype, np.integer) else arr.astype(np.float64)
        if name in self._totals:
            self._totals[name] = self._totals[name] + arr
        else:
            self._totals[name] = arr

    def get(self, name: str) -> np.ndarray:
        return self._totals[name]

    def __contains__(self, name: str) -> bool:
        return name in self._totals

    def names(self):
        return list(self._totals)

    # -- checkpointable state (streaming-job mid-stream durability) ----------
    def state(self) -> dict:
        """name → numpy total, a copy safe to hand to checkpoint writers."""
        return {k: np.array(v) for k, v in self._totals.items()}

    def load(self, state: dict) -> None:
        """Replace the totals with a restored snapshot."""
        self._totals = {k: np.asarray(v) for k, v in state.items()}
