"""Markov-chain and hidden-Markov sequence models + Viterbi decoding.

Capability parity with the reference's ``org.avenir.markov`` package:

- ``MarkovStateTransitionModel.java`` — first-order chain trainer: adjacent
  state-pair counts (:98-108), combiner sums (:112-125), row-normalized
  transition matrix with Laplace smoothing serialized row-wise
  (:141-179, via util/StateTransitionProbability.java:65-126 incl. the
  int-scale ×1000 or double modes);
- ``HiddenMarkovModelBuilder.java`` — supervised HMM trainer, fully-tagged
  ``obs:state`` mode (:136-166) and partially-tagged mode where inline state
  tokens claim surrounding observations with a distance-decay
  ``window.function`` weight vector (:174-260). NOTE: the reference's window
  bounds contain an operator-precedence slip (``a − b / 2`` for
  ``(a − b) / 2``, :197,205); this implementation uses the intended midpoint
  semantics — a documented deliberate fix;
- ``HiddenMarkovModel.java`` — model file layout (line order: states,
  observations, A rows, B rows, π — :46-70);
- ``ViterbiDecoder.java`` — max-product decoding (:66-105 init/iterate,
  :111-143 backtrack); ``ViterbiStatePredictor.java`` — map-only batch
  decoding job (:114-142).

TPU design: sequences pad to [R, T] int arrays (−1 pad); transition/emission
counts are one-hot einsums over the flattened adjacent-pair stream (the MR
shuffle collapsed); Viterbi runs in log space as a ``lax.scan`` over time
vmapped over records — padded steps are identity transitions so ragged
batches decode in one fixed-shape program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from avenir_tpu.ops import agg

DELIM = ","


# ---------------------------------------------------------------------------
# sequence encoding
# ---------------------------------------------------------------------------

class SequenceEncoder:
    """Symbol-name ↔ code mapping with padding to rectangular batches."""

    def __init__(self, symbols: Optional[Sequence[str]] = None):
        self.symbols: List[str] = list(symbols) if symbols else []
        self._map: Dict[str, int] = {s: i for i, s in enumerate(self.symbols)}

    def fit(self, seqs: Iterable[Sequence[str]]) -> "SequenceEncoder":
        for seq in seqs:
            for s in seq:
                if s not in self._map:
                    self._map[s] = len(self.symbols)
                    self.symbols.append(s)
        return self

    def encode(self, seqs: Sequence[Sequence[str]], pad_to: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """([R, T] codes with −1 pad, [R] lengths)."""
        t = pad_to if pad_to is not None else max((len(s) for s in seqs), default=0)
        out = np.full((len(seqs), t), -1, np.int32)
        lens = np.zeros(len(seqs), np.int32)
        for r, seq in enumerate(seqs):
            lens[r] = len(seq)
            for j, s in enumerate(seq):
                out[r, j] = self._map[s]
        return out, lens

    def decode(self, codes: Sequence[int]) -> List[str]:
        return [self.symbols[c] for c in codes if c >= 0]

    def __len__(self) -> int:
        return len(self.symbols)


def adjacent_pairs(seqs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten [R, T] padded sequences into (src, dst) adjacent-pair streams;
    pairs touching pad (−1) become (−1, −1) → count-neutral."""
    a, b = seqs[:, :-1], seqs[:, 1:]
    valid = (a >= 0) & (b >= 0)
    return np.where(valid, a, -1).ravel(), np.where(valid, b, -1).ravel()


# ---------------------------------------------------------------------------
# Markov chain
# ---------------------------------------------------------------------------

@dataclass
class MarkovChainModel:
    states: List[str]
    counts: np.ndarray                   # [S, S] transition counts
    laplace: float = 1.0
    scale: Optional[int] = None          # int-scale mode (reference ×1000); None = float

    def transition_probs(self) -> np.ndarray:
        c = self.counts + self.laplace
        p = c / c.sum(axis=1, keepdims=True)
        if self.scale:
            return np.rint(p * self.scale) / self.scale
        return p

    # row-wise serde, as StateTransitionProbability emits
    def to_lines(self, delim: str = DELIM) -> List[str]:
        probs = self.transition_probs()
        lines = [delim.join(self.states)]
        for row in probs:
            if self.scale:
                lines.append(delim.join(str(int(v * self.scale)) for v in row))
            else:
                lines.append(delim.join(repr(float(v)) for v in row))
        return lines

    @classmethod
    def from_lines(cls, lines: Sequence[str], delim: str = DELIM,
                   scale: Optional[int] = None) -> "MarkovChainModel":
        states = lines[0].split(delim)
        s = len(states)
        probs = np.array([[float(v) for v in lines[1 + i].split(delim)] for i in range(s)])
        if scale:
            probs = probs / scale
        # store probabilities as pseudo-counts; laplace 0 so they round-trip
        return cls(states=states, counts=probs, laplace=0.0, scale=None)


class MarkovChain:
    """First-order chain trainer over state-name sequences."""

    def __init__(self, laplace: float = 1.0, scale: Optional[int] = None,
                 mesh=None):
        self.laplace = laplace
        self.scale = scale
        self.mesh = mesh          # optional data mesh (parallel/mesh.py)

    def fit(self, seqs: Sequence[Sequence[str]],
            encoder: Optional[SequenceEncoder] = None) -> Tuple[MarkovChainModel, SequenceEncoder]:
        enc = encoder if encoder is not None else SequenceEncoder().fit(seqs)
        acc = agg.Accumulator()
        self.accumulate(seqs, enc, acc)
        return self.finalize(enc, acc), enc

    def accumulate(self, seqs: Sequence[Sequence[str]],
                   encoder: SequenceEncoder, acc) -> None:
        """Fold one batch of sequences into ``acc["trans"]`` (exact int64)."""
        codes, _ = encoder.encode(seqs)
        s = len(encoder)
        a, b = adjacent_pairs(codes)
        from avenir_tpu.parallel.mesh import maybe_shard_batch
        a_b, b_b = maybe_shard_batch(self.mesh, a, b)   # -1 pads count-neutral
        acc.add("trans", agg.transition_counts(a_b, b_b, s, s))

    def finalize(self, encoder: SequenceEncoder, acc) -> MarkovChainModel:
        counts = np.asarray(acc.get("trans"), np.float64)
        return MarkovChainModel(states=list(encoder.symbols), counts=counts,
                                laplace=self.laplace, scale=self.scale)

    def fit_chunks(self, chunks: Iterable[Sequence[Sequence[str]]],
                   encoder: SequenceEncoder,
                   accumulator=None) -> Tuple[MarkovChainModel, SequenceEncoder]:
        """Streaming fit over an iterable of sequence batches.

        Requires a pre-built ``encoder`` (``model.states``): with chunked
        input, codes must be stable before the first chunk — vocabulary
        discovery would assign chunk-order-dependent codes.  ``accumulator``
        may be externally owned (multi-process jobs inject one whose totals
        are merged across processes when the stream exhausts; transition
        counts are exact integers, so the merge is order-free).  Raises
        :class:`~avenir_tpu.core.encoding.NoDataError` when no process
        contributed any sequence — after the merge collective, matching
        ``Job.distributed_fit``'s zero-chunk tolerance."""
        acc = accumulator if accumulator is not None else agg.Accumulator()
        for seqs in chunks:
            self.accumulate(seqs, encoder, acc)
        if "trans" not in acc:
            from avenir_tpu.core.encoding import NoDataError
            raise NoDataError("no data")
        return self.finalize(encoder, acc), encoder


# ---------------------------------------------------------------------------
# HMM
# ---------------------------------------------------------------------------

@dataclass
class HMMModel:
    states: List[str]
    observations: List[str]
    transition: np.ndarray       # [S, S] row-normalized A
    emission: np.ndarray         # [S, O] row-normalized B
    initial: np.ndarray          # [S] π

    # -- the reference file layout: states / observations / A rows / B rows / π
    def to_lines(self, delim: str = DELIM) -> List[str]:
        lines = [delim.join(self.states), delim.join(self.observations)]
        for row in self.transition:
            lines.append(delim.join(repr(float(v)) for v in row))
        for row in self.emission:
            lines.append(delim.join(repr(float(v)) for v in row))
        lines.append(delim.join(repr(float(v)) for v in self.initial))
        return lines

    @classmethod
    def from_lines(cls, lines: Sequence[str], delim: str = DELIM) -> "HMMModel":
        states = lines[0].split(delim)
        observations = lines[1].split(delim)
        s = len(states)
        cur = 2
        a = np.array([[float(v) for v in lines[cur + i].split(delim)] for i in range(s)])
        cur += s
        b = np.array([[float(v) for v in lines[cur + i].split(delim)] for i in range(s)])
        cur += s
        pi = np.array([float(v) for v in lines[cur].split(delim)])
        return cls(states, observations, a, b, pi)


class HMMBuilder:
    """Supervised HMM estimation from tagged sequences."""

    def __init__(self, laplace: float = 1.0, mesh=None):
        self.laplace = laplace
        self.mesh = mesh          # optional data mesh (parallel/mesh.py)

    def fit_tagged(
        self,
        seqs: Sequence[Sequence[Tuple[str, str]]],   # [(obs, state), ...] per record
        state_encoder: Optional[SequenceEncoder] = None,
        obs_encoder: Optional[SequenceEncoder] = None,
    ) -> HMMModel:
        """Fully-tagged mode: every token is obs:state
        (HiddenMarkovModelBuilder.java:136-166)."""
        st_enc = state_encoder or SequenceEncoder().fit([[s for _, s in seq] for seq in seqs])
        ob_enc = obs_encoder or SequenceEncoder().fit([[o for o, _ in seq] for seq in seqs])
        acc = agg.Accumulator()
        self.accumulate_tagged(seqs, st_enc, ob_enc, acc)
        return self.finalize(st_enc, ob_enc, acc)

    def accumulate_tagged(self, seqs, st_enc: SequenceEncoder,
                          ob_enc: SequenceEncoder, acc) -> None:
        """Fold one batch of tagged sequences into ``acc`` (keys ``init``,
        ``trans``, ``emit`` — all exact int64 counts)."""
        st_codes, _ = st_enc.encode([[s for _, s in seq] for seq in seqs])
        ob_codes, _ = ob_enc.encode([[o for o, _ in seq] for seq in seqs])
        s, o = len(st_enc), len(ob_enc)
        if not st_codes.size:
            return
        # initial states
        acc.add("init", np.bincount(st_codes[:, 0][st_codes[:, 0] >= 0],
                                    minlength=s).astype(np.int64))
        from avenir_tpu.parallel.mesh import maybe_shard_batch
        # transitions (−1 pads are count-neutral under one-hot)
        a_src, a_dst = maybe_shard_batch(self.mesh, *adjacent_pairs(st_codes))
        acc.add("trans", agg.transition_counts(a_src, a_dst, s, s))
        # emissions: state/obs pairs at the same position
        valid = (st_codes >= 0) & (ob_codes >= 0)
        st_flat, ob_flat = maybe_shard_batch(
            self.mesh,
            np.where(valid, st_codes, -1).ravel(),
            np.where(valid, ob_codes, -1).ravel())
        acc.add("emit", agg.transition_counts(st_flat, ob_flat, s, o))

    def finalize(self, st_enc: SequenceEncoder, ob_enc: SequenceEncoder,
                 acc) -> HMMModel:
        s, o = len(st_enc), len(ob_enc)
        get = lambda k, shape: (np.asarray(acc.get(k), np.float64)
                                if k in acc else np.zeros(shape))
        return self._normalize(st_enc, ob_enc, get("trans", (s, s)),
                               get("emit", (s, o)), get("init", (s,)))

    def fit_tagged_chunks(self, chunks, state_encoder: SequenceEncoder,
                          obs_encoder: SequenceEncoder,
                          accumulator=None) -> HMMModel:
        """Streaming fully-tagged fit over an iterable of sequence batches;
        both encoders must be pre-built (``model.states`` /
        ``model.observations``) for chunk-order-independent codes.  All
        counts are exact integers, so a multi-process merge of the
        injected ``accumulator`` is order-free.  Raises ``NoDataError``
        when no process contributed anything (after the merge collective,
        mirroring :meth:`MarkovChain.fit_chunks`)."""
        acc = accumulator if accumulator is not None else agg.Accumulator()
        for seqs in chunks:
            self.accumulate_tagged(seqs, state_encoder, obs_encoder, acc)
        if "trans" not in acc:
            from avenir_tpu.core.encoding import NoDataError
            raise NoDataError("no data")
        return self.finalize(state_encoder, obs_encoder, acc)

    def fit_partially_tagged(
        self,
        token_seqs: Sequence[Sequence[str]],
        states: Sequence[str],
        window_function: Sequence[float] = (1.0, 0.75, 0.5, 0.25),
        obs_encoder: Optional[SequenceEncoder] = None,
    ) -> HMMModel:
        """Partially-tagged mode: state names appear inline among observation
        tokens; each state claims the observations out to the midpoint toward
        its neighboring states, weighted by distance through
        ``window_function`` (HiddenMarkovModelBuilder.java:174-260, with the
        midpoint computed as intended rather than with the reference's
        precedence slip)."""
        state_set = set(states)
        st_enc = SequenceEncoder(list(states))
        ob_enc = obs_encoder or SequenceEncoder().fit(
            [[t for t in seq if t not in state_set] for seq in token_seqs])
        acc = agg.Accumulator()
        self.accumulate_partial(token_seqs, st_enc, ob_enc, window_function,
                                acc)
        return self.finalize(st_enc, ob_enc, acc)

    def fit_partially_tagged_chunks(self, chunks, states: Sequence[str],
                                    obs_encoder: SequenceEncoder,
                                    window_function: Sequence[float] = (1.0, 0.75, 0.5, 0.25),
                                    accumulator=None) -> HMMModel:
        """Streaming partially-tagged fit; ``obs_encoder`` must be pre-built
        (``model.observations``).  ``init``/``trans`` counts are exact
        integers; ``emit`` sums window weights in float64 — with the
        default dyadic window (1, .75, .5, .25) those sums are exact too,
        so a multi-process merge stays byte-identical; non-dyadic custom
        windows may differ from a single-process run in the last ulp."""
        st_enc = SequenceEncoder(list(states))
        acc = accumulator if accumulator is not None else agg.Accumulator()
        for seqs in chunks:
            self.accumulate_partial(seqs, st_enc, obs_encoder,
                                    window_function, acc)
        if "init" not in acc:
            from avenir_tpu.core.encoding import NoDataError
            raise NoDataError("no data")
        return self.finalize(st_enc, obs_encoder, acc)

    def accumulate_partial(self, token_seqs, st_enc: SequenceEncoder,
                           ob_enc: SequenceEncoder,
                           window_function: Sequence[float], acc) -> None:
        """Fold one batch of partially-tagged sequences into ``acc``."""
        state_set = set(st_enc.symbols)
        s, o = len(st_enc), len(ob_enc)
        init = np.zeros(s, np.int64)
        trans = np.zeros((s, s), np.int64)
        st_list: List[int] = []
        ob_list: List[int] = []
        w_list: List[float] = []
        wf = list(window_function)
        for seq in token_seqs:
            pos = [i for i, t in enumerate(seq) if t in state_set]
            if not pos:
                continue
            init[st_enc._map[seq[pos[0]]]] += 1
            for i in range(len(pos) - 1):
                trans[st_enc._map[seq[pos[i]]], st_enc._map[seq[pos[i + 1]]]] += 1
            for i, p in enumerate(pos):
                left = (p + pos[i - 1]) // 2 + 1 if i > 0 else None
                right = (p + pos[i + 1]) // 2 if i < len(pos) - 1 else None
                if left is None:
                    span = (right - p) if right is not None else (len(seq) - 1 - p) // 2
                    left = max(p - span, 0)
                if right is None:
                    span = p - left
                    right = min(p + span, len(seq) - 1)
                sc = st_enc._map[seq[p]]
                for j in range(p - 1, left - 1, -1):
                    if seq[j] in state_set:
                        continue
                    k = p - 1 - j
                    st_list.append(sc)
                    ob_list.append(ob_enc._map[seq[j]])
                    w_list.append(wf[k] if k < len(wf) else wf[-1])
                for j in range(p + 1, right + 1):
                    if seq[j] in state_set:
                        continue
                    k = j - p - 1
                    st_list.append(sc)
                    ob_list.append(ob_enc._map[seq[j]])
                    w_list.append(wf[k] if k < len(wf) else wf[-1])
        emit = np.zeros((s, o))
        if st_list:
            from avenir_tpu.parallel.mesh import maybe_shard_batch

            st_all = np.array(st_list, np.int32)
            ob_all = np.array(ob_list, np.int32)
            w_all = np.array(w_list, np.float32)
            # chunked accumulation in float64 on host: stays under the
            # kernel's per-chunk cap on any corpus size and bounds f32
            # rounding in the on-device partial sums. Mesh pad rows are
            # neutral (−1 codes one-hot to zero, w pads to 0.0); float
            # reduction order may differ in the last ulp under a mesh.
            # The step is a multiple of the data-axis size so that mesh
            # padding (up to the next multiple of d) can never push a full
            # chunk to >= the cap.
            d = (self.mesh.shape.get("data", 1)
                 if self.mesh is not None else 1) or 1
            # max(·, d) keeps the loop well-formed even for a (theoretical)
            # data axis wider than the chunk cap, where the floored multiple
            # would be 0 and range(0, n, 0) would raise (round-2 advisory)
            step = max(((agg.MAX_EXACT_CHUNK_ROWS - 1) // d) * d, d)
            for s0 in range(0, len(st_all), step):
                st_b, ob_b, w_b = maybe_shard_batch(
                    self.mesh, st_all[s0:s0 + step], ob_all[s0:s0 + step],
                    w_all[s0:s0 + step])
                emit += np.asarray(agg.weighted_transition_counts(
                    st_b, ob_b, w_b, s, o), np.float64)
        acc.add("init", init)
        acc.add("trans", trans)
        acc.add("emit", emit)

    def _normalize(self, st_enc, ob_enc, trans, emit, init) -> HMMModel:
        lam = self.laplace
        a = (trans + lam) / (trans + lam).sum(axis=1, keepdims=True)
        b = (emit + lam) / (emit + lam).sum(axis=1, keepdims=True)
        pi = (init + lam) / (init + lam).sum()
        return HMMModel(list(st_enc.symbols), list(ob_enc.symbols), a, b, pi)


# ---------------------------------------------------------------------------
# Viterbi
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _viterbi_batch(log_a: jax.Array, log_b: jax.Array, log_pi: jax.Array,
                   obs: jax.Array) -> jax.Array:
    """obs [R, T] (−1 pad) → [R, T] best state path (−1 on pads).

    Forward max-product scan with backpointers; padded steps are identity
    (δ carried, backpointer = self) so one compiled program serves ragged
    batches."""
    s = log_a.shape[0]

    def decode_one(o):
        t = o.shape[0]
        valid0 = o[0] >= 0
        delta0 = jnp.where(valid0, log_pi + log_b[:, jnp.maximum(o[0], 0)],
                           jnp.zeros(s))

        def step(delta, ot):
            valid = ot >= 0
            cand = delta[:, None] + log_a                     # [S_prev, S]
            best_prev = jnp.argmax(cand, axis=0)              # [S]
            best_val = jnp.max(cand, axis=0) + log_b[:, jnp.maximum(ot, 0)]
            new_delta = jnp.where(valid, best_val, delta)
            ptr = jnp.where(valid, best_prev, jnp.arange(s))
            return new_delta, ptr

        delta_t, ptrs = jax.lax.scan(step, delta0, o[1:])     # ptrs [T-1, S]
        last = jnp.argmax(delta_t)

        def back(state, ptr):
            prev = ptr[state]
            return prev, prev        # emit path[t], not the incoming path[t+1]

        _, path_rev = jax.lax.scan(back, last, ptrs, reverse=True)
        path = jnp.concatenate([path_rev, jnp.array([last])])
        return jnp.where(o >= 0, path, -1)

    return jax.vmap(decode_one)(obs)


_NEG = -1.0e30          # max-plus "-inf" kept finite (NaN-safe under XLA)


def _step_matrices(log_a: jax.Array, log_b: jax.Array, obs: jax.Array) -> jax.Array:
    """[T-1, S, S] max-plus step matrices M_t[i,j] = A[i,j] + B[j, o_t] for
    t ≥ 1; padded steps (o_t < 0) become the max-plus identity (0 diagonal,
    -BIG elsewhere) so δ is carried unchanged."""
    s = log_a.shape[0]
    steps = log_a[None, :, :] + log_b[:, jnp.maximum(obs[1:], 0)].T[:, None, :]
    eye = jnp.where(jnp.eye(s, dtype=bool), 0.0, _NEG)
    return jnp.where((obs[1:] >= 0)[:, None, None], steps, eye[None])


def _maxplus(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a ⊗ b)[i,j] = max_k a[i,k] + b[k,j] — the associative max-plus
    matrix product underlying the Viterbi recurrence."""
    return jnp.max(a[..., :, :, None] + b[..., None, :, :], axis=-2)


def _viterbi_assoc_batch(log_a: jax.Array, log_b: jax.Array, log_pi: jax.Array,
                         obs: jax.Array) -> jax.Array:
    """Log-depth Viterbi: ``associative_scan`` over max-plus step matrices.

    Same results as :func:`_viterbi_batch` but O(log T) depth at O(T·S³)
    work — the long-sequence form (SURVEY.md §2.12: 'associative-scan for
    the max-plus recurrence if long sequences matter'). Backpointers are
    recomputed in parallel from the prefix δ's, so only the final [T]
    backtrack is sequential.
    """
    s = log_a.shape[0]

    def decode_one(o):
        valid0 = o[0] >= 0
        delta0 = jnp.where(valid0, log_pi + log_b[:, jnp.maximum(o[0], 0)],
                           jnp.zeros(s))
        steps = _step_matrices(log_a, log_b, o)               # [T-1, S, S]
        prefix = jax.lax.associative_scan(_maxplus, steps)    # [T-1, S, S]
        # δ_t for t ≥ 1, all at once: δ_t = δ_0 ⊗ prefix_t
        deltas = jnp.max(delta0[None, :, None] + prefix, axis=1)   # [T-1, S]
        all_deltas = jnp.concatenate([delta0[None], deltas])       # [T, S]
        # backpointers in parallel: ψ_t[j] = argmax_i δ_{t-1}[i] + M_t[i,j]
        ptrs = jnp.argmax(all_deltas[:-1, :, None] + steps, axis=1)  # [T-1, S]
        # padded steps have identity M: argmax column j is j (carry) ✓
        last = jnp.argmax(all_deltas[-1])

        def back(state, ptr):
            prev = ptr[state]
            return prev, prev

        _, path_rev = jax.lax.scan(back, last, ptrs, reverse=True)
        path = jnp.concatenate([path_rev, jnp.array([last])])
        return jnp.where(o >= 0, path, -1)

    return jax.vmap(decode_one)(obs)


def viterbi_time_sharded(log_a: jax.Array, log_b: jax.Array, log_pi: jax.Array,
                         obs_row: jax.Array, mesh, axis: str = "data"
                         ) -> jax.Array:
    """Context-parallel Viterbi: ONE long sequence with its time axis
    sharded over a mesh axis.

    The sequence-parallelism pattern the task's long-context requirement
    maps to in this framework: each device runs a local ``associative_scan``
    over its chunk of max-plus step matrices, a single ``all_gather`` of the
    [D, S, S] per-chunk products (ICI/DCN traffic independent of T) gives
    every device its exclusive offset, and local prefixes are rebased — the
    max-plus analog of blockwise-parallel attention's chunked softmax
    rebasing. Backtrack pointers are computed locally and the final [T]
    pointer chase runs once, after gather.

    obs_row: [T] observation codes (−1 pad), T divisible by the axis size.
    Returns [T] state path.
    """
    import functools as _ft

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    s = log_a.shape[0]
    d = mesh.shape[axis]
    ring = [(i, (i + 1) % d) for i in range(d)]

    @_ft.partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(), P(), P(axis)),
                 out_specs=(P(axis), P(axis)))
    def forward(la, lb, lpi, o_loc):
        # o_loc [L = T/D]: chunk d's step matrices cover the transitions
        # INTO its positions; the first one needs the previous chunk's last
        # observation (one scalar ppermute hop around the ring)
        idx = jax.lax.axis_index(axis)
        prev_tail = jax.lax.ppermute(o_loc[-1], axis, ring)
        o_ext = jnp.concatenate([prev_tail[None], o_loc])      # [L + 1]
        steps = _step_matrices(la, lb, o_ext)                  # [L, S, S]
        # global position 0 has no incoming transition: identity
        eye = jnp.where(jnp.eye(s, dtype=bool), 0.0, _NEG)
        steps = steps.at[0].set(jnp.where(idx == 0, eye, steps[0]))
        prefix = jax.lax.associative_scan(_maxplus, steps)     # [L, S, S]
        # exclusive offset = max-plus product of all previous chunks' totals:
        # ONE [D, S, S] all_gather — cross-device traffic independent of T
        totals = jax.lax.all_gather(prefix[-1], axis)          # [D, S, S]

        def offset_scan(carry, x):
            return _maxplus(carry, x), carry

        # newer jax's varying-type system needs the closed-over constant
        # cast to device-varying before the scan; pre-varying jax treats
        # every array as device-local already, so the cast is an identity
        pcast = getattr(jax.lax, "pcast", None)
        init = pcast(eye, (axis,), to="varying") if pcast else eye
        _, excl = jax.lax.scan(offset_scan, init, totals)      # [D, S, S]
        global_prefix = _maxplus(excl[idx][None], prefix)      # [L, S, S]
        # δ_t = δ_0 ⊗ (M_1 … M_t); δ_0 from the replicated first observation
        o0 = jax.lax.all_gather(o_loc[0], axis)[0]
        delta0 = jnp.where(o0 >= 0, lpi + lb[:, jnp.maximum(o0, 0)],
                           jnp.zeros(s))
        deltas = jnp.max(delta0[None, :, None] + global_prefix, axis=1)  # [L, S]
        # backpointers need δ_{t-1}: shift deltas by one along the ring
        prev_last = jax.lax.ppermute(deltas[-1], axis, ring)
        delta_prev = jnp.concatenate([prev_last[None], deltas[:-1]])
        delta_prev = jnp.where(idx == 0,
                               jnp.concatenate([delta0[None], deltas[:-1]]),
                               delta_prev)
        # position 0 overall: ψ unused (identity step makes argmax = j)
        psi = jnp.argmax(delta_prev[:, :, None] + steps, axis=1)  # [L, S]
        return deltas, psi

    deltas, psi = forward(log_a, log_b, log_pi,
                          jnp.asarray(obs_row, jnp.int32))

    @jax.jit
    def backtrack(deltas, psi):
        last = jnp.argmax(deltas[-1])

        def back(state, ptr):
            prev = ptr[state]
            return prev, prev

        _, path_rev = jax.lax.scan(back, last, psi[1:], reverse=True)
        return jnp.concatenate([path_rev, jnp.array([last])])

    path = np.asarray(backtrack(deltas, psi))
    valid = np.asarray(obs_row) >= 0
    return np.where(valid, path, -1)


class ViterbiDecoder:
    """Batch Viterbi decoding over an HMM model.

    ``method``: ``"scan"`` (sequential ``lax.scan`` over time, O(T·S²) work —
    the default for typical short per-record sequences) or ``"assoc"``
    (log-depth ``associative_scan`` over max-plus step matrices, O(T·S³)
    work — for long sequences). :func:`viterbi_time_sharded` additionally
    shards one sequence's time axis over a device mesh."""

    def __init__(self, model: HMMModel, method: str = "scan", mesh=None):
        if method not in ("scan", "assoc"):
            raise ValueError(f"unknown viterbi method {method!r}")
        self.model = model
        self.method = method
        self.mesh = mesh          # optional data mesh: records shard over it
        eps = 1e-12
        self._log_a = jnp.asarray(np.log(np.maximum(model.transition, eps)), jnp.float32)
        self._log_b = jnp.asarray(np.log(np.maximum(model.emission, eps)), jnp.float32)
        self._log_pi = jnp.asarray(np.log(np.maximum(model.initial, eps)), jnp.float32)
        self._obs_map = {o: i for i, o in enumerate(model.observations)}

    def decode_codes(self, obs: np.ndarray) -> np.ndarray:
        """[R, T] obs codes (−1 pad) → [R, T] state codes (−1 pad).

        Under a data mesh the record axis shards across devices (all-−1 pad
        rows decode to all-−1 and are trimmed) — the map-only prediction
        job's record parallelism."""
        from avenir_tpu.parallel.mesh import maybe_shard_batch

        fn = _viterbi_batch if self.method == "scan" else _viterbi_assoc_batch
        obs = np.asarray(obs, np.int32)
        n = obs.shape[0]
        obs_b = maybe_shard_batch(self.mesh, obs)[0]
        return np.asarray(fn(self._log_a, self._log_b, self._log_pi,
                             obs_b))[:n]

    def decode(self, obs_seqs: Sequence[Sequence[str]],
               pad_to: Optional[int] = None) -> List[List[str]]:
        """``pad_to`` pins the time axis to a fixed length instead of the
        batch max — the serving plane's shape discipline (one compiled
        program per bucket, regardless of the sequences in it).  Padded
        steps are max-plus identities, so the decoded path of each record
        is identical for any ``pad_to`` ≥ its length; longer sequences
        raise (a serving request must fail loudly, not silently truncate)."""
        t = max((len(s) for s in obs_seqs), default=0)
        if pad_to is not None:
            if t > pad_to:
                raise ValueError(
                    f"sequence of length {t} exceeds pad_to={pad_to}")
            t = pad_to
        codes = np.full((len(obs_seqs), t), -1, np.int32)
        for r, seq in enumerate(obs_seqs):
            for j, o in enumerate(seq):
                codes[r, j] = self._obs_map[o]
        paths = self.decode_codes(codes)
        return [[self.model.states[c] for c in row if c >= 0] for row in paths]


class ViterbiStatePredictor:
    """The map-only prediction job: rows of (id, obs...) → decoded states
    (ViterbiStatePredictor.java:114-142; ``obs:state`` pair output mode)."""

    def __init__(self, model: HMMModel, pair_output: bool = False,
                 delim: str = DELIM, mesh=None):
        self.decoder = ViterbiDecoder(model, mesh=mesh)
        self.pair_output = pair_output
        self.delim = delim

    def predict_lines(self, rows: Sequence[Sequence[str]],
                      pad_to: Optional[int] = None) -> List[str]:
        ids = [r[0] for r in rows]
        seqs = [list(r[1:]) for r in rows]
        paths = self.decoder.decode(seqs, pad_to=pad_to)
        out = []
        for rid, seq, path in zip(ids, seqs, paths):
            if self.pair_output:
                body = self.delim.join(f"{o}:{s}" for o, s in zip(seq, path))
            else:
                body = self.delim.join(path)
            out.append(f"{rid}{self.delim}{body}")
        return out
