"""The plain reference of the kNN deployment, and the comparison that decides
``correct``.

Straightforward semantics of upstream's ``knn.sh`` DAG, independent of the
program: it imports nothing of ``avenir_tpu`` and takes nothing the program
made (no packed operand, no normalised copy, no posteriors).  From the raw
reference signals and the request's own CSV text it computes

- train-range normalisation of the nine signals to [0, 1];
- the exact k nearest references by the euclidean metric, as direct f32
  differences (never the norm expansion), brute force over all references on
  the accelerator in blocks, keeping ``keep`` > k candidates so that a tie at
  the k-th place can be seen;
- the vote: gaussian kernel weights, optionally times the neighbour's
  Gaussian-Naive-Bayes posterior of its own class (class-conditional
  weighting, ``NearestNeighbor.java:239-240``), computed in float64.

``precision="bf16"`` is the CONTROL: the same search with every operand and
every intermediate rounded to bfloat16 — the nearest precision below the f32
the configuration states.  ``lax.reduce_precision`` does the rounding, since
a compiler may drop an ``astype`` round trip as excess precision.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 17        # references per step of the blocked search


def normalise(cont: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    span = np.maximum(hi - lo, np.float32(1e-9))
    return np.clip((cont - lo) / span, 0.0, 1.0).astype(np.float32)


def parse_queries(lines: Sequence[str], ordinals: Sequence[int],
                  delim: str = ",") -> np.ndarray:
    """[S, A] float32 raw signals read from the request text itself."""
    rows = [line.split(delim) for line in lines]
    return np.array([[float(r[o]) for o in ordinals] for r in rows],
                    np.float32)


def _bf16(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("keep", "block", "low"))
def _search(q01: jax.Array, r01_t: jax.Array, n_real: jax.Array, *,
            keep: int, block: int, low: bool):
    """q01 [S, A]; r01_t [A, Npad] (attribute-major, Npad % block == 0).
    Returns ([S, keep] squared distances ascending, [S, keep] indices)."""
    s, a = q01.shape
    rnd = _bf16 if low else (lambda x: x)
    q = rnd(q01)

    def body(b, carry):
        best_d, best_i = carry
        blk = rnd(jax.lax.dynamic_slice(r01_t, (0, b * block), (a, block)))
        d2 = jnp.zeros((s, block), jnp.float32)
        for j in range(a):
            diff = rnd(q[:, j, None] - blk[j][None, :])
            d2 = rnd(d2 + rnd(diff * diff))
        idx = b * block + jnp.arange(block, dtype=jnp.int32)
        d2 = jnp.where(idx[None, :] < n_real, d2, jnp.inf)
        neg, pos = jax.lax.top_k(-d2, keep)
        cd = jnp.concatenate([best_d, -neg], axis=1)
        ci = jnp.concatenate([best_i, idx[pos]], axis=1)
        neg2, pos2 = jax.lax.top_k(-cd, keep)
        return -neg2, jnp.take_along_axis(ci, pos2, axis=1)

    init = (jnp.full((s, keep), jnp.inf, jnp.float32),
            jnp.full((s, keep), -1, jnp.int32))
    return jax.lax.fori_loop(0, r01_t.shape[1] // block, body, init)


class Reference:
    """The reference set as the reference holds it: raw signals and labels on
    the host, the normalised attribute-major copy on the accelerator."""

    def __init__(self, cont: np.ndarray, labels: np.ndarray,
                 block: int = BLOCK):
        self.cont, self.labels = cont, labels
        self.n, self.attrs = cont.shape
        self.lo, self.hi = cont.min(axis=0), cont.max(axis=0)
        self.block = min(block, max(128, 1 << (self.n - 1).bit_length()))
        npad = -(-self.n // self.block) * self.block
        r01_t = np.zeros((self.attrs, npad), np.float32)
        step = 1 << 20
        for s0 in range(0, self.n, step):
            r01_t[:, s0:s0 + step] = normalise(
                cont[s0:s0 + step], self.lo, self.hi).T
        self.r01_t = jnp.asarray(r01_t)
        self._nb: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None

    def search(self, q_raw: np.ndarray, keep: int, precision: str = "f32"
               ) -> Tuple[np.ndarray, np.ndarray]:
        """([S, keep] distances in [0, 1] ascending, [S, keep] indices)."""
        q01 = normalise(q_raw, self.lo, self.hi)
        d2, idx = _search(jnp.asarray(q01), self.r01_t, jnp.int32(self.n),
                          keep=min(keep, self.n), block=self.block,
                          low=(precision == "bf16"))
        d2 = np.asarray(d2, np.float64)
        return np.clip(np.sqrt(d2 / self.attrs), 0.0, 1.0), np.asarray(idx)

    def distance_to(self, q_raw: np.ndarray, idx: np.ndarray) -> np.ndarray:
        """[S, k] distances from each query to the references it names."""
        q01 = normalise(q_raw, self.lo, self.hi).astype(np.float64)
        r01 = normalise(self.cont[np.maximum(idx, 0)], self.lo,
                        self.hi).astype(np.float64)
        d2 = ((q01[:, None, :] - r01) ** 2).sum(-1)
        return np.clip(np.sqrt(d2 / self.attrs), 0.0, 1.0)

    # -- Gaussian Naive Bayes posteriors (class-conditional weighting) -------
    def _nb_stats(self):
        if self._nb is None:
            c = int(self.labels.max()) + 1
            cnt = np.zeros(c)
            s1 = np.zeros((c, self.attrs))
            s2 = np.zeros((c, self.attrs))
            step = 1 << 20
            for s0 in range(0, self.n, step):
                x = self.cont[s0:s0 + step].astype(np.float64)
                lab = self.labels[s0:s0 + step]
                for cls in range(c):
                    xc = x[lab == cls]
                    cnt[cls] += xc.shape[0]
                    s1[cls] += xc.sum(axis=0)
                    s2[cls] += (xc * xc).sum(axis=0)
            mean = s1 / np.maximum(cnt, 1.0)[:, None]
            var = np.maximum(s2 / np.maximum(cnt, 1.0)[:, None] - mean ** 2,
                             1e-12)
            var *= (cnt / np.maximum(cnt - 1.0, 1.0))[:, None]   # sample sigma
            self._nb = (np.log(cnt / cnt.sum()), mean, np.sqrt(var))
        return self._nb

    def own_class_posterior(self, idx: np.ndarray) -> np.ndarray:
        """[S, k] P(label_j | x_j) of each named reference under Gaussian
        Naive Bayes fitted on the whole reference set."""
        log_prior, mean, std = self._nb_stats()
        x = self.cont[idx].astype(np.float64)[..., None, :]       # [S,k,1,A]
        sd = np.maximum(std, 1e-6)
        logp = (log_prior + (-0.5 * ((x - mean) / sd) ** 2 - np.log(sd)
                             - 0.5 * np.log(2.0 * np.pi)).sum(-1))  # [S,k,C]
        logp -= logp.max(axis=-1, keepdims=True)
        post = np.exp(logp)
        post /= post.sum(axis=-1, keepdims=True)
        return np.take_along_axis(post, self.labels[idx][..., None],
                                  axis=-1)[..., 0]

    def vote(self, dists: np.ndarray, idx: np.ndarray, kernel: str,
             sigma: float, class_cond: bool, num_classes: int) -> np.ndarray:
        """[S, C] vote shares of the given neighbours."""
        if kernel == "gaussian":
            w = np.exp(-0.5 * (dists / max(sigma, 1e-6)) ** 2)
        elif kernel == "none":
            w = np.ones_like(dists)
        else:
            raise ValueError(f"reference has no kernel {kernel!r}")
        if class_cond:
            w = w * self.own_class_posterior(idx)
        lab = self.labels[idx]
        scores = np.stack([(w * (lab == c)).sum(axis=1)
                           for c in range(num_classes)], axis=1)
        return scores / np.maximum(scores.sum(axis=1, keepdims=True), 1e-9)


# a k-th and (k+1)-th reference nearer to each other than this (relative) are
# a tie: either may stand in the k-th place, so the vote is not compared
TIE_REL = 1e-5
# A reply is held to the reference's class only where the reference's vote is
# further from even than twice the limit on ``share_gap``: two shares that
# may each move by the limit can change places inside that margin.


def compare(ref: Reference, settings: Dict, query_lines: Sequence[str],
            replies: Sequence[Optional[str]], got_dist: np.ndarray,
            got_idx: np.ndarray, got_shares: np.ndarray,
            class_values: Sequence[str], ordinals: Sequence[int],
            share_limit: float, precision: str = "f32", delim: str = ","
            ) -> Dict[str, float]:
    """The numbers ``correct`` is decided from, for a sample of answered
    requests: what the timed path produced (``replies``, and the neighbours,
    distances and vote shares it computed on the way) against the reference.

    - ``dist_gap``: widest |distance of the j-th neighbour returned − distance
      of the reference's j-th nearest| — blind to the order among ties;
    - ``nbr_gap``: widest |distance returned for a neighbour − the reference's
      own distance to the reference row it names|;
    - ``share_gap``: widest |vote share − reference's| over rows with no tie
      at the k-th place;
    - ``class_mismatch``: replies that are not ``<request>,<class>`` with the
      class the reference's vote gives (rows with a tie at the k-th place or
      a vote within 2 x ``share_limit`` of even excepted).
    """
    k = int(settings["top.match.count"])
    q_raw = parse_queries(query_lines, ordinals, delim)
    ref_d, ref_i = ref.search(q_raw, keep=k + 6, precision=precision)
    dist_gap = float(np.abs(got_dist - ref_d[:, :k]).max())
    nbr_gap = float(np.abs(got_dist - ref.distance_to(q_raw, got_idx)).max())
    in_range = bool(((got_idx >= 0) & (got_idx < ref.n)).all())
    if ref_d.shape[1] > k:
        tie = ref_d[:, k] <= ref_d[:, k - 1] * (1.0 + TIE_REL)
    else:
        tie = np.zeros(len(query_lines), bool)
    shares = ref.vote(ref_d[:, :k], ref_i[:, :k], settings["kernel.function"],
                      float(settings.get("kernel.param", 0.3)),
                      bool(settings.get("class.condtion.weighted", False)),
                      len(class_values))
    share_gap = float(np.abs(got_shares - shares)[~tie].max()) if (~tie).any() \
        else 0.0
    top2 = np.sort(shares, axis=1)[:, -2:]
    decided = ~tie & ((top2[:, 1] - top2[:, 0]) > 2.0 * share_limit)
    want = [f"{line}{delim}{class_values[int(c)]}"
            for line, c in zip(query_lines, shares.argmax(axis=1))]
    mismatch = 0
    for line, reply, exp, dec in zip(query_lines, replies, want, decided):
        well_formed = (reply is not None
                       and reply.rsplit(delim, 1)[0] == line
                       and reply.rsplit(delim, 1)[-1] in class_values)
        if not well_formed or (dec and reply != exp):
            mismatch += 1
    return {"dist_gap": dist_gap,
            "nbr_gap": nbr_gap if in_range else float("inf"),
            "share_gap": share_gap, "class_mismatch": float(mismatch),
            "rows": float(len(query_lines)), "ties": float(tie.sum()),
            "undecided": float((~decided).sum())}


def control(ref: Reference, settings: Dict, query_lines: Sequence[str],
            class_values: Sequence[str], ordinals: Sequence[int],
            share_limit: float, precision: str = "bf16", delim: str = ","
            ) -> Dict[str, float]:
    """The reference put in the program's place and computed in a lower
    precision: its neighbours, distances, vote shares and reply lines go
    through the same comparison as the program's.  With ``precision="f32"``
    the comparison is of the reference with itself (all gaps 0)."""
    k = int(settings["top.match.count"])
    q_raw = parse_queries(query_lines, ordinals, delim)
    dist, idx = ref.search(q_raw, keep=k, precision=precision)
    shares = ref.vote(dist, idx, settings["kernel.function"],
                      float(settings.get("kernel.param", 0.3)),
                      bool(settings.get("class.condtion.weighted", False)),
                      len(class_values))
    replies = [f"{line}{delim}{class_values[int(c)]}"
               for line, c in zip(query_lines, shares.argmax(axis=1))]
    return compare(ref, settings, query_lines, replies, dist, idx, shares,
                   class_values, ordinals, share_limit, delim=delim)
