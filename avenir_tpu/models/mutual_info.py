"""Mutual-information feature analysis — the flagship exploration job.

Capability parity with the reference's ``explore/MutualInformation.java``
(mapper emits 7 distribution families per record :61-67,136-214; single
reducer materializes joints and prints MI values :598-784 and
feature-selection scores :792-823) plus ``MutualInformationScore.java``
(MIM :98-101, MIFS with redundancy factor :116-153, JMI :177-179,
DISR :185-187, MRMR :265-300).

TPU design: where the reference shuffles O(records · F²) emitted tuples to
one reducer, this computes the exact same joint distributions as one-hot
einsum contractions per chunk — [F,B,C] feature-class and [P,B,B,C]
pair-class count tensors — accumulated in 64-bit on host. All seven
reference distribution families are marginals of these two tensors plus the
class vector, so a single pass yields everything. Feature pairs are processed
in bounded-size chunks to keep the [P,B,B,C] tensor inside HBM
(SURVEY.md §7 'high-cardinality joint-distribution tensors').

MI values are in nats (the reference uses log2-free ``Math.log`` too).
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from avenir_tpu.core.encoding import EncodedDataset, peek_chunks
from avenir_tpu.ops import agg, info


@dataclass
class MutualInfoResult:
    """All distributions + MI statistics from one pass over the data."""

    feature_names: List[str]                 # [F] display names (binned features)
    class_values: List[str]
    n_bins: np.ndarray                       # [F]
    class_counts: np.ndarray                 # [C]
    feature_class_counts: np.ndarray         # [F, B, C]
    pair_index: np.ndarray                   # [P, 2] (i, j) with i < j
    pair_class_counts: np.ndarray            # [P, B, B, C]

    # derived statistics (computed in finish())
    feature_class_mi: Optional[np.ndarray] = None        # [F]  I(f; class)
    feature_pair_mi: Optional[np.ndarray] = None         # [P]  I(fi; fj)
    pair_class_mi: Optional[np.ndarray] = None           # [P]  I((fi,fj); class)
    pair_class_entropy: Optional[np.ndarray] = None      # [P]  H(fi, fj, class)
    feature_pair_class_cond_mi: Optional[np.ndarray] = None  # [P] I(fi; fj | class)
    feature_entropy: Optional[np.ndarray] = None         # [F]  H(f)
    class_entropy: Optional[float] = None

    @property
    def num_features(self) -> int:
        return len(self.feature_names)

    # -- distribution views (the reference's 7 families) ---------------------
    def class_distr(self) -> np.ndarray:
        return self.class_counts / self.class_counts.sum()

    def feature_distr(self) -> np.ndarray:
        fc = self.feature_class_counts.sum(-1)
        return fc / np.maximum(fc.sum(-1, keepdims=True), 1)

    def feature_pair_distr(self) -> np.ndarray:
        pc = self.pair_class_counts.sum(-1)
        return pc / np.maximum(pc.sum((-2, -1), keepdims=True), 1)

    def feature_class_cond_distr(self) -> np.ndarray:
        """[F, B, C] P(bin | class) — the reference's feature-class-conditional."""
        fcc = self.feature_class_counts
        return fcc / np.maximum(fcc.sum(1, keepdims=True), 1)

    def feature_pair_class_cond_distr(self) -> np.ndarray:
        """[P, B, B, C] P(bin_i, bin_j | class)."""
        pcc = self.pair_class_counts
        return pcc / np.maximum(pcc.sum((1, 2), keepdims=True), 1)

    def finish(self) -> "MutualInfoResult":
        # one fused jitted kernel on the LOCAL CPU backend: the derived
        # statistics are ~10^4 elements of math, but spelled as ~100 eager
        # jnp ops they each pay a dispatch (and, against a remote TPU, a
        # ~60 ms round trip) — fused + host-local, the whole phase is one
        # sub-millisecond call after a one-time compile
        with info.on_host():
            (fc_mi, f_ent, c_ent, fp_mi, pc_mi, pc_ent, cond) = _derived_stats(
                jnp.asarray(self.feature_class_counts, jnp.float32),
                jnp.asarray(self.pair_class_counts, jnp.float32),
                jnp.asarray(self.class_counts, jnp.float32))
        self.feature_class_mi = np.asarray(fc_mi)
        self.feature_entropy = np.asarray(f_ent)
        self.class_entropy = float(c_ent)
        self.feature_pair_mi = np.asarray(fp_mi)
        self.pair_class_mi = np.asarray(pc_mi)
        self.pair_class_entropy = np.asarray(pc_ent)
        self.feature_pair_class_cond_mi = np.asarray(cond)
        return self

    # -- lookup helpers ------------------------------------------------------
    def pair_pos(self) -> Dict[Tuple[int, int], int]:
        return {(int(i), int(j)): k for k, (i, j) in enumerate(self.pair_index)}

    def to_lines(self, delim: str = ",") -> List[str]:
        """Statistic rows in the spirit of the reference's reducer output:
        tagged rows for each MI family, ordered by feature/pair."""
        lines = []
        for f, name in enumerate(self.feature_names):
            lines.append(delim.join(["featureClassMI", name, f"{self.feature_class_mi[f]:.6f}"]))
        for k, (i, j) in enumerate(self.pair_index):
            a, b = self.feature_names[i], self.feature_names[j]
            lines.append(delim.join(["featurePairMI", a, b, f"{self.feature_pair_mi[k]:.6f}"]))
            lines.append(delim.join(["featurePairClassMI", a, b, f"{self.pair_class_mi[k]:.6f}"]))
            lines.append(delim.join(
                ["featurePairClassCondMI", a, b, f"{self.feature_pair_class_cond_mi[k]:.6f}"]))
        return lines




def result_from_counts(
    feature_names: Sequence[str],
    class_values: Sequence[str],
    n_bins: np.ndarray,
    class_counts: np.ndarray,
    feature_class_counts: np.ndarray,
    pair_index: np.ndarray,
    pair_class_counts: np.ndarray,
) -> MutualInfoResult:
    """Finished :class:`MutualInfoResult` from already-aggregated count
    tensors, without touching data — the finalize step of
    :meth:`MutualInformation.fit` and the SharedScan seam
    (``pipeline/scan.py``): both the [F, B, C] and [P, B, B, C] tensors
    are read-outs of the shared co-occurrence gram."""
    return MutualInfoResult(
        feature_names=list(feature_names),
        class_values=list(class_values),
        n_bins=np.asarray(n_bins, np.int64),
        class_counts=np.asarray(class_counts),
        feature_class_counts=np.asarray(feature_class_counts),
        pair_index=np.asarray(pair_index),
        pair_class_counts=np.asarray(pair_class_counts),
    ).finish()


@jax.jit
def _derived_stats(fcc, pcc, cc):
    """All of finish()'s derived statistics as ONE compiled program.

    fcc [F,B,C], pcc [P,B,B,C], cc [C] float32 counts →
    (featureClassMI [F], featureEntropy [F], classEntropy [],
     featurePairMI [P], pairClassMI [P], pairClassEntropy [P],
     featurePairClassCondMI [P])."""
    p, b, _, c = pcc.shape
    return (info.mutual_information(fcc),
            info.entropy_from_counts(fcc.sum(-1), axis=-1),
            info.entropy_from_counts(cc),
            info.mutual_information(pcc.sum(-1)),
            info.mutual_information(pcc.reshape(p, b * b, c)),
            info.entropy_from_counts(pcc.reshape(p, -1), axis=-1),
            info.conditional_mutual_information(pcc))


class MutualInformation:
    """One-pass MI/distribution engine over encoded chunks.

    ``pair_chunk`` bounds the feature-pair dimension of the on-device
    [P, B, B, C] tensor; pairs are swept in slices and accumulated on host.
    """

    def __init__(self, pair_chunk: int = 256, mesh=None):
        """``mesh``: optional ``jax.sharding.Mesh`` with a ``data`` axis —
        chunks are then batch-sharded over the mesh and XLA inserts the
        cross-device count reduction (−1 pad rows are count-neutral);
        integer counts make the result bit-identical to single-device."""
        self.pair_chunk = pair_chunk
        self.mesh = mesh
        self.count_path = None      # routing tag of the last fit()
        self.chunks_seen = 0        # chunks the last fit() folded

    def fit(self, data: Union[EncodedDataset, Iterable[EncodedDataset]],
            feature_names: Optional[Sequence[str]] = None,
            accumulator=None) -> MutualInfoResult:
        """``accumulator``: an externally-owned (possibly checkpoint-restored)
        ``agg.Accumulator`` — the streaming jobs pass their
        StreamCheckpointer's so mid-stream snapshots see the totals."""
        meta, chunks = peek_chunks(data)           # lazy: stream-friendly
        if meta.labels is None:
            raise ValueError("mutual information requires a class attribute")
        f, b, c = meta.num_binned, meta.max_bins, meta.num_classes
        pair_index = np.array([(i, j) for i in range(f) for j in range(i + 1, f)],
                              np.int32).reshape(-1, 2)
        acc = accumulator if accumulator is not None else agg.Accumulator()
        # single-TPU fast path: one MXU co-occurrence kernel per chunk
        # (ops/pallas_hist.py, ~4-5× the einsum form) accumulates the
        # [Wp, Wp] G matrix; the [F,B,C] tensor and every pair's [B,B,C]
        # joint are read out of the int64 G total ONCE at the end on host
        # (device-side extraction measured slower than the kernel itself).
        # TPU MESHES (round 4) run the same kernel under shard_map — each
        # device grams its local rows and ONE psum over ``data`` merges
        # (collectives.sharded_cooc_step, the shuffle analog the dryrun
        # attests). The einsum loop remains for CPU runs, non-TPU meshes,
        # and shapes past every kernel gate — bit-identical counts.
        from avenir_tpu.ops import pallas_hist
        step = None                        # kernel route when set
        if pallas_hist.use_kernel(f, b, c, mesh=self.mesh):
            step = lambda cd, lb: pallas_hist.cooc_counts(cd, lb, b, c)
        elif (pallas_hist.applicable(f, b, c)
                and pallas_hist.mesh_on_tpu(self.mesh)):
            from avenir_tpu.parallel import collectives
            step = collectives.sharded_cooc_step(self.mesh, b, c)
        gk = pallas_hist.g_key(f, b, c)
        # a checkpoint-restored accumulator dictates the path: counts from a
        # crashed run on the OTHER path must not be silently dropped. A
        # kernel-path snapshot (layout-qualified G key) resumed where the
        # kernel no longer applies converts G into the einsum path's tensors
        # (exact); an einsum-path snapshot simply continues on the einsum
        # path.  A G key from a DIFFERENT kernel layout/version (e.g. the
        # round-3 j-major "g") cannot be read with this build's indexing —
        # reject it loudly rather than corrupt counts.
        if accumulator is not None:
            stale = [k for k in accumulator.names()
                     if (k == "g" or k.startswith("g:")) and k != gk]
            if stale:
                raise ValueError(
                    f"checkpoint holds count matrix {stale[0]!r} from an "
                    f"incompatible kernel layout (this build uses {gk!r}); "
                    f"restart the job without --resume")
            if gk in accumulator and step is None:
                g = accumulator.state()
                fc0, pcc0 = pallas_hist.counts_from_cooc(
                    g.pop(gk), f, b, c, pair_index[:, 0], pair_index[:, 1])
                g["fc"] = fc0
                for s in range(0, len(pair_index), self.pair_chunk):
                    g[f"pcc{s}"] = pcc0[s:s + self.pair_chunk]
                accumulator.load(g)
            elif "fc" in accumulator and step is not None:
                step = None
        # same tags as SharedScan.count_path (pipeline/scan.py), so the
        # job's counters say which route the counts took
        self.count_path = ("einsum" if step is None else
                           "kernel" if self.mesh is None else "sharded")
        self.chunks_seen = 0
        for ds in chunks:
            self.chunks_seen += 1
            from avenir_tpu.parallel.mesh import maybe_shard_batch
            codes, labels = maybe_shard_batch(self.mesh, ds.codes, ds.labels)
            acc.add("class", agg.class_counts(labels, c))
            if step is not None:
                acc.add(gk, step(codes, labels))
                continue
            acc.add("fc", agg.feature_class_counts(codes, labels, c, b))
            for s in range(0, len(pair_index), self.pair_chunk):
                sl = pair_index[s:s + self.pair_chunk]
                pcc = agg.pair_class_counts(
                    codes[:, sl[:, 0]], codes[:, sl[:, 1]], labels, c, b)
                # the expected-set resume gate above rejects stale key
                # families, and MI always counts ALL pairs for a given F,
                # so the pcc chunk keys are fully determined by (F, B, C)
                # which the gate validates — an explicit fingerprint would
                # invalidate every existing checkpoint for no added safety
                # graftlint: disable=GL002
                acc.add(f"pcc{s}", pcc)
        if gk in acc:
            fc_full, pcc_full = pallas_hist.counts_from_cooc(
                acc.get(gk), f, b, c, pair_index[:, 0], pair_index[:, 1])
        elif len(pair_index):
            fc_full = acc.get("fc")
            pcc_full = np.concatenate(
                [acc.get(f"pcc{s}")
                 for s in range(0, len(pair_index), self.pair_chunk)])
        else:
            fc_full = acc.get("fc")
            pcc_full = np.zeros((0, b, b, c), np.int64)
        names = list(feature_names) if feature_names is not None else [
            f"f{o}" for o in meta.binned_ordinals]
        return result_from_counts(
            feature_names=names,
            class_values=list(meta.class_values),
            n_bins=meta.n_bins,
            class_counts=acc.get("class"),
            feature_class_counts=fc_full,
            pair_index=pair_index,
            pair_class_counts=pcc_full,
        )


# ---------------------------------------------------------------------------
# feature-subset scoring (host-side greedy, as in MutualInformationScore.java)
# ---------------------------------------------------------------------------

def _greedy(num_features: int, first: int, gain) -> List[Tuple[int, float]]:
    """Shared greedy loop: start from ``first``, repeatedly add argmax gain."""
    selected = [first]
    out = [(first, float("nan"))]
    while len(selected) < num_features:
        best, best_score = -1, -np.inf
        for f in range(num_features):
            if f in selected:
                continue
            s = gain(f, selected)
            if s > best_score:
                best, best_score = f, s
        selected.append(best)
        out.append((best, best_score))
    return out


def mim_score(result: MutualInfoResult) -> List[Tuple[int, float]]:
    """Mutual Information Maximization: rank by I(f; class)."""
    order = np.argsort(-result.feature_class_mi)
    return [(int(f), float(result.feature_class_mi[f])) for f in order]


def mifs_score(result: MutualInfoResult, redundancy_factor: float = 1.0) -> List[Tuple[int, float]]:
    """MIFS greedy: gain = I(f;c) − β · Σ_{s∈S} I(f;s)."""
    mi_c = result.feature_class_mi
    pos = result.pair_pos()
    pmi = result.feature_pair_mi

    def pair_mi(a, bf):
        return pmi[pos[(min(a, bf), max(a, bf))]]

    def gain(f, sel):
        return mi_c[f] - redundancy_factor * sum(pair_mi(f, s) for s in sel)

    first = int(np.argmax(mi_c))
    out = _greedy(result.num_features, first, gain)
    return [(f, (float(mi_c[f]) if np.isnan(s) else s)) for f, s in out]


def jmi_score(result: MutualInfoResult) -> List[Tuple[int, float]]:
    """Joint Mutual Information greedy: gain = Σ_{s∈S} I((f,s); class)."""
    pos = result.pair_pos()
    jmi = result.pair_class_mi

    def gain(f, sel):
        return sum(jmi[pos[(min(f, s), max(f, s))]] for s in sel)

    first = int(np.argmax(result.feature_class_mi))
    out = _greedy(result.num_features, first, gain)
    return [(f, (float(result.feature_class_mi[f]) if np.isnan(s) else s)) for f, s in out]


def disr_score(result: MutualInfoResult) -> List[Tuple[int, float]]:
    """Double Input Symmetrical Relevance: gain = Σ_s I((f,s);c) / H(f,s,c)."""
    pos = result.pair_pos()
    jmi = result.pair_class_mi
    ent = result.pair_class_entropy

    def gain(f, sel):
        return sum(jmi[k] / max(ent[k], 1e-12)
                   for k in (pos[(min(f, s), max(f, s))] for s in sel))

    first = int(np.argmax(result.feature_class_mi))
    out = _greedy(result.num_features, first, gain)
    return [(f, (float(result.feature_class_mi[f]) if np.isnan(s) else s)) for f, s in out]


def mrmr_score(result: MutualInfoResult) -> List[Tuple[int, float]]:
    """min-Redundancy-Max-Relevance greedy: gain = I(f;c) − mean_{s∈S} I(f;s)."""
    mi_c = result.feature_class_mi
    pos = result.pair_pos()
    pmi = result.feature_pair_mi

    def gain(f, sel):
        red = sum(pmi[pos[(min(f, s), max(f, s))]] for s in sel) / len(sel)
        return mi_c[f] - red

    first = int(np.argmax(mi_c))
    out = _greedy(result.num_features, first, gain)
    return [(f, (float(mi_c[f]) if np.isnan(s) else s)) for f, s in out]


SCORE_ALGORITHMS = {
    "mutual.info.maximization": mim_score,
    "mutual.info.selection": mifs_score,
    "joint.mutual.info": jmi_score,
    "double.input.symmetrical.relevance": disr_score,
    "min.redundancy.max.relevance": mrmr_score,
    # short aliases
    "mim": mim_score, "mifs": mifs_score, "jmi": jmi_score,
    "disr": disr_score, "mrmr": mrmr_score,
}


def score_features(result: MutualInfoResult, algorithm: str, **kwargs) -> List[Tuple[int, float]]:
    try:
        fn = SCORE_ALGORITHMS[algorithm]
    except KeyError:
        raise ValueError(f"unknown scoring algorithm {algorithm!r}; "
                         f"known: {sorted(set(SCORE_ALGORITHMS))}") from None
    return fn(result, **kwargs)
