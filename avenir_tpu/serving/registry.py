"""Model registry — every trained family servable from device-resident params.

The reference's entire prediction surface is offline map-only MR jobs
(``BayesianPredictor``, ``ViterbiStatePredictor``, ``NearestNeighbor`` —
SURVEY §2): a trained model can only score a *file*.  This module turns each
trained artifact into a :class:`ServableModel` — parameters uploaded to the
device ONCE at load, scoring jit-compiled against the microbatcher's fixed
bucket shapes — and a :class:`ModelRegistry` mapping model names to entries.

Parity contract (tests/test_serving.py): every servable routes scoring
through the SAME model-layer predict entry its batch job uses
(``models.naive_bayes.predict_batch``, ``models.tree.predict_fn``,
``models.knn.KNN.predict``, ``models.markov.ViterbiStatePredictor``,
``models.logistic.predict_batch``) and formats its response exactly like the
job's output line, so serving responses are byte-identical to the batch
predictions for the same rows.  Pad rows added by the batcher are sliced off
before formatting — they can never leak into a response.

Artifact handoff reuses the jobs' own config keys (``bayesian.model.file.path``,
``coeff.file.path``, ``tree.model.file.path``, ``training.data.path``,
``hmm.model.file.path``), so a pipeline stage's output artifact plugs straight
into ``serve.models`` (see ``serving/replay.py`` for the driver stage).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from avenir_tpu.core.config import ConfigError, JobConfig
from avenir_tpu.core.csv_io import read_csv_string
from avenir_tpu.core.encoding import (DatasetEncoder, EncodedDataset,
                                      pad_ballast)
from avenir_tpu.jobs.base import Job, read_lines
from avenir_tpu.serving.errors import RequestError
from avenir_tpu.telemetry import spans as tel


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

def _pad_ds(ds: EncodedDataset, pad_to: int) -> EncodedDataset:
    """Pad the batch axis with neutral zero rows up to the bucket size; the
    caller slices outputs back to the real row count, so pad rows are pure
    shape ballast (mask-by-slicing — a pad row's score is never read).
    Routes through the shared :func:`~avenir_tpu.core.encoding.pad_ballast`
    contract with ``fill=0``: scoring pad rows stay in-vocabulary (an
    all-zero request row scores without error), unlike count-path ballast
    whose −1 labels must drop out of every table."""
    if pad_to < ds.num_rows:
        raise ValueError(f"batch of {ds.num_rows} rows exceeds bucket {pad_to}")
    return pad_ballast(ds, pad_to, fill=0)


def _blank_ds(enc: DatasetEncoder, n: int) -> EncodedDataset:
    """An all-zeros encoded batch of ``n`` rows in ``enc``'s code space —
    the warmup operand that compiles a bucket shape without real traffic."""
    return EncodedDataset(
        codes=np.zeros((n, len(enc.binned_fields)), np.int32),
        cont=np.zeros((n, len(enc.cont_fields)), np.float32),
        labels=None, ids=None,
        n_bins=np.array([enc.n_bins[f.ordinal] for f in enc.binned_fields],
                        np.int32),
        class_values=list(enc.class_values),
        binned_ordinals=[f.ordinal for f in enc.binned_fields],
        cont_ordinals=[f.ordinal for f in enc.cont_fields])


def _parse_rows(lines: Sequence[str], delim: str,
                max_ordinal: int) -> np.ndarray:
    """Request payloads → [N, ncols] field array, with the data errors a
    batch job would throw surfaced as typed :class:`RequestError` instead.
    A raise here fails the whole padded batch; the batcher then isolates —
    re-scores each member alone — so one bad request never poisons its
    coalesced neighbors (``BucketedMicrobatcher._dispatch_isolated``)."""
    try:
        rows = read_csv_string("\n".join(lines), delim=delim)
    except ValueError as e:
        raise RequestError(f"unparseable request rows: {e}") from None
    if rows.shape[0] != len(lines):
        raise RequestError("blank request rows are not servable")
    if rows.shape[1] <= max_ordinal:
        raise RequestError(
            f"request rows carry {rows.shape[1]} fields but the schema "
            f"reads ordinal {max_ordinal}")
    return rows


class _LineEncoder:
    """Request lines → the encoded batch padded to ``pad_to``, in one pass
    of the C++ CSV encoder the job ingest uses (``runtime/native.py``), its
    specs built once, here.  ``_parse_rows`` + ``transform`` stay the
    fallback and the oracle: they run whole wherever the library is
    missing, the first line is narrower than the schema reads, a line holds
    a newline, or the native pass refuses the bytes or finds another number
    of records than lines — so a bad request fails with exactly the typed
    error it always did.  The id column is not parsed (no scorer reads
    ``ds.ids``).  ``enc`` is read by attribute only; nothing here changes
    after construction, so the batcher's dispatcher threads share one.

    Spans: ``servable.encode`` over the whole call (``rows``; ``native_rows``
    = ``rows`` on the native pass, 0 on the fallback), ``servable.parse``
    inside it on the fallback only."""

    def __init__(self, enc: DatasetEncoder, delim: str):
        from avenir_tpu.runtime import native

        self.enc, self.delim = enc, delim
        self.max_ordinal = enc.max_ordinal(False)
        self.specs = None
        # the library is found (or built) here, at load, never on a call
        if native.is_available() and len(delim.encode()) == 1 and \
                (enc._fitted or enc.schema_complete(with_labels=False)):
            self.specs = native.EncoderSpecs(enc, with_labels=False,
                                             with_ids=False)

    def __call__(self, lines: Sequence[str], pad_to: int) -> EncodedDataset:
        tracer = tel.tracer()
        n = len(lines)
        with tracer.span("servable.encode",
                         {"rows": n, "native_rows": n}) as span:
            ds = self._native(lines, pad_to)
            if ds is None:
                span.set("native_rows", 0)
                with tracer.span("servable.parse"):
                    rows = _parse_rows(lines, self.delim, self.max_ordinal)
                ds = _pad_ds(self.enc.transform(rows, with_labels=False),
                             pad_to)
        return ds

    def _native(self, lines: Sequence[str],
                pad_to: int) -> Optional[EncodedDataset]:
        if self.specs is None or not lines or pad_to < len(lines):
            return None
        ncols = lines[0].rstrip("\r").count(self.delim) + 1
        text = "\n".join(lines)
        if ncols <= self.max_ordinal or text.count("\n") != len(lines) - 1:
            return None
        try:
            return self.specs.encode(text.encode(), ncols, self.delim,
                                     rows=len(lines), pad_to=pad_to)
        except ValueError:        # a refused field; UnicodeEncodeError too
            return None


def _complete_encoder(conf: JobConfig) -> DatasetEncoder:
    """A transform-ready encoder straight from the schema: online scoring
    has no training pass to fit vocabularies from, so the schema must fully
    specify them (the same contract streaming training already imposes)."""
    enc = Job.encoder_for(conf)
    if not enc.schema_complete(with_labels=False) or not enc.class_values:
        raise ConfigError(
            "serving requires a schema-complete encoder (categorical "
            "cardinality / numeric min+max+bucketWidth, and class "
            "cardinality) — online requests cannot fit a vocabulary")
    return enc


class ServableModel:
    """One loaded model: device-resident params + a fixed-shape scorer.

    ``compile_keys`` records every (bucket, ...) shape this entry has
    dispatched — the batcher diffs it after each batch to count steady-state
    recompiles (zero after warmup is the serving plane's core invariant).
    """

    family: str = ""
    # the row counts a dispatch that carries block rows behind its online
    # rows is padded to (``BucketedMicrobatcher.submit_block``), ascending
    # and each beyond the batcher's largest bucket; empty: no bulk entry.
    # The first is the cheapest RIDE (what the online rows leave of it costs
    # the device next to nothing): the fill target while the blocks keep up.
    # The last is the cheapest ROW: the target while a backlog of blocks
    # waits.  Each is one more warmed shape
    tile_rows: Tuple[int, ...] = ()

    def __init__(self) -> None:
        self.compile_keys: Set[Tuple] = set()

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        """Score ``lines`` (raw CSV request rows) padded to ``pad_to``;
        returns exactly ``len(lines)`` response lines."""
        raise NotImplementedError

    def warmup(self, pad_to: int) -> None:
        """Compile the ``pad_to`` bucket shape on a blank batch."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Naive Bayes
# ---------------------------------------------------------------------------

class NaiveBayesServable(ServableModel):
    """BayesianPredictor's scoring path online: response line =
    ``<request row>,<predictedClass>[,ambiguous]`` — exactly the job's
    output row (bayesian/BayesianPredictor.java:319-391 semantics,
    including cost-based arbitration and the ambiguity flag)."""

    family = "naiveBayes"

    def __init__(self, model, encoder: DatasetEncoder, delim: str = ",",
                 cost: Optional[np.ndarray] = None,
                 ambiguity_threshold: Optional[float] = None):
        super().__init__()
        self.model = model
        self.enc = encoder
        self.delim = delim
        self.cost = cost
        self.ambiguity_threshold = ambiguity_threshold
        self._encode = _LineEncoder(encoder, delim)
        model.scoring_params()            # device upload happens at load

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "NaiveBayesServable":
        from avenir_tpu.jobs.bayesian import _cost_matrix
        from avenir_tpu.models import naive_bayes as nb

        path = conf.get("bayesian.model.file.path")
        if not path:
            raise ConfigError("serving naiveBayes requires "
                              "bayesian.model.file.path")
        enc = _complete_encoder(conf)
        model = nb.model_from_lines(read_lines(path), enc,
                                    delim=conf.field_delim)
        threshold = conf.get_float("class.prob.diff.threshold")
        if threshold is not None and threshold > 1.0:
            threshold /= 100.0            # reference thresholds are % ints
        cost = (_cost_matrix(conf, model.class_values)
                if conf.get_bool("use.cost.based.classifier") else None)
        return cls(model, enc, delim=conf.field_delim, cost=cost,
                   ambiguity_threshold=threshold)

    def _score_ds(self, ds: EncodedDataset):
        from avenir_tpu.models import naive_bayes as nb

        return nb.NaiveBayes().predict(
            self.model, ds, cost=self.cost,
            ambiguity_threshold=self.ambiguity_threshold)

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        ds = self._encode(lines, pad_to)
        self.compile_keys.add((pad_to,))
        result = self._score_ds(ds)
        out = []
        for i, line in enumerate(lines):
            items = [line, self.model.class_values[int(result.predicted[i])]]
            if result.ambiguous is not None and bool(result.ambiguous[i]):
                items.append("ambiguous")
            out.append(self.delim.join(items))
        return out

    def warmup(self, pad_to: int) -> None:
        self.compile_keys.add((pad_to,))
        self._score_ds(_blank_ds(self.enc, pad_to))


# ---------------------------------------------------------------------------
# logistic regression
# ---------------------------------------------------------------------------

class LogisticServable(ServableModel):
    """Online LR scoring from the coefficient-history artifact.  The
    reference never had an LR scoring job (coefficients went to generic
    chombo tooling), so the response format is this port's own:
    ``<request row>,<0|1>,<probability .6f>``."""

    family = "logistic"

    def __init__(self, weights: np.ndarray, encoder: DatasetEncoder,
                 delim: str = ",", threshold: float = 0.5):
        import jax.numpy as jnp

        super().__init__()
        self.enc = encoder
        self.delim = delim
        self.threshold = threshold
        self.weights = jnp.asarray(np.asarray(weights), jnp.float32)
        self._encode = _LineEncoder(encoder, delim)

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "LogisticServable":
        from avenir_tpu.models import logistic as mlr

        path = conf.get("coeff.file.path")
        if not path:
            raise ConfigError("serving logistic requires coeff.file.path")
        model = mlr.LogisticRegressionModel.from_history_lines(
            read_lines(path), delim=conf.field_delim)
        return cls(model.weights, _complete_encoder(conf),
                   delim=conf.field_delim,
                   threshold=conf.get_float("decision.threshold", 0.5))

    def _design(self, ds: EncodedDataset) -> np.ndarray:
        from avenir_tpu.models import logistic as mlr

        x = mlr.design_matrix(ds)
        if x.shape[1] != self.weights.shape[0]:
            raise ConfigError(
                f"design width {x.shape[1]} != coefficient count "
                f"{self.weights.shape[0]} — the schema does not match the "
                f"one the coefficients were trained under")
        return x

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        from avenir_tpu.models import logistic as mlr

        x = self._design(self._encode(lines, len(lines)))
        x = np.pad(x, ((0, pad_to - x.shape[0]), (0, 0)))
        self.compile_keys.add((pad_to,))
        probs, pred = mlr.predict_batch(self.weights, x,
                                        threshold=self.threshold)
        return [f"{line}{self.delim}{int(pred[i])}{self.delim}{probs[i]:.6f}"
                for i, line in enumerate(lines)]

    def warmup(self, pad_to: int) -> None:
        from avenir_tpu.models import logistic as mlr

        self.compile_keys.add((pad_to,))
        mlr.predict_batch(self.weights,
                          np.zeros((pad_to, int(self.weights.shape[0])),
                                   np.float32),
                          threshold=self.threshold)


# ---------------------------------------------------------------------------
# decision tree
# ---------------------------------------------------------------------------

class TreeServable(ServableModel):
    """DecisionTreeBuilder's scoring mode online: the saved JSON model (with
    its embedded train-time encoder state) drives the jitted node walker;
    response line = ``<fields...>,<predictedClass>`` exactly as
    jobs/tree.py::_predict writes it."""

    family = "tree"

    def __init__(self, model, encoder: DatasetEncoder, delim: str = ","):
        from avenir_tpu.models import tree as dtree

        super().__init__()
        self.model = model
        self.enc = encoder
        self.delim = delim
        self.walk = dtree.predict_fn(model)   # holds device-resident tables
        # the walker's arrays pad to pow-2 depth/node/segment buckets and
        # the compiled program keys on those SHAPES (models/tree.py::
        # _tree_walk), so the compile key carries the bucket signature:
        # a hot-swap onto a retrained tree inside the same buckets is
        # provably recompile-free (the monitor sees no fresh key and the
        # walker's jit cache is reused), while a bucket change is counted
        self._shape_sig = dtree.predict_shape_signature(model)

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "TreeServable":
        import json

        from avenir_tpu.models import tree as dtree

        path = conf.get("tree.model.file.path")
        if not path:
            raise ConfigError("serving tree requires tree.model.file.path")
        model_lines = read_lines(path)
        model = dtree.DecisionTreeModel.from_string(model_lines[0])
        enc = Job.encoder_for(conf)
        if len(model_lines) > 1:
            enc.load_state_dict(json.loads(model_lines[1])["encoder"])
        elif not (enc.schema_complete(with_labels=False) and enc.class_values):
            raise ConfigError(
                "tree model file has no encoder-state line and the schema "
                "does not fully specify the encoding — re-train with this "
                "version to embed encoder state")
        return cls(model, enc, delim=conf.field_delim)

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        import jax.numpy as jnp

        rows = _parse_rows(lines, self.delim, self.enc.max_ordinal(False))
        ds = _pad_ds(self.enc.transform(rows, with_labels=False), pad_to)
        self.compile_keys.add((pad_to,) + self._shape_sig)
        pred, _distr = self.walk(jnp.asarray(ds.codes))
        pred = np.asarray(pred)
        return [self.delim.join(list(r) + [self.model.class_values[int(p)]])
                for r, p in zip(rows, pred[:len(lines)])]

    def warmup(self, pad_to: int) -> None:
        import jax.numpy as jnp

        self.compile_keys.add((pad_to,) + self._shape_sig)
        self.walk(jnp.asarray(_blank_ds(self.enc, pad_to).codes))


# ---------------------------------------------------------------------------
# k nearest neighbors
# ---------------------------------------------------------------------------

class KNNServable(ServableModel):
    """NearestNeighbor classification online: the reference set is uploaded
    once (KNNModel caches its device tiles across queries), requests score
    through the same tiled top-k + kernel-weighted vote the batch job runs;
    response line = ``<request row>,<predictedClass>``.  Regression mode
    stays batch-only (it needs per-call input-variable columns)."""

    family = "knn"

    def __init__(self, est, model, encoder: DatasetEncoder, delim: str = ","):
        from avenir_tpu.ops import pallas_knn

        super().__init__()
        # the kernel's own fill targets: the largest query tile that still
        # costs one read of the index, and its largest tile (PERF.md §5)
        self.tile_rows = pallas_knn.FILL_TILES
        self.est = est
        self.model = model
        self.enc = encoder
        self.delim = delim
        self._encode = _LineEncoder(encoder, delim)

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "KNNServable":
        from avenir_tpu.jobs.bayesian import _cost_matrix
        from avenir_tpu.models import knn as mknn
        from avenir_tpu.models import naive_bayes as nb

        train_path = conf.get("training.data.path")
        if not train_path:
            raise ConfigError("serving knn requires training.data.path")
        enc, train_ds, _rows = Job.encode_input(conf, train_path,
                                                need_rows=False)
        class_cond = (conf.get_bool("class.condition.weighted", False)
                      or conf.get_bool("class.condtion.weighted", False))
        class_probs = None
        if class_cond:
            model_path = conf.get("bayesian.model.file.path")
            if not model_path:
                raise ConfigError("class-conditional weighting requires "
                                  "bayesian.model.file.path")
            bayes = nb.model_from_lines(read_lines(model_path), enc,
                                        delim=conf.field_delim)
            class_probs = nb.NaiveBayes().predict(bayes, train_ds).probs
        cost = (_cost_matrix(conf, train_ds.class_values)
                if conf.get_bool("use.cost.based.classifier") else None)
        est = mknn.KNN(
            k=conf.get_int("top.match.count", 10),
            kernel=conf.get("kernel.function", "none"),
            kernel_sigma=conf.get_float("kernel.param", 0.3),
            inverse_distance=conf.get_bool("inverse.distance.weighted", False),
            class_cond_weighting=class_cond,
            decision_threshold=conf.get_float("decision.threshold"),
            pos_class=conf.get("positive.class.value"),
            cost=cost,
            search_mode=conf.get("knn.search.mode", "exact"),
            mesh=Job.auto_mesh(conf),      # the batch job's own placement
        )
        model = est.fit(train_ds, class_probs=class_probs)
        return cls(est, model, enc, delim=conf.field_delim)

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        # the work is a call of its own so that its locals (the encoded
        # batch) are freed inside the span too
        with tel.tracer().span("servable.score",
                               {"rows": len(lines), "pad_to": pad_to}):
            return self._score(lines, pad_to)

    def _score(self, lines: Sequence[str], pad_to: int) -> List[str]:
        ds = self._encode(lines, pad_to)
        self.compile_keys.add((pad_to,))
        result = self.est.predict(self.model, ds)
        if result.refused:
            # the exact scan that answers refused rows is one compiled
            # program per COUNT of them: a count first met on the hot path
            # is a recompile the batcher's monitor has to see.  The count
            # is this call's own (``KNNResult.refused``): the model's
            # counters also move with the other dispatch in flight
            self.compile_keys.add(("fallback", result.refused))
        with tel.tracer().span("servable.format"):
            return [
                f"{line}{self.delim}"
                f"{self.model.class_values[int(result.predicted[i])]}"
                for i, line in enumerate(lines)]

    def warmup(self, pad_to: int) -> None:
        self.compile_keys.add((pad_to,))
        self.est.predict(self.model, _blank_ds(self.enc, pad_to))


# ---------------------------------------------------------------------------
# Markov / Viterbi
# ---------------------------------------------------------------------------

class ViterbiServable(ServableModel):
    """ViterbiStatePredictor online: request rows are ``id[,...],obs,...``
    sequences (``skip.field.count`` leading id fields), decoded against a
    FIXED time axis (``serve.sequence.pad.len``) so every bucket compiles
    one [bucket, padLen] program — padded steps are max-plus identities, so
    paths are byte-identical to the batch job's variable-length decode.
    Response line matches the job: ``id,state,...`` (or ``obs:state`` pairs
    under ``output.state.only=false``)."""

    family = "viterbi"

    def __init__(self, predictor, delim: str = ",", in_delim: str = ",",
                 skip: int = 1, pad_len: int = 64):
        super().__init__()
        self.predictor = predictor
        self.delim = delim
        self.in_delim = in_delim          # the job's field.delim.regex split
        self.skip = max(int(skip), 1)
        self.pad_len = int(pad_len)
        self._known = set(predictor.decoder.model.observations)

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "ViterbiServable":
        from avenir_tpu.models import markov as mk

        path = (conf.get("hmm.model.file.path")
                or conf.get("model.file.path"))
        if not path:
            raise ConfigError("serving viterbi requires hmm.model.file.path")
        model = mk.HMMModel.from_lines(read_lines(path),
                                       delim=conf.field_delim)
        predictor = mk.ViterbiStatePredictor(
            model, mesh=Job.auto_mesh(conf),
            pair_output=not conf.get_bool("output.state.only", True),
            delim=conf.field_delim)
        return cls(predictor, delim=conf.field_delim,
                   in_delim=conf.field_delim_regex,
                   skip=conf.get_int("skip.field.count", 1),
                   pad_len=conf.get_int("serve.sequence.pad.len", 64))

    def _rows(self, lines: Sequence[str]) -> List[List[str]]:
        rows = []
        for line in lines:
            parts = line.split(self.in_delim)
            if len(parts) <= self.skip:
                raise RequestError(
                    f"sequence row needs at least {self.skip + 1} fields "
                    f"(ids + one observation): {line!r}")
            seq = [t for t in parts[self.skip:] if t != ""]
            if len(seq) > self.pad_len:
                raise RequestError(
                    f"sequence of {len(seq)} observations exceeds "
                    f"serve.sequence.pad.len={self.pad_len}")
            unknown = [t for t in seq if t not in self._known]
            if unknown:
                raise RequestError(
                    f"unknown observation symbol(s) {unknown[:3]} — model "
                    f"vocabulary has {len(self._known)} symbols")
            rows.append([self.delim.join(parts[:self.skip])] + seq)
        return rows

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        rows = self._rows(lines)
        rows += [[""] for _ in range(pad_to - len(rows))]   # empty-seq pads
        self.compile_keys.add((pad_to, self.pad_len))
        return self.predictor.predict_lines(rows,
                                            pad_to=self.pad_len)[:len(lines)]

    def warmup(self, pad_to: int) -> None:
        self.compile_keys.add((pad_to, self.pad_len))
        self.predictor.predict_lines([[""] for _ in range(pad_to)],
                                     pad_to=self.pad_len)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

FAMILIES: Dict[str, type] = {
    cls.family: cls
    for cls in (NaiveBayesServable, LogisticServable, TreeServable,
                KNNServable, ViterbiServable)
}


class ModelRegistry:
    """name → :class:`ServableModel`; the scoring plane's model namespace.

    Entries are VERSIONED: :meth:`swap` atomically replaces a loaded entry
    with a freshly built one (the drift→retrain→hot-swap seam,
    ``stream/controller.py``) and bumps the model's version.  ``get`` hands
    out the entry object itself, so a dispatch that already resolved the
    old entry finishes scoring on the old params while every later ``get``
    sees the new ones — zero-downtime swap with no request ever observing
    half a model.  Use :meth:`~avenir_tpu.serving.batcher.BucketedMicrobatcher.swap`
    rather than calling this directly under a live batcher: the batcher
    warms the incoming entry's bucket shapes BEFORE publishing it (the
    swap barrier), so the zero-steady-state-recompiles invariant survives
    the swap."""

    def __init__(self) -> None:
        import threading

        self._entries: Dict[str, ServableModel] = {}
        self._versions: Dict[str, int] = {}
        self._lock = threading.Lock()

    def add(self, name: str, entry: ServableModel) -> "ModelRegistry":
        with self._lock:
            self._entries[name] = entry
            self._versions[name] = self._versions.get(name, 0) + 1
        return self

    def get(self, name: str) -> ServableModel:
        entry = self._entries.get(name)
        if entry is None:
            from avenir_tpu.serving.errors import UnknownModelError
            raise UnknownModelError(
                f"unknown model {name!r}; loaded: {sorted(self._entries)}")
        return entry

    def swap(self, name: str, entry: ServableModel) -> int:
        """Atomically replace a LOADED entry; returns the new version.
        Swapping an unknown name raises (publish new models with ``add`` —
        a swap that silently creates a model would hide a routing typo)."""
        from avenir_tpu.serving.errors import UnknownModelError

        with self._lock:
            if name not in self._entries:
                raise UnknownModelError(
                    f"cannot swap unknown model {name!r}; loaded: "
                    f"{sorted(self._entries)}")
            self._entries[name] = entry
            self._versions[name] += 1
            return self._versions[name]

    def version(self, name: str) -> int:
        """The entry's version (1 = initial load, +1 per swap)."""
        self.get(name)                    # raises UnknownModelError
        return self._versions[name]

    def names(self) -> List[str]:
        return sorted(self._entries)

    def items(self):
        return sorted(self._entries.items())

    @classmethod
    def from_conf(cls, conf: JobConfig) -> "ModelRegistry":
        """Load every family named in ``serve.models`` from its job-contract
        artifact keys (one entry per family, named by the family id)."""
        families = conf.get_list("serve.models")
        if not families:
            raise ConfigError(
                f"serve.models not set — name the families to load "
                f"(known: {sorted(FAMILIES)})")
        registry = cls()
        for family in families:
            loader = FAMILIES.get(family)
            if loader is None:
                raise ConfigError(
                    f"unknown serving family {family!r} in serve.models "
                    f"(known: {sorted(FAMILIES)})")
            registry.add(family, loader.from_conf(conf))
        return registry

    def warmup(self, buckets: Sequence[int]) -> Dict[str, int]:
        """Compile every (model, bucket) shape up front; returns the number
        of shapes warmed per model — after this, steady-state serving must
        record zero recompiles."""
        warmed = {}
        for name, entry in self.items():
            before = len(entry.compile_keys)
            for bucket in buckets:
                entry.warmup(int(bucket))
            warmed[name] = len(entry.compile_keys) - before
        return warmed
