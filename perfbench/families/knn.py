"""The kNN deployment as the benchmark drives it: the one file of this family
that imports the program.

Set-up builds what ``KNNServable.from_conf`` builds (schema → encoder →
``KNN.fit`` → ``KNNServable``; with class-conditional weighting the posteriors
come from the program's own ``NaiveBayes().fit/predict``), but from the
seed-made reference arrays instead of 2^24 lines of CSV.  The window then
drives the program's own entries — ``KNNServable.score_lines`` or
``BucketedMicrobatcher.submit`` — through thin wrappers of this file that
put a span around each call and keep what the call computed, so that the
comparison after the window reads what the timed path itself produced.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from avenir_tpu.core.encoding import DatasetEncoder, EncodedDataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.models import knn as mknn
from avenir_tpu.models import naive_bayes as nb
from avenir_tpu.serving.batcher import BucketedMicrobatcher
from avenir_tpu.serving.registry import (KNNServable, ModelRegistry,
                                         ServableModel)

from lib import data, knn_reference

MODEL = "knn"
# the spans this file puts around the program's calls, outermost first
SPAN_NAMES = ("score_lines", "encode.transform", "knn.predict")
NB_CHUNK_ROWS = 1 << 22      # the job streams its input in chunks this size
# The fused search sends each row that fails its exactness certificate to the
# exact XLA scan: one program per NUMBER of refused rows in a call, and a
# second resident copy of the references on first use.  About one row in
# 40 000 is refused at this size (my chip runs, PR 26), so a window meets
# calls with one refused row, seldom two: those shapes are the cell's own and
# are warmed with the rest.
FALLBACK_ROWS = (1, 2, 3)


class _SpannedKNN(mknn.KNN):
    """``KNN.predict`` under a span, its result kept for the caller."""

    last = threading.local()

    def predict(self, model, test, validate=False):
        with jax.profiler.TraceAnnotation("knn.predict"):
            result = super().predict(model, test, validate=validate)
        self.last.result = result
        return result


class _SpannedEncoder:
    """The encoder with a span around ``transform``; everything else is the
    encoder's own."""

    def __init__(self, enc: DatasetEncoder):
        self._enc = enc

    def __getattr__(self, name):
        return getattr(self._enc, name)

    def transform(self, rows, with_labels=True):
        with jax.profiler.TraceAnnotation("encode.transform"):
            return self._enc.transform(rows, with_labels=with_labels)


class _SpannedServable(ServableModel):
    """``KNNServable.score_lines`` under a span.  Each call leaves a record
    (host clock, the lines, and the ``KNNResult`` the call computed)."""

    family = "knn"

    def __init__(self, inner: KNNServable, est: _SpannedKNN):
        super().__init__()
        self.inner, self.est = inner, est
        self.compile_keys = inner.compile_keys
        self.calls: List[Dict] = []
        self.keep = False

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("score_lines"):
            out = self.inner.score_lines(lines, pad_to)
        t1 = time.perf_counter()
        if self.keep:
            self.calls.append({"t0": t0, "t1": t1, "lines": list(lines),
                               "pad_to": pad_to,
                               "result": self.est.last.result})
        return out

    def warmup(self, pad_to: int) -> None:
        self.inner.warmup(pad_to)


def _phase(name: str, since: float) -> float:
    """Set-up phases on standard error, for whoever reads where it goes."""
    now = time.perf_counter()
    print(f"perfbench set-up: {name} {now - since:.2f} s", file=sys.stderr)
    return now


class System:
    def __init__(self, config: Dict, seed: int, refs: Optional[int] = None):
        at = time.perf_counter()
        st = config["settings"]
        n = int(refs if refs is not None else config["refs"])
        self.config, self.settings, self.seed = config, st, seed
        self.cont, self.labels = data.make_refs(n, seed)
        at = _phase(f"references from the seed ({n} rows)", at)
        schema = FeatureSchema.from_json(config["schema"])
        enc = DatasetEncoder(schema)
        self.class_values = list(enc.class_values)
        self.ordinals = [f.ordinal for f in enc.cont_fields]
        ds = EncodedDataset(
            codes=np.zeros((n, 0), np.int32), cont=self.cont,
            labels=self.labels, n_bins=np.zeros(0, np.int32),
            class_values=list(enc.class_values), binned_ordinals=[],
            cont_ordinals=list(self.ordinals))
        self.class_cond = bool(st.get("class.condtion.weighted", False))
        class_probs = None
        if self.class_cond:
            chunks = [ds.slice(s, s + NB_CHUNK_ROWS)
                      for s in range(0, n, NB_CHUNK_ROWS)]
            bayes = nb.NaiveBayes().fit(iter(chunks))
            class_probs = np.concatenate(
                [nb.NaiveBayes().predict(bayes, c).probs for c in chunks])
            at = _phase("Naive-Bayes posteriors of the references", at)
        self._ds = ds
        self.est = _SpannedKNN(
            k=int(st["top.match.count"]), kernel=st["kernel.function"],
            kernel_sigma=float(st.get("kernel.param", 0.3)),
            class_cond_weighting=self.class_cond,
            search_mode=st.get("knn.search.mode", "exact"))
        self.model = self.est.fit(ds, class_probs=class_probs)
        self.servable = _SpannedServable(
            KNNServable(self.est, self.model, _SpannedEncoder(enc)), self.est)
        self.batcher: Optional[BucketedMicrobatcher] = None
        self._base: Dict[str, float] = {}

    def query_pool(self, n: int) -> List[str]:
        """``n`` request rows as CSV text, from a stream of the seed that the
        references do not use."""
        return data.make_query_lines(n, self.seed)

    def _warm_fallback(self) -> None:
        """Run the exact scan the certificate falls back to, through the
        program's documented switch for it, at the row counts a window meets."""
        at = time.perf_counter()
        mknn.USE_PALLAS = False
        try:
            for rows in FALLBACK_ROWS:
                mknn.nearest_neighbors(self.model, self._ds.slice(0, rows),
                                       self.est.k, self.est.metric)
        finally:
            mknn.USE_PALLAS = True
        _phase("exact-scan fallback shapes", at)

    # -- entries -------------------------------------------------------------
    def open(self, traffic: Dict) -> Callable[[Sequence[str]], List[str]]:
        """Warm the shapes this traffic uses (and no others) and return its
        entry: lines of one request in, its reply lines out."""
        entry = traffic["entry"]
        if entry == "score_lines":
            pad_to = int(traffic["rows_per_request"])
            at = time.perf_counter()
            self.servable.warmup(pad_to)
            at = _phase("pack + upload of the index, first warm block", at)
            self.servable.warmup(pad_to)
            _phase("second warm block", at)
            self._warm_fallback()
            return lambda lines: self.servable.score_lines(lines, pad_to)
        if entry == "batcher_submit":
            b = traffic["batcher"]
            registry = ModelRegistry().add(MODEL, self.servable)
            at = time.perf_counter()
            self.servable.warmup(int(b["serve.bucket.sizes"][0]))
            at = _phase("pack + upload of the index, first warm bucket", at)
            self.batcher = BucketedMicrobatcher(
                registry, bucket_sizes=b["serve.bucket.sizes"],
                flush_deadline_ms=b["serve.flush.deadline.ms"],
                queue_depth=b["serve.queue.depth"],
                request_timeout_ms=b["serve.request.timeout.ms"], warmup=True)
            _phase("batcher warm-up of its buckets", at)
            self._warm_fallback()
            submit = self.batcher.submit_nowait
            return lambda lines: [r.wait(30.0) for r in
                                  [submit(MODEL, ln) for ln in lines]]
        raise ValueError(f"family knn has no entry {entry!r}")

    # -- counters and spans ----------------------------------------------------
    def _counters(self) -> Dict[str, float]:
        m = self.model
        out = {"fused_rows": m.fused_rows, "tourney_rows": m.tourney_rows,
               "cert_fallback_rows": m.cert_fallback_rows}
        if self.batcher is not None:
            grp = self.batcher.counters.as_dict().get(f"Serving.{MODEL}", {})
            out.update({f"serving.{k}": v for k, v in grp.items()})
        return {k: float(v) for k, v in out.items()}

    def start_window(self) -> None:
        self._base = self._counters()
        self.servable.calls.clear()
        self.servable.keep = True

    def end_window(self) -> Dict:
        """Counters counted over the window, and the window's call spans."""
        self.servable.keep = False
        now = self._counters()
        return {"counters": {k: v - self._base.get(k, 0.0)
                             for k, v in now.items()},
                "calls": [{"t0": c["t0"], "t1": c["t1"],
                           "rows": len(c["lines"]), "pad_to": c["pad_to"]}
                          for c in self.servable.calls],
                "attrs": self.cont.shape[1], "refs": self.cont.shape[0],
                "k": self.est.k}

    # -- after the window ------------------------------------------------------
    def produced(self, requests: Sequence[Dict]) -> Dict:
        """What the timed path computed for each sampled request row: the
        neighbours, distances and vote shares of the call that answered it."""
        where: Dict[str, tuple] = {}
        for ci, call in enumerate(self.servable.calls):
            for ri, line in enumerate(call["lines"]):
                where.setdefault(line, (ci, ri))
        dist, idx, shares = [], [], []
        for req in requests:
            ci, ri = where[req["line"]]
            res = self.servable.calls[ci]["result"]
            dist.append(res.neighbor_dist[ri])
            idx.append(res.neighbor_idx[ri])
            shares.append(res.class_scores[ri])
        return {"dist": np.array(dist, np.float64), "idx": np.array(idx),
                "shares": np.array(shares, np.float64)}

    def close(self) -> None:
        """Stop the batcher and free the program's state on the device."""
        if self.batcher is not None:
            self.batcher.close()
            self.batcher = None
        self.servable = self.model = self.est = self._ds = None
        gc.collect()

    def check(self, requests: Sequence[Dict], produced: Dict,
              precision: str = "f32") -> Dict[str, float]:
        ref = knn_reference.Reference(self.cont, self.labels)
        return knn_reference.compare(
            ref, self.settings, [r["line"] for r in requests],
            [r["reply"] for r in requests], produced["dist"], produced["idx"],
            produced["shares"], self.class_values, self.ordinals,
            float(self.config["limits"]["share_gap"]), precision=precision)
