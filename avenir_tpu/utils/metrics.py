"""Validation metrics, arbitration, counters, and latency tracking.

Replaces the reference's validation-mode machinery: the binary confusion
matrix with ×100 integer accuracy/recall/precision published as Hadoop
counters (util/ConfusionMatrix.java:34-77, consumed at
bayesian/BayesianPredictor.java:170-180 and knn/NearestNeighbor.java:300-312),
the misclassification-cost arbitrator (util/CostBasedArbitrator.java:35-45),
and the Hadoop counter channel itself (here a plain named-counter object
returned alongside results).

:class:`LatencyTracker` + :func:`serving_stats` are the shared observability
schema of BOTH online paths — the scoring plane (``serving/batcher.py``) and
the RL serving loop (``pipeline/streaming.py``) — so their health endpoints
and benchmark artifacts report identically.
"""

from __future__ import annotations

import threading

from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile_of(values, q: float) -> float:
    """THE percentile definition every surface uses — numpy's linear
    interpolation over the given samples (round 14): ``LatencyTracker``,
    ``StepTimer`` and the bench probes all route through here, so bench,
    profile and serving percentiles agree by construction instead of by
    three copies of the same formula drifting apart."""
    arr = np.asarray(values, np.float64)
    if arr.size == 0:
        return 0.0
    return float(np.percentile(arr, q))


def percentile_summary(samples_ms,
                       percentiles=(50.0, 95.0, 99.0)) -> Dict[str, float]:
    """The shared wall-time summary shape: ``count``, ``mean_ms``,
    ``p50_ms``/``p95_ms``/``p99_ms`` (configurable), ``max_ms`` — the one
    helper behind ``StepTimer.summary`` and any probe that reports
    percentile rows."""
    arr = np.asarray(list(samples_ms), np.float64)
    out: Dict[str, float] = {"count": int(arr.size)}
    if not arr.size:
        out["mean_ms"] = out["max_ms"] = 0.0
        for q in percentiles:
            out[f"p{q:g}_ms"] = 0.0
        return out
    out["mean_ms"] = float(arr.mean())
    for q in percentiles:
        out[f"p{q:g}_ms"] = percentile_of(arr, q)
    out["max_ms"] = float(arr.max())
    return out


class Counters:
    """Named counters — the in-process stand-in for Hadoop job counters.

    Increment is a read-modify-write, and one Counters may be shared across
    serving threads (frontend handlers, fleet workers aggregating into one
    report), so mutations take a lock — the Hadoop counter channel was
    task-concurrent too.
    """

    def __init__(self):
        self._groups: Dict[str, Dict[str, int]] = {}
        self._lock = threading.Lock()

    def increment(self, group: str, name: str, amount: int = 1) -> None:
        with self._lock:
            g = self._groups.setdefault(group, {})
            g[name] = g.get(name, 0) + amount

    def set(self, group: str, name: str, value: int) -> None:
        with self._lock:
            self._groups.setdefault(group, {})[name] = int(value)

    def get(self, group: str, name: str) -> int:
        with self._lock:
            return self._groups.get(group, {}).get(name, 0)

    def as_dict(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {g: dict(d) for g, d in self._groups.items()}

    def merge(self, other: "Counters") -> "Counters":
        """Adopt every counter from ``other`` (overwriting same-named ones)
        — the "latest snapshot wins" semantics for republishing one source's
        counters (e.g. a job adopting its batcher's final totals).  For
        aggregating MANY sources into one report use :meth:`merge_add`:
        overwrite-merge on same-named counters silently keeps only the last
        contributor's count."""
        for group, vals in other.as_dict().items():
            for name, value in vals.items():
                self.set(group, name, value)
        return self

    def merge_add(self, other: "Counters") -> "Counters":
        """SUM every counter from ``other`` into this one — the
        fleet/run-level aggregation semantics (Hadoop's counter merge):
        per-stage or per-worker Counters folded into one rollup keep every
        contributor's counts instead of last-writer-wins."""
        for group, vals in other.as_dict().items():
            for name, value in vals.items():
                self.increment(group, name, value)
        return self

    def __repr__(self) -> str:
        lines = []
        for g in sorted(self._groups):
            for n in sorted(self._groups[g]):
                lines.append(f"{g}::{n} = {self._groups[g][n]}")
        return "\n".join(lines)


class LatencyTracker:
    """Per-request latency percentiles over a bounded ring of recent samples.

    A ring (default 8192 samples) rather than an unbounded list: a serving
    loop alive for days must not grow host memory per request, and recent
    samples are what a health endpoint should describe.  Thread-safe
    (requests complete on dispatch/worker threads while a frontend thread
    reads the percentiles).
    """

    def __init__(self, capacity: int = 8192):
        self._buf = np.zeros(max(int(capacity), 1), np.float64)
        self._next = 0
        self._filled = 0
        self.count = 0                      # total samples ever recorded
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        with self._lock:
            self._buf[self._next] = seconds
            self._next = (self._next + 1) % len(self._buf)
            self._filled = min(self._filled + 1, len(self._buf))
            self.count += 1

    def record_many(self, seconds: Sequence[float]) -> None:
        """``record`` of each of ``seconds`` in order, under one lock and
        as one slice assignment (two where the ring wraps): what a serving
        dispatch records of its requests, once."""
        vals = np.asarray(seconds, np.float64)
        n = len(vals)
        with self._lock:
            cap = len(self._buf)
            # samples that later ones of the same call would overwrite
            skip = max(n - cap, 0)
            vals = vals[skip:]
            at = (self._next + skip) % cap
            head = min(len(vals), cap - at)
            self._buf[at:at + head] = vals[:head]
            self._buf[:len(vals) - head] = vals[head:]
            self._next = (self._next + n) % cap
            self._filled = min(self._filled + n, cap)
            self.count += n

    def percentile(self, q: float) -> float:
        """q-th percentile in seconds over the retained window (0.0 when
        no sample was recorded yet)."""
        with self._lock:
            if not self._filled:
                return 0.0
            return percentile_of(self._buf[:self._filled], q)

    @property
    def p50_ms(self) -> float:
        return self.percentile(50.0) * 1e3

    @property
    def p99_ms(self) -> float:
        return self.percentile(99.0) * 1e3

    def snapshot(self) -> Dict[str, float]:
        return {"p50_ms": round(self.p50_ms, 4),
                "p99_ms": round(self.p99_ms, 4),
                "latency_samples": self.count}


def serving_stats(counters: "Counters",
                  latency: Dict[str, LatencyTracker],
                  identity: Optional[Dict[str, str]] = None
                  ) -> Dict[str, dict]:
    """The one stats schema both online paths publish: per served model,
    the ``Serving.<name>`` counter group merged with its latency
    percentiles.  Counter names inside the group: ``requests``, ``batches``,
    ``shed``, ``timeouts``, ``errors``, ``recompiles`` and the batched-size
    histogram ``bucket.<n>`` (the RL loop, which dispatches one event at a
    time, reports everything under ``bucket.1``).

    Covers the UNION of the latency trackers and the ``Serving.<name>``
    counter groups: a model that has counters but no tracker yet (e.g.
    registered and shedding before its first scored request, or a fleet
    rollup that only carried counters) reports with zeroed latency instead
    of silently vanishing from the stats.

    ``identity`` (GraftFleet round 15 —
    ``telemetry.export.fleet_identity``: process index + replica suffix)
    merges into every row, so stats federated from N workers of one
    deployment never collide on identical model names."""
    groups = counters.as_dict()
    prefix = "Serving."
    names = set(latency) | {g[len(prefix):] for g in groups
                            if g.startswith(prefix)}
    out: Dict[str, dict] = {}
    for name in sorted(names):
        stats = dict(groups.get(f"Serving.{name}", {}))
        tracker = latency.get(name)
        stats.update(tracker.snapshot() if tracker is not None else
                     {"p50_ms": 0.0, "p99_ms": 0.0, "latency_samples": 0})
        if identity:
            stats.update(identity)
        out[name] = stats
    return out


class ConfusionMatrix:
    """Multi-class confusion counts with the reference's binary metrics.

    The reference's version is strictly binary (pos/neg class values); this
    one keeps full multi-class counts and exposes the binary metrics when a
    positive class is designated.
    """

    def __init__(self, class_values: Sequence[str], pos_class: Optional[str] = None):
        self.class_values = list(class_values)
        self.pos_class = pos_class if pos_class is not None else (self.class_values[0] if self.class_values else None)
        k = len(self.class_values)
        self.matrix = np.zeros((k, k), dtype=np.int64)   # [actual, predicted]

    def add(self, actual: int, predicted: int, count: int = 1) -> None:
        self.matrix[actual, predicted] += count

    def add_batch(self, actual: np.ndarray, predicted: np.ndarray) -> None:
        k = len(self.class_values)
        idx = actual.astype(np.int64) * k + predicted.astype(np.int64)
        self.matrix += np.bincount(idx, minlength=k * k).reshape(k, k)

    # -- binary metrics (×100 ints to mirror the reference's counter values) --
    def _binary(self):
        p = self.class_values.index(self.pos_class)
        tp = int(self.matrix[p, p])
        fn = int(self.matrix[p, :].sum() - tp)
        fp = int(self.matrix[:, p].sum() - tp)
        tn = int(self.matrix.sum() - tp - fn - fp)
        return tp, fp, tn, fn

    @property
    def accuracy(self) -> int:
        total = int(self.matrix.sum())
        correct = int(np.trace(self.matrix))
        return (100 * correct) // total if total else 0

    @property
    def recall(self) -> int:
        tp, _, _, fn = self._binary()
        return (100 * tp) // (tp + fn) if tp + fn else 0

    @property
    def precision(self) -> int:
        tp, fp, _, _ = self._binary()
        return (100 * tp) // (tp + fp) if tp + fp else 0

    def publish(self, counters: Counters, group: str = "Validation") -> None:
        counters.set(group, "accuracy", self.accuracy)
        counters.set(group, "recall", self.recall)
        counters.set(group, "precision", self.precision)
        correct = int(np.trace(self.matrix))
        counters.set(group, "correct", correct)
        counters.set(group, "incorrect", int(self.matrix.sum()) - correct)


class CostBasedArbitrator:
    """Expected-misclassification-cost argmin over class posteriors.

    Generalizes the reference's binary version (cost of a false-negative vs
    false-positive, util/CostBasedArbitrator.java:35-45) to a full cost
    matrix: pick argmin_k Σ_c P(c|x) · cost[c, k].
    """

    def __init__(self, class_values: Sequence[str], cost: np.ndarray):
        cost = np.asarray(cost, dtype=np.float64)
        k = len(class_values)
        if cost.shape == (k,):
            # reference-style per-class misclassification cost: cost[c] applies
            # when the true class c is predicted as anything else
            full = np.tile(cost[:, None], (1, k))
            np.fill_diagonal(full, 0.0)
            cost = full
        if cost.shape != (k, k):
            raise ValueError(f"cost must be [{k}] or [{k},{k}], got {cost.shape}")
        self.class_values = list(class_values)
        self.cost = cost

    def arbitrate(self, probs: np.ndarray) -> np.ndarray:
        """probs [N, C] → predicted class index [N] minimizing expected cost."""
        expected = probs @ self.cost                     # [N, K]
        return np.argmin(expected, axis=-1).astype(np.int32)
