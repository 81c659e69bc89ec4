"""Tracing/profiling hooks — the observability layer the reference lacks.

The reference's only observability is the Hadoop job UI plus custom counters
(SURVEY §5). Here: a :func:`trace` context manager around ``jax.profiler``
(viewable in TensorBoard/XProf), a :class:`StepTimer` for per-step
wall-times with percentile summaries (blocking on device results so times
are real), and ``debug.on``-gated logging matching the reference's per-job
debug flag.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Dict, List, Optional

import jax
import numpy as np


def device_sync(value):
    """Reliable device barrier: fetch one scalar PER SHARD of ``value``.

    Timing code forces a host read of the result: the barrier holds on
    any backend (whether ``jax.block_until_ready`` alone blocks on today's
    chip is what ``chip_smoke.py`` times and prints). One scalar is read
    from every addressable shard — fetching only element 0 would wait for the
    device holding shard 0 while the rest of a sharded result is still
    computing (and a global multi-host array is not eagerly indexable at
    all). Works on any pytree of arrays; returns ``value`` unchanged."""
    leaves = [x for x in jax.tree_util.tree_leaves(value)
              if hasattr(x, "dtype") and getattr(x, "size", 0)]
    for x in leaves:
        shards = getattr(x, "addressable_shards", None)
        if shards:
            for sh in shards:
                d = sh.data
                if getattr(d, "size", 0):
                    # this helper IS the blessed sync point the GL005 rule
                    # steers hot loops toward — one scalar per shard, by
                    # design            # graftlint: disable=GL005
                    np.asarray(jax.device_get(d.ravel()[0] if d.ndim else d))
        else:
            # graftlint: disable=GL005 — same: the sync helper itself
            np.asarray(jax.device_get(x.ravel()[0] if x.ndim else x))
    return value


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Capture an XLA/device trace under ``log_dir`` (no-op when None)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock step timing with device synchronization.

    Register the step's device output via :meth:`block_on` — JAX dispatch is
    async, so without the block the recorded time would measure only dispatch
    latency, not the step::

        timer = StepTimer()
        with timer.step("fit") as t:
            out = t.block_on(step_fn(batch))   # synced at step exit
        timer.summary()["fit"]["p50_ms"]
    """

    def __init__(self):
        self.samples: Dict[str, List[float]] = {}
        self._pending = None

    @contextlib.contextmanager
    def step(self, name: str):
        start = time.perf_counter()
        self._pending = None
        yield self
        if self._pending is not None:
            device_sync(self._pending)     # a host fetch, not
            # block_until_ready: the latter is a no-op on some transports
            self._pending = None
        self.samples.setdefault(name, []).append(
            (time.perf_counter() - start) * 1e3)

    def block_on(self, value):
        """Register the step's device output; synced at step exit."""
        self._pending = value
        return value

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-step percentile rows through the ONE shared helper
        (``utils.metrics.percentile_summary``, round 14) — StepTimer,
        bench probes and serving latency now agree on the percentile
        definition by construction, and StepTimer gains p99."""
        from avenir_tpu.utils.metrics import percentile_summary

        return {name: percentile_summary(ms)
                for name, ms in self.samples.items()}


def get_logger(name: str = "avenir_tpu", debug_on: bool = False) -> logging.Logger:
    """Per-job logger honoring the reference's ``debug.on`` flag
    (e.g. CramerCorrelation.java:106-109)."""
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(name)s %(levelname)s %(message)s"))
        logger.addHandler(handler)
    logger.setLevel(logging.DEBUG if debug_on else logging.INFO)
    return logger
