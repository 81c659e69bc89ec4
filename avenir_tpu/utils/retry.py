"""Failure detection and elastic retry — the task-retry layer.

The reference delegates failure handling wholly to its cluster runtimes:
Hadoop re-runs a failed map/reduce task on its input split up to
``mapred.map.max.attempts`` times (resource/knn.properties:5-6 sets 2), and
Storm optionally replays failed messages (``replay.failed.message`` —
resource/boost_lead_generation_tutorial.txt:27; the spout's failed-message
hook is stubbed at RedisSpout.java:103-106). There is no fault injection
anywhere in the reference (SURVEY.md §5).

Here the equivalent unit of work is a *chunk step* — one encoded chunk
through a jitted aggregation kernel — so task retry becomes chunk retry:
chunks are materialized values and every chunk step is a pure function of
its chunk, so re-running a failed step is idempotent by construction (the
framework's accumulate-per-chunk-then-merge discipline; contrast the
reference's only unsafe spot, the single-reducer LR coefficient-file
rewrite, SURVEY.md §5 "race detection").

:class:`FaultInjector` is the fault-injection capability the reference
lacks: deterministic fault schedules wrap any callable so tests can assert
fault-free results survive injected crashes (tests/test_hardening.py).
"""

from __future__ import annotations

import logging
import random
import threading
import time
from dataclasses import dataclass, field
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple, TypeVar)

from avenir_tpu.utils.metrics import Counters

log = logging.getLogger(__name__)

T = TypeVar("T")
R = TypeVar("R")

# counter names (the observability channel, as Hadoop publishes task retries)
ATTEMPTS = ("Task", "attempts")
FAILURES = ("Task", "failed.attempts")
EXHAUSTED = ("Task", "exhausted")


@dataclass(frozen=True)
class RetryPolicy:
    """Chunk/task retry policy.

    ``max_attempts`` defaults to 2, the reference deployment's
    ``mapred.map.max.attempts`` value. ``backoff_s`` is the sleep before
    each re-attempt (0 for in-process compute retries; nonzero for I/O).
    ``retryable`` filters which exception types are retried — anything else
    propagates immediately (a schema error will not pass on attempt 2).

    ``jitter`` (round 16, default on — ``retry.jitter``): decorrelated
    jitter on the backoff, so N replicas that all failed on one shared
    resource (a checkpoint store, a queue endpoint) re-arrive spread out
    instead of thundering-herding it in lockstep.  Each sleep draws
    uniformly from ``[backoff_s, 3·previous_sleep]``, capped at
    ``backoff_cap_s`` (default 16× base) — the bounds
    :meth:`next_backoff` pins in tests.  Off, the fixed-``backoff_s``
    schedule is exactly the pre-round-16 behavior.
    """

    max_attempts: int = 2
    backoff_s: float = 0.0
    retryable: Tuple[type, ...] = (Exception,)
    non_retryable: Tuple[type, ...] = ()
    jitter: bool = True
    backoff_cap_s: float = 0.0           # 0 = 16 × backoff_s
    # injectable uniform(a, b) draw — tests pin the distribution bounds
    # through it; random.uniform in production
    uniform: Callable[[float, float], float] = random.uniform

    @property
    def cap_s(self) -> float:
        # never below base: an inverted cap (cap < base) would silently
        # break the documented [base, cap] floor
        if self.backoff_cap_s > 0:
            return max(self.backoff_cap_s, self.backoff_s)
        return 16.0 * self.backoff_s

    def next_backoff(self, prev_sleep_s: float) -> float:
        """The sleep before the next attempt given the previous sleep
        (pass 0 before the first retry).  With jitter on:
        ``min(cap, uniform(base, 3·max(prev, base)))`` — the AWS
        "decorrelated jitter" recipe, bounded to ``[base, cap]``."""
        if self.backoff_s <= 0:
            return 0.0
        if not self.jitter:
            return self.backoff_s
        upper = 3.0 * max(prev_sleep_s, self.backoff_s)
        return min(self.cap_s, self.uniform(self.backoff_s, upper))

    @classmethod
    def from_conf(cls, conf) -> "RetryPolicy":
        """Read the reference's property name (``mapred.map.max.attempts``)
        with the framework name ``task.max.attempts`` as an alias.

        Deterministic configuration errors (:class:`ConfigError` — e.g. a
        schema too incomplete for streaming encode) are non-retryable: the
        same attempt would fail the same way, and wrapping the clear error
        in a TaskExhaustedError would bury it."""
        from avenir_tpu.core.config import ConfigError

        attempts = int(conf.get("task.max.attempts",
                                conf.get("mapred.map.max.attempts", 2)))
        backoff = float(conf.get("task.retry.backoff.sec", 0.0))
        return cls(max_attempts=max(attempts, 1), backoff_s=backoff,
                   non_retryable=(ConfigError,),
                   jitter=conf.get_bool("retry.jitter", True),
                   backoff_cap_s=conf.get_float(
                       "task.retry.backoff.cap.sec", 0.0))


class TaskExhaustedError(RuntimeError):
    """A task failed on every attempt; carries the last underlying error."""

    def __init__(self, task: str, attempts: int, last: BaseException):
        super().__init__(
            f"task {task!r} failed after {attempts} attempts: {last!r}")
        self.task = task
        self.attempts = attempts
        self.last = last


def run_with_retry(fn: Callable[[], R], *, policy: RetryPolicy,
                   counters: Optional[Counters] = None,
                   task: str = "task") -> R:
    """Run ``fn`` under the retry policy; raises TaskExhaustedError after the
    final failed attempt. ``fn`` must be safe to re-run (pure, or idempotent
    against external state)."""
    last: Optional[BaseException] = None
    sleep_s = 0.0
    for attempt in range(1, policy.max_attempts + 1):
        if counters is not None:
            counters.increment(*ATTEMPTS)
        try:
            return fn()
        except policy.retryable as e:          # noqa: PERF203 — retry loop
            if isinstance(e, policy.non_retryable):
                raise                          # deterministic: fail fast
            last = e
            if counters is not None:
                counters.increment(*FAILURES)
            log.warning("task %s attempt %d/%d failed: %r",
                        task, attempt, policy.max_attempts, e)
            if attempt < policy.max_attempts and policy.backoff_s > 0:
                sleep_s = policy.next_backoff(sleep_s)
                time.sleep(sleep_s)
    if counters is not None:
        counters.increment(*EXHAUSTED)
    assert last is not None
    raise TaskExhaustedError(task, policy.max_attempts, last)


def process_chunks(chunks: Iterable[T], step: Callable[[T], R], *,
                   policy: Optional[RetryPolicy] = None,
                   counters: Optional[Counters] = None,
                   task: str = "chunk") -> List[R]:
    """Run ``step`` over each chunk with per-chunk retry — the MR task-retry
    analog (a failed map task re-runs on its split; a failed chunk step
    re-runs on its chunk). Returns the per-chunk results in order."""
    policy = policy or RetryPolicy()
    out: List[R] = []
    for i, chunk in enumerate(chunks):
        out.append(run_with_retry(
            lambda c=chunk: step(c), policy=policy, counters=counters,
            task=f"{task}[{i}]"))
    return out


class InjectedFault(RuntimeError):
    """Raised by FaultInjector on scheduled invocations."""


class FaultInjector:
    """Deterministic fault injection for tests and chaos drills.

    Wraps a callable; raises :class:`InjectedFault` on the 1-based
    invocation numbers in ``fail_on`` — the deterministic analog of a flaky
    worker. A single scheduled number models a transient fault (the retry
    then succeeds); consecutive numbers model a persistent fault that
    defeats an N-attempt policy.
    """

    def __init__(self, fn: Callable[..., R], fail_on: Sequence[int],
                 exc: Callable[[], BaseException] = lambda: InjectedFault("injected")):
        self._fn = fn
        self._fail_on = frozenset(fail_on)
        self._exc = exc
        self.calls = 0
        self.faults_fired = 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self.calls in self._fail_on:
            self.faults_fired += 1
            raise self._exc()
        return self._fn(*args, **kwargs)


class FaultPlan:
    """Conf-driven deterministic fault schedule — the ``fault.*`` family
    (round 16): :class:`FaultInjector` generalized from wrap-one-callable
    to named SITES any seam can consult, so a preemption drill arms
    crashes from configuration alone (no test-only wiring).

    - ``fault.fold.crash.after`` — raise on the N-th fold boundary
      (``stream/windows.py::WindowedScan.close_pane``, before the pane's
      state reaches the ring: a mid-fold kill, the preemption shape);
    - ``fault.checkpoint.save.crash.after`` — raise on the N-th snapshot
      save, BEFORE anything is written (the save must stay atomic);
    - ``fault.checkpoint.restore.crash.after`` — raise on the N-th
      restore attempt (a worker preempted while coming back up);
    - ``fault.serve.dispatch.crash.after`` — raise on the N-th serving
      batch dispatch, BEFORE any request of the batch scores (FleetServe
      round 17: the batcher treats it as replica-fatal — the whole
      replica dies mid-batch and the requests of every dispatch in
      flight fail over);
    - ``fault.serve.heartbeat.crash.after`` — wedge the serving
      dispatcher on its N-th loop wake (one at start, one after every
      dispatch): both dispatcher threads exit WITHOUT finishing pending
      work, so the replica's heartbeat goes stale and the pool's deadline
      detection is what has to catch it;
    - ``fault.tenant.flood.after`` — the GraftPool noisy-tenant drill
      (round 18): fire on a tenant workload's N-th pacing boundary.  The
      workload driver (``benchmarks/tenancy_soak.py``) treats the raise
      as "go noisy": it stops pacing and floods the arbiter, which must
      throttle then shed THAT tenant while the others' SLOs stay green —
      misbehavior armed from configuration alone, like every other site.

    Each firing journals a golden-schema'd ``fault.injected`` event
    (site, 1-based hit number) so the run's trace explains the drill.
    Counts are per-plan-instance; build one plan per run seam — a
    replica POOL shares one plan across its replicas, so "kill the N-th
    dispatch" means the N-th dispatch pool-wide (``from_conf`` returns
    None when no ``fault.*`` key is armed — the zero-cost default)."""

    SITES = ("fold", "checkpoint.save", "checkpoint.restore",
             "serve.dispatch", "serve.heartbeat", "tenant.flood")

    def __init__(self, schedule: Dict[str, int]):
        unknown = set(schedule) - set(self.SITES)
        if unknown:
            raise ValueError(f"unknown fault sites {sorted(unknown)}; "
                             f"known: {self.SITES}")
        self.schedule = {site: int(n) for site, n in schedule.items()
                         if int(n) > 0}
        self.hits = {site: 0 for site in self.SITES}
        self.faults_fired = 0
        # a site is passed by several threads (a batcher's two
        # dispatchers, every replica of a pool): the N-th pass is one pass
        self._lock = threading.Lock()

    @classmethod
    def from_conf(cls, conf) -> Optional["FaultPlan"]:
        # literal key reads, one per site: the GL004 registry scans
        # conf.get* literals, so the fault.* family stays documented
        sched = {
            "fold": conf.get_int("fault.fold.crash.after", 0) or 0,
            "checkpoint.save":
                conf.get_int("fault.checkpoint.save.crash.after", 0) or 0,
            "checkpoint.restore":
                conf.get_int("fault.checkpoint.restore.crash.after", 0) or 0,
            "serve.dispatch":
                conf.get_int("fault.serve.dispatch.crash.after", 0) or 0,
            "serve.heartbeat":
                conf.get_int("fault.serve.heartbeat.crash.after", 0) or 0,
            "tenant.flood":
                conf.get_int("fault.tenant.flood.after", 0) or 0,
        }
        plan = cls(sched)
        return plan if plan.schedule else None

    def hit(self, site: str) -> None:
        """Count one pass through ``site``; raise :class:`InjectedFault`
        (journaled first) when the schedule says this is the one."""
        if site not in self.hits:
            raise ValueError(f"unknown fault site {site!r}; "
                             f"known: {self.SITES}")
        with self._lock:
            self.hits[site] += 1
            hit = self.hits[site]
            fire = hit == self.schedule.get(site, 0)
            if fire:
                self.faults_fired += 1
        if fire:
            from avenir_tpu.telemetry import spans as tel

            tel.tracer().event("fault.injected", site=site, hit=hit)
            raise InjectedFault(
                f"fault.{site}.crash.after={hit}: injected "
                f"crash at {site} boundary {hit}")


@dataclass
class HeartbeatMonitor:
    """Failure *detection* for long-running host loops: callers beat on
    progress; :meth:`stalled` reports whether the loop has gone silent for
    longer than ``timeout_s`` (the JobTracker's task-timeout analog,
    decoupled from any cluster runtime). Pure bookkeeping — the policy
    (restart, alert) belongs to the supervisor that polls it."""

    timeout_s: float = 600.0
    clock: Callable[[], float] = time.monotonic
    last_beat: float = field(default=0.0)
    beats: int = 0

    def __post_init__(self):
        self.last_beat = self.clock()

    def beat(self) -> None:
        self.beats += 1
        self.last_beat = self.clock()

    def stalled(self) -> bool:
        return (self.clock() - self.last_beat) > self.timeout_s
