"""Bucketed microbatcher — the scoring plane's shape-discipline core.

Concurrent requests for one model are folded into padded power-of-two batch
buckets (``serve.bucket.sizes``), every (model, bucket) shape is compiled at
startup (``serve.warmup.on.start``), and steady-state serving therefore
NEVER recompiles — the compiler-first caching discipline of
"Compiler-First State Space Duality and Portable O(1) Autoregressive
Caching for Inference" (PAPERS.md) applied to this framework's classical
models.  The batcher diffs each entry's ``compile_keys`` after every batch
and publishes a ``recompiles`` counter so the invariant is *measured*, not
assumed (benchmarks/serving_qps.py asserts it is zero).

Latency/throughput policy:

- a batch dispatches as soon as a full ``max(bucket)`` is waiting, or when
  the OLDEST pending request ages past ``serve.flush.deadline.ms`` — the
  max-latency flush that keeps a lone request from waiting for company;
- each model's pending queue is bounded by ``serve.queue.depth``; a submit
  against a full queue is rejected with a typed :class:`ShedError` (the
  ``max.spout.pending`` analog — load is shed at the door, not absorbed
  until everything is slow);
- a request that ages past ``serve.request.timeout.ms`` before a batch
  picks it up fails with :class:`RequestTimeout`.

Two dispatches in flight (PR 33): two dispatcher threads run the same
``_loop``, each taking a batch and running the WHOLE dispatch for it (slot →
``score_lines`` → replies).  While one dispatch is between its take and its
last reply the other thread may take a second one.  Of a model that HAS a
batch in flight it takes only a FULL ``max(bucket)``: that model's short
bucket flushes by the deadline rule above only when no dispatch in flight
carries a batch of the same model, which is when a single dispatcher would
have looked at it.  The rule is per model — a quiet model's short bucket
past its deadline is taken by the next free thread whatever a saturated
neighbour has in flight, at most one dispatch later, as with one dispatcher.
So a model under light load sees no request go out earlier or in a shorter
bucket than with one dispatcher, and under load the second dispatch's parse,
encode, upload and launch run while the first program is on the chip, its
program queues behind the first on the device's stream, and the first
dispatch's read-back, vote and replies run beside it.  Depth is two and
fixed: one program on the chip, one prepared behind it.
``Serving.<model>::overlapped`` counts, of ``batches``, those taken while
another dispatch was in flight.  Requests are independent: replies of
different buckets may complete in either order and nothing promises an order
across buckets.  What two dispatcher threads on one interpreter lock cost a
family whose device time is microseconds, or callers below two buckets, no
cell of the benchmark measures: its one serve cell (kNN, 128 callers on a
64-row bucket) sits on the always-overlapped side of the rule, and the one
reading from outside it leaves Naive Bayes unresolved (PERF.md §6–§7).
``submit`` may be called from any number of frontend threads.

Two classes of request on one model (PR 34): beside single rows (``submit``)
a model whose kernel has tiles larger than the largest bucket
(``ServableModel.tile_rows``: the fused kNN search's 256 and 512 query rows)
takes whole BLOCKS through :meth:`BucketedMicrobatcher.submit_block`
— a day's file handed in by its class (a ``tenant.<id>`` contract, the
``backfill`` of docs/multitenancy.md).  A block waits in a queue of its own,
bounded in blocks by its class's ``queue.depth``: its rows never count against
``serve.queue.depth``, are never timed out by ``serve.request.timeout.ms``
and an online shed never touches them.  Every take puts the waiting online
rows first (at most ``max(bucket)``, by the rules above, unchanged) and fills
what is left of a tile with block rows, so the backfill advances under
always-full online buckets.  WHICH tile follows what waits: while one block
waits — the submitter's pace is kept — the servable's first, the cheapest
ride, which costs the online rows next to nothing; while a backlog of blocks
waits, its last, the cheapest row, which the online rows of that dispatch
wait for (PERF.md §5 has what each costs the kNN search).  Block rows
go alone — a whole tile of them — only when the model has nothing in flight
(the short bucket's own rule): at most one such dispatch at a time, so an
online row never waits for more than the dispatch already on the chip, and a
block never holds the device for longer than one tile.  Rows of a block may
ride in both dispatches in flight and come back in either order; a block is
replied whole, and blocks of a model in the order handed in.

The hand-over (PR 38): under load a request's way through the batcher is
paid in turns of the one interpreter lock, by 128 caller threads and two
dispatchers, so what is done once a REQUEST is kept to a C call a side.  A
request carries a one-shot latch — ONE raw lock, acquired at construction:
``wait`` is the lock's own ``acquire(timeout)``, ``finish`` its ``release``
— and no ``Event``, ``Condition`` or second lock.  ``_reply`` first releases
every request of the dispatch, result and latch and nothing else, and then
does what they share ONCE: the latency samples (``record_many``), the
``serve.request`` spans, the counters (``reply_passes`` beside ``batches``),
each with the value of the one clock read before the pass.  The time-out
check asks for the oldest request of a batch and walks the batch only where
that one has timed out.

FleetServe (round 17): a batcher is now one REPLICA of a
:class:`~avenir_tpu.serving.pool.ReplicaPool` — ``name`` labels its spans,
errors and journal events; ``counters``/``latency`` may be shared across
the pool so ``/metrics`` aggregates for free; every dispatch in flight keeps a
heartbeat the pool's deadline detection reads (:meth:`stalled`: the OLDEST
one, so a wedged dispatch shows while the other beats); and a
conf-armed :class:`~avenir_tpu.utils.retry.FaultPlan` can kill it through
two sites — ``serve.dispatch`` (replica dies mid-batch: every unfinished
request of both dispatches and the queue fails with the retryable
:class:`ReplicaDownError`, the pool's failover cue) and ``serve.heartbeat``
(the dispatchers wedge silently: pending requests stay stranded until the
pool's heartbeat deadline reaps them) — so chaos drills arm replica loss
from configuration alone.
"""

from __future__ import annotations

import contextlib
import operator
import threading
import time
from collections import deque
from typing import (Callable, Deque, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

from avenir_tpu import tenancy
from avenir_tpu.core.config import ConfigError, JobConfig
from avenir_tpu.serving.errors import (
    ReplicaDownError,
    RequestError,
    RequestTimeout,
    ServingError,
    ShedError,
    TenantShedError,
)
from avenir_tpu.serving.registry import ModelRegistry
from avenir_tpu.telemetry import blackbox
from avenir_tpu.telemetry import profile as prof_mod
from avenir_tpu.telemetry import spans as tel
from avenir_tpu.utils.metrics import Counters, LatencyTracker, serving_stats
from avenir_tpu.utils.retry import FaultPlan, InjectedFault


def _latch():
    """A one-shot latch (:class:`PendingRequest`, :class:`PendingBlock`):
    ONE raw lock, held from here until its one ``release`` opens it.  A
    waiter blocks in the lock's own acquire — no condition, no allocation
    on either side."""
    lock = threading.Lock()
    lock.acquire()
    return lock


def _await(latch, timeout_s: Optional[float]) -> bool:
    """Wait for ``latch`` to open; False where it has not within
    ``timeout_s``.  Passing through leaves it open, for a second wait from
    any thread."""
    if not latch.acquire(
            timeout=-1 if timeout_s is None else max(timeout_s, 0.0)):
        return False
    latch.release()
    return True


class PendingRequest:
    """One in-flight request; ``wait`` blocks until scored (or failed).

    ``trace_ctx`` captures the submitter's span (None with tracing off):
    the dispatch thread can't see the submitting context, so the request's
    span is emitted retroactively with this parent — how a serving request
    joins the pipeline trace through the ScoringPlane stage.

    ``rid`` (FleetServe): an optional caller-assigned request id carried
    into the ``serve.request`` span, so a pool's failover dedupe — "this
    request scored exactly once, on exactly one replica" — is assertable
    from the journal.  ``probe`` marks a breaker half-open liveness probe:
    the dispatcher answers it without scoring (and without counters).

    ``tenant`` (GlobalServe): captured from the SUBMITTER's ambient
    labels, because the ``serve.request`` span is emitted by the
    dispatcher thread, whose own contextvars never saw the tenant — the
    attribute is what lets ``telemetry slo --label tenant=<id>`` gate one
    tenant's requests out of a merged fleet journal."""

    __slots__ = ("model", "line", "enqueued", "queued", "result", "error",
                 "_latch", "_token", "_set", "trace_ctx", "rid", "probe",
                 "tenant")

    def __init__(self, model: str, line: str, rid: Optional[str] = None,
                 probe: bool = False, tenant: Optional[str] = None):
        self.model = model
        self.line = line
        self.enqueued = time.monotonic()
        # ``time.perf_counter()`` when appended to its queue: where the
        # request's ``serve.queue`` span starts (the recorder's clock)
        self.queued = 0.0
        self.result: Optional[str] = None
        self.error: Optional[ServingError] = None
        self._latch = _latch()
        # what ``finish`` is raced for (see there), and whether it was won
        self._token = [None]
        self._set = False
        # the two context-variable reads of a submit: the span is skipped
        # where the tracer is off (one attribute read); the tenant label is
        # semantics and no flag says that no scope is set, so it is read
        tracer = tel.tracer()
        self.trace_ctx = tracer.current() if tracer.enabled else None
        self.rid = rid
        self.probe = probe
        self.tenant = tenant if tenant is not None \
            else tel.current_label("tenant")

    def finish(self, result: Optional[str] = None,
               error: Optional[ServingError] = None) -> bool:
        """True where THIS call finished the request.  Idempotent: a
        request that already scored must NEVER be re-finished with a
        replica-death error, and one a dying replica already failed over
        is never also reported scored (the at-most-once pillar of pool
        failover — a done request is done).

        A reply may race ``_die`` / ``fail_pending`` from another thread.
        The winner is whoever pops the one-element ``_token``: ``list.pop``
        is one call that the interpreter lock makes atomic, so exactly one
        caller gets the element and every other gets ``IndexError`` — and
        leaves ``result`` / ``error`` alone.  The winner writes both BEFORE
        it releases the latch, so a waiter that gets through reads the
        final values."""
        try:
            self._token.pop()
        except IndexError:
            return False
        self.result = result
        self.error = error
        self._set = True
        self._latch.release()
        return True

    def done(self) -> bool:
        """True once ``result`` / ``error`` are final."""
        return self._set

    def wait(self, timeout_s: Optional[float] = None) -> str:
        if not self._set and not _await(self._latch, timeout_s):
            raise RequestTimeout(
                f"no response for {self.model!r} request within "
                f"{timeout_s}s (dispatcher wedged or closed?)")
        if self.error is not None:
            raise self.error
        return self.result  # type: ignore[return-value]


class PendingBlock:
    """A block of rows handed in whole through the bulk entry
    (:meth:`BucketedMicrobatcher.submit_block`); ``wait`` blocks until every
    row is answered and returns the reply lines in the order given.

    ``tenant`` is the block's CLASS: the ``tenant.<id>`` contract it was
    handed in under (``backfill``), which bounds the blocks waiting and
    owns the slot of a dispatch that carries block rows alone.  ``seq``
    numbers the model's blocks in the order handed in — the order they are
    replied in.  ``queued`` / ``finished`` are ``time.perf_counter()`` at
    hand-in and at the last row's reply (the ``serve.backfill.block`` span).
    ``taken`` and ``answered`` count rows popped by a take and rows replied;
    they and ``results`` change under the batcher's lock only.  So does the
    block's release (``_release``: ``_set``, then the latch, once — the
    batcher's lock decides it, nothing races)."""

    __slots__ = ("model", "lines", "tenant", "rid", "seq", "queued",
                 "finished", "results", "taken", "answered", "error",
                 "_latch", "_set", "trace_ctx")

    def __init__(self, model: str, lines: Sequence[str], tenant: str,
                 rid: Optional[str] = None):
        self.model = model
        self.lines = list(lines)
        self.tenant = tenant
        self.rid = rid
        self.seq = 0
        self.queued = 0.0
        self.finished = 0.0
        self.results: List[Optional[str]] = [None] * len(self.lines)
        self.taken = 0
        self.answered = 0
        self.error: Optional[ServingError] = None
        self._latch = _latch()
        self._set = False
        self.trace_ctx = tel.tracer().current()

    def done(self) -> bool:
        return self._set

    def wait(self, timeout_s: Optional[float] = None) -> List[str]:
        if not self._set and not _await(self._latch, timeout_s):
            raise RequestTimeout(
                f"no reply for a block of {len(self.lines)} {self.model!r} "
                f"rows within {timeout_s}s (dispatcher wedged or closed?)")
        if self.error is not None:
            raise self.error
        return self.results  # type: ignore[return-value]


def _typed(exc: BaseException) -> ServingError:
    """A scoring failure as the typed error its request (or block) carries."""
    return (exc if isinstance(exc, ServingError)
            else RequestError(f"{type(exc).__name__}: {exc}"))


_ENQUEUED = operator.attrgetter("enqueued")

# the flight ring's ``serve.submit`` record, by name: a request's is a tuple
# of the values, which the ring's snapshot turns into the dict
_SUBMIT_FIELDS = ("rid", "model", "tenant", "depth")

# a run of one block's rows in one dispatch: (block, first row, past last)
_Fill = Tuple[PendingBlock, int, int]


class _Batch(NamedTuple):
    """What one take popped of one model: the online requests (first in the
    dispatch) and the block rows that fill the rest of the tile."""

    model: str
    reqs: List[PendingRequest]
    fill: List[_Fill]


class _Flight:
    """One dispatch in flight: the batches it took (one a ready model), how
    many dispatches were already in flight at its take, and its own
    heartbeat (a float store is atomic under the GIL, and
    :meth:`BucketedMicrobatcher.stalled` only compares staleness)."""

    __slots__ = ("batches", "ahead", "beat")

    def __init__(self, batches: List[_Batch], ahead: int):
        self.batches = batches
        self.ahead = ahead
        self.beat = time.monotonic()

    def tick(self) -> None:
        self.beat = time.monotonic()

    def requests(self) -> List[PendingRequest]:
        return [r for batch in self.batches for r in batch.reqs]


class BucketedMicrobatcher:
    # dispatches in flight at once, fixed: one program on the chip, one
    # prepared behind it — a third could add nothing but queueing
    DEPTH = 2

    def __init__(self, registry: ModelRegistry,
                 bucket_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
                 flush_deadline_ms: float = 5.0,
                 queue_depth: int = 1024,
                 request_timeout_ms: float = 1000.0,
                 warmup: bool = True,
                 counters: Optional[Counters] = None,
                 latency: Optional[Dict[str, LatencyTracker]] = None,
                 name: str = "",
                 tenant: str = "",
                 fault: Optional[FaultPlan] = None,
                 device=None,
                 on_batch_ok: Optional[Callable[[], None]] = None,
                 on_batch_error: Optional[Callable[[BaseException],
                                                   None]] = None):
        self.registry = registry
        self.buckets = sorted({int(b) for b in bucket_sizes})
        if not self.buckets or self.buckets[0] < 1:
            raise ConfigError(f"invalid serve.bucket.sizes {bucket_sizes!r}")
        self.max_bucket = self.buckets[-1]
        self.flush_deadline_s = float(flush_deadline_ms) / 1e3
        self.queue_depth = max(int(queue_depth), 1)
        self.request_timeout_s = float(request_timeout_ms) / 1e3
        self.counters = counters if counters is not None else Counters()
        # ``latency`` may be a POOL-shared dict (FleetServe): every replica
        # records into the same per-model trackers, so the pool's /metrics
        # and SLO evaluation aggregate without a merge step
        self.latency: Dict[str, LatencyTracker] = (
            latency if latency is not None else {})
        for model in registry.names():
            self.latency.setdefault(model, LatencyTracker())
        # FleetServe replica identity + failure machinery: ``name`` labels
        # spans/errors/events; ``fault`` is the conf-armed kill schedule
        # (shared across a pool so site counts are pool-wide); ``device``
        # pins this replica's dispatches (the dispatcher thread enters
        # jax.default_device(device) — one replica per local chip);
        # ``heartbeat`` is the idle dispatchers' liveness signal, updated
        # every loop wake; a dispatch in flight beats on its own _Flight
        # (ReplicaPool's deadline checks read both through ``stalled``)
        self.name = name
        # GraftPool (round 18): the tenant this serving plane belongs to
        # (``tenant.id``).  The dispatcher runs under the tenant's label
        # scope (every serve.request span/gauge it journals carries the
        # tenant), each batch dispatch draws an arbitrated device slot
        # under the tenant's contract, and door sheds are tenant-scoped:
        # they name the tenant + quota and carry the queue drain estimate
        # the HTTP frontend renders as Retry-After.
        self.tenant = tenant
        self.fault = fault
        self.device = device
        self.on_batch_ok = on_batch_ok
        self.on_batch_error = on_batch_error
        self.heartbeat = time.monotonic()
        self.failed = False
        # per-model EWMA of batch dispatch seconds — the queue drain
        # estimate behind a shed's Retry-After (satellite: a 429 tells
        # the client WHEN to come back, not just "go away")
        self._dispatch_ewma: Dict[str, float] = {}
        self._queues: Dict[str, Deque[PendingRequest]] = {
            name: deque() for name in registry.names()}
        # the second class of request (``submit_block``), all under
        # ``_cond``: per model the blocks with rows still to take (the head
        # may be partly taken), the blocks handed in and not yet replied
        # (in the order they will be), how many were handed in, and the
        # tiles a dispatch that carries block rows may be padded to
        self._blocks: Dict[str, Deque[PendingBlock]] = {
            name: deque() for name in registry.names()}
        self._open: Dict[str, Deque[PendingBlock]] = {
            name: deque() for name in registry.names()}
        self._block_seq: Dict[str, int] = {}
        self._tiles: Dict[str, Tuple[int, ...]] = {}
        # recompile accounting: the shared compile-key diff (telemetry,
        # generalized out of this file in round 10) — warmup primes it,
        # any fresh key afterwards counts under Serving.<name>::recompiles
        self._monitors: Dict[str, tel.CompileKeyMonitor] = {
            name: tel.CompileKeyMonitor(self.counters,
                                        group=f"Serving.{name}", scope=name)
            for name in registry.names()}
        self._cond = threading.Condition()
        self._stop = False
        # set by ``_die`` and by the wedge drill: every dispatcher thread
        # ends at its next look, taking nothing more
        self._halt = False
        # the dispatches in flight (at most DEPTH, under ``_cond``): what
        # ``stalled`` reads the oldest heartbeat of, what ``_die`` fails,
        # and — GraftBox — with the queues the in-flight table a forensics
        # bundle snapshots (rid + tenant + queue age of everything this
        # replica would strand if it died right now)
        self._flights: List[_Flight] = []
        self._bb_name = f"batcher-{name}" if name else \
            f"batcher-{id(self):x}"
        blackbox.register_provider(self._bb_name, self._blackbox_inflight,
                                   kind="inflight")
        # readiness (GraftFleet round 15): the /healthz probe's contract —
        # a load balancer must not route to a replica whose (model,
        # bucket) shapes are not compiled yet, or the first requests pay
        # the compile on the hot path.  False until warm() completes; a
        # deployment that disables serve.warmup.on.start stays NOT ready
        # until it calls warm() itself (scoring is never gated — only the
        # readiness signal).
        self.ready = False
        if warmup:
            self.warm()
        with self._cond:
            self._wake()
        stem = f"serve-dispatch-{name}" if name else "serve-dispatch"
        self._threads = [
            threading.Thread(target=self._loop, daemon=True,
                             name=f"{stem}-{i}")
            for i in range(self.DEPTH)]
        for thread in self._threads:
            thread.start()

    @classmethod
    def from_conf(cls, registry: ModelRegistry, conf: JobConfig,
                  **kwargs) -> "BucketedMicrobatcher":
        """``kwargs`` passes through the FleetServe wiring (``name``,
        shared ``counters``/``latency``, ``device``, the dispatch
        callbacks).  A ``fault`` plan not supplied by the caller is armed
        from the conf's own ``fault.*`` keys, so a single-replica tier-1
        test kills its batcher through configuration alone."""
        if "fault" not in kwargs:
            kwargs["fault"] = FaultPlan.from_conf(conf)
        if "tenant" not in kwargs:
            kwargs["tenant"] = conf.get("tenant.id", "") or ""
        return cls(
            registry,
            bucket_sizes=conf.get_int_list("serve.bucket.sizes",
                                           [1, 2, 4, 8, 16, 32, 64]),
            flush_deadline_ms=conf.get_float("serve.flush.deadline.ms", 5.0),
            queue_depth=conf.get_int("serve.queue.depth", 1024),
            request_timeout_ms=conf.get_float("serve.request.timeout.ms",
                                              1000.0),
            warmup=conf.get_bool("serve.warmup.on.start", True),
            **kwargs,
        )

    # -- warmup / recompile accounting ---------------------------------------
    def warm(self) -> Dict[str, int]:
        """Compile every (model, bucket) shape; shapes seen here never count
        as recompiles later.  Completing marks the batcher ready (the
        /healthz readiness contract)."""
        warmed = self.registry.warmup(self.buckets)
        for name, entry in self.registry.items():
            self._monitors[name].prime(entry.compile_keys)
        self.ready = True
        return warmed

    # -- hot swap (any thread) -----------------------------------------------
    def swap(self, model: str, entry, warm: bool = True) -> int:
        """Zero-downtime model hot-swap with the compile barrier.

        Warms the INCOMING entry's bucket shapes and primes its recompile
        monitor BEFORE publishing it to the registry, so the first
        post-swap batch scores on already-compiled shapes — the
        zero-steady-state-recompiles invariant holds ACROSS a swap, not
        just between swaps.  In-flight batches hold the old entry object
        they resolved at dispatch and finish on the old params; every
        batch dispatched after the publish resolves the new entry.
        The warmup compiles run on the CALLER's thread concurrently with live
        dispatches (JAX is thread-safe; routing them through the
        dispatcher would stall the same batches behind the same compiles)
        — expect a p99 bump for the duration of a swap either way.
        ``warm=False`` (``serve.swap.warmup``) skips the barrier — the
        first post-swap batch then pays the compile on the hot path and
        the monitor counts it, which is exactly the visibility the
        default exists to avoid.  Returns the model's new version."""
        self.registry.get(model)          # raises UnknownModelError early
        if warm:
            for bucket in self.buckets:
                entry.warmup(int(bucket))
            for tile in self._tiles.get(model, ()):
                # the model has taken blocks: their tiles are shapes too
                entry.warmup(tile)
            self._monitors[model].prime(entry.compile_keys)
        version = self.registry.swap(model, entry)
        self.counters.increment(f"Serving.{model}", "swaps")
        tel.tracer().event("model.swap", model=model, version=version,
                           family=entry.family, warmed=bool(warm))
        # swap boundary: the outgoing entry's device buffers should be
        # collectable once in-flight batches drain — a leak across
        # repeated hot-swaps shows up in this gauge before it OOMs
        prof_mod.profiler().sample_device_memory("swap")
        return version

    # -- submission (any thread) ---------------------------------------------
    def submit_nowait(self, model: str, line: str,
                      rid: Optional[str] = None) -> PendingRequest:
        entry = self.registry.get(model)            # raises UnknownModelError
        del entry
        req = PendingRequest(model, line, rid=rid)
        shed_depth = None
        with self._cond:
            if self.failed:
                raise self._down_error("replica is down")
            if self._stop:
                raise ServingError("batcher is closed")
            queue = self._queues[model]
            if len(queue) >= self.queue_depth:
                self.counters.increment(f"Serving.{model}", "shed")
                if self.tenant:
                    self.counters.increment(f"Tenant.{self.tenant}", "shed")
                shed_depth = len(queue)
            else:
                queue.append(req)
                req.queued = time.perf_counter()
                depth = len(queue)
                if depth >= self.max_bucket or (
                        depth == 1 and not self._in_flight(model)):
                    # only the request that fills a bucket or starts a
                    # queue's deadline gives a sleeper something to act on
                    # (with two sleepers, a wake a submit is two threads
                    # contending with the submitters for nothing); a short
                    # bucket behind a dispatch of its own model is nobody's
                    # to take (``_ready``): that dispatch's return notifies
                    self._cond.notify()
        if shed_depth is None:
            # GraftBox: the submit door records straight to the flight
            # ring (trace.on or not, and outside the lock) — a SIGKILLed
            # replica's bundle shows WHICH rids were in flight
            blackbox.ring_record("serve.submit",
                                 (req.rid, model, req.tenant, depth),
                                 _SUBMIT_FIELDS)
        if shed_depth is not None:
            if self.tenant:
                # tenant-scoped door shed: booked under the tenant (above,
                # in the lock), journaled as tenant.shed and raised HERE —
                # outside the lock, so a shed storm's journal I/O never
                # serializes other submitters — carrying the queue drain
                # estimate (Retry-After) + the quota that fired
                retry_after = self.drain_estimate_s(model)
                tel.tracer().event(
                    "tenant.shed", tenant=self.tenant,
                    quota="serve.queue.depth",
                    waiting=shed_depth, inflight=0,
                    retry_after_ms=round(retry_after * 1e3, 1))
                raise self._attribute(TenantShedError(
                    f"{model!r} queue at depth {self.queue_depth} for "
                    f"tenant {self.tenant!r} — request shed "
                    f"(backpressure); retry after ~{retry_after:.2f}s",
                    tenant=self.tenant, quota="serve.queue.depth",
                    retry_after_s=retry_after), wait_s=0.0)
            raise self._attribute(ShedError(
                f"{model!r} queue at depth {self.queue_depth}"
                + (f" on replica {self.name!r}" if self.name else "")
                + " — request shed (backpressure)"), wait_s=0.0)
        return req

    def submit(self, model: str, line: str,
               timeout_s: Optional[float] = None) -> str:
        """Blocking submit: returns the response line or raises the typed
        error.  Default wait bound covers the request timeout plus dispatch
        slack so a wedged dispatcher surfaces as RequestTimeout, not a hang."""
        if timeout_s is None:
            timeout_s = self.request_timeout_s + 30.0
        return self.submit_nowait(model, line).wait(timeout_s)

    # -- the bulk entry (any thread) -------------------------------------------
    def submit_block(self, model: str, lines: Sequence[str],
                     klass: Optional[str] = None,
                     rid: Optional[str] = None) -> PendingBlock:
        """Hand in a block of rows WHOLE, as the class ``klass`` (a
        ``tenant.<id>`` contract; default: the submitter's ambient tenant
        label): the second class of request on a model, see the module
        docstring.  Returns at once; ``PendingBlock.wait`` gives the reply
        lines in the order of ``lines`` once the last row is answered.

        Bounded in BLOCKS waiting by the class's ``queue.depth`` (the
        grammar's default where the class has no contract): a block over
        the bound is shed at the door, typed — whatever the online queue
        holds, and an online shed never touches a block.  The first block
        of a model compiles the tiles' shapes here, on the caller's thread
        (as ``swap`` warms), so no dispatch pays for one."""
        entry = self.registry.get(model)            # raises UnknownModelError
        tiles = tuple(int(t) for t in entry.tile_rows)
        if not tiles or tiles[0] <= self.max_bucket:
            raise RequestError(
                f"{model!r} has no bulk entry: its dispatches sweep no tile "
                f"beyond the largest bucket ({self.max_bucket} rows) for "
                f"block rows to ride in")
        if not lines:
            raise RequestError("an empty block is not servable")
        klass = klass or tel.current_label("tenant") or ""
        contract = tenancy.pool().contract(klass)
        bound = (contract.queue_depth if contract is not None
                 else tenancy.DEFAULT_QUEUE_DEPTH)
        warm = {k[0] for k in tuple(entry.compile_keys) if k}
        for tile in tiles:
            if tile not in warm:
                entry.warmup(tile)
                self._monitors[model].prime(entry.compile_keys)
        block = PendingBlock(model, lines, klass, rid=rid)
        with self._cond:
            if self.failed:
                raise self._down_error("replica is down")
            if self._stop:
                raise ServingError("batcher is closed")
            waiting = len(self._blocks[model])
            if waiting < bound:
                self._tiles[model] = tiles
                block.seq = self._block_seq.get(model, 0)
                self._block_seq[model] = block.seq + 1
                block.queued = time.perf_counter()
                self._blocks[model].append(block)
                self._open[model].append(block)
                if not self._in_flight(model):
                    # otherwise the rows ride with the next online take,
                    # or that dispatch's return notifies
                    self._cond.notify()
                return block
            self.counters.increment(f"Serving.{model}", "backfill_shed")
            if klass:
                self.counters.increment(f"Tenant.{klass}", "shed")
        retry_after = self.drain_estimate_s(model)
        if klass:
            tel.tracer().event(
                "tenant.shed", tenant=klass, quota="queue.depth",
                waiting=waiting, inflight=0,
                retry_after_ms=round(retry_after * 1e3, 1))
        raise self._attribute(TenantShedError(
            f"{waiting} blocks of {model!r} waiting for class {klass!r} "
            f"(queue.depth {bound}) — block shed (backpressure); retry "
            f"after ~{retry_after:.2f}s",
            tenant=klass, quota="queue.depth", retry_after_s=retry_after),
            wait_s=0.0)

    # -- dispatch loop (DEPTH threads) ----------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_bucket

    def _tile_for(self, model: str, n: int) -> int:
        """The smallest of the model's tiles that holds ``n`` rows."""
        return next(t for t in self._tiles[model] if t >= n)

    @property
    def _dispatching(self) -> bool:
        # the parent's flag, by its name: tests/test_tenancy.py polls it
        return bool(self._flights)

    def _in_flight(self, model: str) -> bool:
        """Whether a dispatch in flight carries a batch of ``model`` (under
        ``_cond``)."""
        return any(batch.model == model
                   for flight in self._flights for batch in flight.batches)

    def _ready(self, now: float) -> List[str]:
        """The models a dispatcher may take a batch of now (under
        ``_cond``).  A full ``max(bucket)`` always; a short one — past the
        flush deadline, or whatever is left at close — only when no
        dispatch in flight carries a batch of the SAME model, which is when
        a single dispatcher would have looked at it.  A deadline that
        cannot cut while the model has something in flight lets a straggler
        rejoin its group, so callers of twice a bucket settle into full
        buckets, not into fragments (PERF.md §6, PR 26 finding 2, is what a
        deadline that may cut did); a quiet model beside a saturated one is
        not held back by the neighbour's flights.  Block rows alone are one
        more thing that only goes with nothing of the model in flight — and
        then at once, with whatever online rows wait, deadline or not."""
        out = []
        for name, queue in self._queues.items():
            if len(queue) >= self.max_bucket:
                out.append(name)
            elif (queue or self._blocks[name]) and (
                    self._blocks[name] or self._stop
                    or now - queue[0].enqueued >= self.flush_deadline_s
                    ) and not self._in_flight(name):
                out.append(name)
        return out

    def _next_wait(self, now: float) -> Optional[float]:
        # a short bucket's deadline is not looked at before its model's
        # dispatch in flight returns, and that return notifies
        deadlines = [queue[0].enqueued + self.flush_deadline_s - now
                     for name, queue in self._queues.items()
                     if queue and not self._in_flight(name)]
        if not deadlines:
            return None                   # sleep until a submit notifies
        return max(min(deadlines), 0.0)

    def _wake(self) -> None:
        """One pass of the ``serve.heartbeat`` drill site (under ``_cond``):
        once at start and once after every dispatch — the loop wakes of the
        single dispatcher the site was counted on.  The drill wedges the
        batcher: BOTH threads exit WITHOUT finishing pending work, the
        heartbeat goes stale and the pool's deadline detection is what has
        to reap the stranded queue."""
        self.heartbeat = time.monotonic()
        if self.fault is not None and not self._halt:
            try:
                self.fault.hit("serve.heartbeat")
            except InjectedFault:
                self._halt = True
                self._cond.notify_all()

    def _take(self) -> Optional[_Flight]:
        """Block until this thread may take a dispatch; pop one batch of
        every ready model and return them registered in flight.  None when
        the thread is to end: closed and drained, died, or wedged."""
        with self._cond:
            while True:
                self.heartbeat = time.monotonic()
                if self._halt:
                    return None
                ready = self._ready(time.monotonic())
                if ready:
                    break
                if self._stop and not any(self._queues.values()) \
                        and not any(self._blocks.values()):
                    return None
                self._cond.wait(timeout=self._next_wait(time.monotonic()))
            batches = []
            for name in ready:
                queue = self._queues[name]
                take = min(len(queue), self.max_bucket)
                reqs = [queue.popleft() for _ in range(take)]
                batches.append(_Batch(name, reqs, self._take_fill(name, take)))
            flight = _Flight(batches, ahead=len(self._flights))
            self._flights.append(flight)
            if any(self._queues.values()):
                # what is left is the other thread's to time: it may be
                # asleep with no deadline, or with this model's
                self._cond.notify()
            return flight

    def _take_fill(self, model: str, online: int) -> List[_Fill]:
        """Pop block rows of ``model`` into what a dispatch of ``online``
        rows leaves of a tile, oldest block first (under ``_cond``): of the
        cheapest tile while the blocks keep up, of the widest while more
        than the one being taken wait."""
        blocks = self._blocks[model]
        fill: List[_Fill] = []
        if not blocks:
            return fill
        tiles = self._tiles[model]
        room = (tiles[-1] if len(blocks) > 1 else tiles[0]) - online
        while blocks and room > 0:
            block = blocks[0]
            n = min(room, len(block.lines) - block.taken)
            fill.append((block, block.taken, block.taken + n))
            block.taken += n
            room -= n
            if block.taken == len(block.lines):
                blocks.popleft()
        return fill

    def _loop(self) -> None:
        with contextlib.ExitStack() as stack:
            if self.tenant:
                # a dispatcher works AS the tenant: every span, gauge
                # and recompile event it journals carries the label, so
                # one merged fleet view attributes this plane's serving
                # cost to its owner (the label is the thread's own)
                stack.enter_context(tel.label_scope(tenant=self.tenant))
            if self.device is not None:
                import jax

                # replica-per-chip placement: every dispatch this thread
                # makes defaults onto this replica's device (params
                # committed elsewhere still win — jax array placement)
                stack.enter_context(jax.default_device(self.device))
            while True:
                flight = self._take()
                if flight is None:
                    return
                try:
                    for batch in flight.batches:
                        # refreshed PER BATCH so a dispatcher working
                        # through several slow batches reads as busy,
                        # not wedged — only true silence past the
                        # deadline is a miss
                        flight.tick()
                        try:
                            # GraftBox: a dispatch that wedges (stuck
                            # device call, deadlocked arbiter) trips the
                            # progress watchdog and captures a bundle
                            with blackbox.watchdog_guard("serve.dispatch"):
                                self._dispatch(batch, flight)
                        except Exception:  # noqa: BLE001
                            # replica-fatal, injected (serve.dispatch
                            # kill) or real: every unfinished request
                            # (both dispatches in flight + everything
                            # queued) fails RETRYABLE so the pool can
                            # re-enqueue it on a survivor — waiting for
                            # the heartbeat deadline to reap a
                            # silently-dead loop would stall them for
                            # seconds instead
                            self._die()
                            return
                finally:
                    with self._cond:
                        self._flights.remove(flight)
                        self._wake()
                        # the other thread may now look at a short bucket
                        self._cond.notify_all()

    def _dispatch(self, batch: _Batch, flight: _Flight) -> None:
        """One batch under its ``serve.dispatch`` span (``inflight``: the
        dispatches already in flight when it was taken, 0 or 1), then one
        retroactive ``serve.queue`` span a request: appended to the queue →
        taken by this dispatch (the span's start — batches popped together
        wait their turn in the queue span), linked by ``dispatch``; and one
        ``serve.backfill.queue`` span a run of block rows, handed in → this
        dispatch, so that ``serve.queue`` keeps meaning online rows."""
        tracer = tel.tracer()
        model, reqs = batch.model, batch.reqs
        # a batch joins the trace of the first request that carries one
        # (under a ScoringPlane stage: the stage's own trace)
        ctx = next((r.trace_ctx for r in reqs if r.trace_ctx is not None),
                   None)
        with tracer.span("serve.dispatch",
                         {"model": model, "inflight": flight.ahead},
                         parent=ctx) as span:
            self._dispatch_batch(batch, span, flight)
        if span.enabled:
            for req in reqs:
                if req.probe:
                    continue
                attrs = {"model": model, "dispatch": span.span_id}
                if req.rid is not None:
                    attrs["rid"] = req.rid
                tracer.emit_span("serve.queue", span.start - req.queued,
                                 parent=req.trace_ctx, attrs=attrs,
                                 start=req.queued)
            for block, lo, hi in batch.fill:
                tracer.emit_span(
                    "serve.backfill.queue", span.start - block.queued,
                    parent=block.trace_ctx, start=block.queued,
                    attrs={"model": model, "dispatch": span.span_id,
                           "block": block.seq, "rows": hi - lo})

    def _dispatch_batch(self, batch: _Batch, span, flight: _Flight) -> None:
        model, reqs, fill = batch.model, batch.reqs, batch.fill
        scorable = [r for r in reqs if not r.probe]
        if len(scorable) != len(reqs):
            for req in reqs:
                if req.probe:
                    # breaker half-open liveness probe: answered by the
                    # dispatcher without scoring (and without counters) —
                    # it proves THIS thread is alive and draining its queue
                    req.finish(result="pong")
        if not scorable and not fill:
            return
        if self.fault is not None:
            # the replica-kill site: fires BEFORE any request of the
            # batch scores (InjectedFault propagates to _loop → _die),
            # so an injected death can never double-score a request of
            # THIS batch; the other dispatch in flight is failed over with
            # it and its late replies are dropped (``finish`` is idempotent
            # and ``_reply`` reports only what it finished)
            self.fault.hit("serve.dispatch")
        group = f"Serving.{model}"
        now = time.monotonic()
        # once a dispatch: where the OLDEST request has not timed out none
        # has.  It is looked up, not taken for the batch's first: the queue
        # is in the order the submitters took ``_cond`` and ``enqueued`` is
        # stamped just before, so a submitter that lost the interpreter
        # between the two sits behind a younger request
        live = scorable
        if scorable and \
                now - min(map(_ENQUEUED, scorable)) > self.request_timeout_s:
            live = []
            for req in scorable:
                if now - req.enqueued > self.request_timeout_s:
                    self.counters.increment(group, "timeouts")
                    req.finish(error=self._attribute(RequestTimeout(
                        f"request waited past "
                        f"{self.request_timeout_s * 1e3:.0f} ms before "
                        f"dispatch"
                        + (f" on replica {self.name!r}" if self.name else "")),
                        wait_s=now - req.enqueued))
                else:
                    live.append(req)
        if not live and not fill:
            return
        entry = self.registry.get(model)
        # block rows ride behind the online rows, in one shape: the tile
        lines = [r.line for r in live]
        for block, lo, hi in fill:
            lines.extend(block.lines[lo:hi])
        bucket = (self._tile_for(model, len(lines)) if fill
                  else self._bucket_for(len(live)))
        span.set("rows", len(live)).set("bucket", bucket)
        span.set("online_rows", len(live))
        span.set("backfill_rows", len(lines) - len(live))
        # a dispatch that carries an online row draws its slot as the plane
        # and bounds the wait by the request timeout; block rows alone draw
        # it as their own class, under that contract's deadline if any
        tenant = self.tenant if live or not fill else fill[0][0].tenant
        try:
            # GraftPool (round 18): the batch draws an arbitrated device
            # slot under this plane's tenant contract before it scores —
            # serve dispatches and batch/stream chunk folds share ONE
            # fair-queued pool.  Un-tenanted batchers pass through (the
            # shared null context).  The slot wait is bounded by the
            # request timeout (a tenant paced past it sheds typed rather
            # than stranding requests) and ticks the dispatch's heartbeat
            # while queued — being PACED is not being WEDGED, and the
            # pool's deadline watch must not reap a merely-contended
            # replica.  Where the contract grants one slot the second
            # dispatch in flight waits here and the batcher runs as with
            # one dispatcher.
            with contextlib.ExitStack() as held:
                with tel.tracer().span("serve.slot"):
                    held.enter_context(tenancy.pool().slot(
                        tenant=tenant or None,
                        timeout_s=self.request_timeout_s if live else None,
                        on_wait=flight.tick))
                t0 = time.monotonic()
                outs = entry.score_lines(lines, bucket)
                dispatch_s = time.monotonic() - t0
        except TenantShedError as exc:
            # the tenant's pool share refused this batch before any row
            # scored: fail the whole batch typed — tenant-scoped, so the
            # other tenants' planes keep dispatching
            self.counters.increment(group, "shed", len(live))
            self._attribute(exc)
            for req in live:
                req.finish(error=exc)
            self._fail_blocks(model, [block for block, _, _ in fill], exc)
            return
        except Exception as exc:
            # typed ServingErrors are REQUEST faults (bad rows); anything
            # else is an infrastructure fault the pool's breaker counts
            if self.on_batch_error is not None and \
                    not isinstance(exc, ServingError):
                self.on_batch_error(exc)
            # one bad row must not poison its coalesced batch neighbors:
            # re-score each request alone (smallest bucket — warmed, so no
            # recompile) so only the genuinely bad ones fail typed; each
            # block's rows are re-scored apart from everybody else's, and
            # a block with a bad row fails whole
            if len(live) > 1 or fill:
                self._dispatch_isolated(entry, group, live, fill, flight)
                return
            self.counters.increment(group, "errors")
            live[0].finish(error=self._attribute(
                _typed(exc), wait_s=time.monotonic() - live[0].enqueued))
            return
        with self._cond:
            prev = self._dispatch_ewma.get(model)
            self._dispatch_ewma[model] = (
                dispatch_s if prev is None
                else 0.8 * prev + 0.2 * dispatch_s)
        if self.on_batch_ok is not None:
            self.on_batch_ok()
        self._finish_scored(entry, group, model, live, outs, bucket, flight,
                            dispatch_s, fill)

    def _dispatch_isolated(self, entry, group: str,
                           reqs: List[PendingRequest], fill: List[_Fill],
                           flight: _Flight) -> None:
        """Failure-isolation path: score each request of a failed batch
        alone, and each run of block rows alone; good rows still succeed,
        bad rows carry their own error, a block with a bad row fails
        whole."""
        model = reqs[0].model if reqs else fill[0][0].model
        bucket = self._bucket_for(1)
        for req in reqs:
            try:
                with tenancy.pool().slot(tenant=self.tenant or None,
                                         timeout_s=self.request_timeout_s,
                                         on_wait=flight.tick):
                    outs = entry.score_lines([req.line], bucket)
            except TenantShedError as exc:
                self.counters.increment(group, "shed")
                req.finish(error=self._attribute(exc))
                continue
            except Exception as exc:
                if self.on_batch_error is not None and \
                        not isinstance(exc, ServingError):
                    self.on_batch_error(exc)
                self.counters.increment(group, "errors")
                req.finish(error=self._attribute(
                    _typed(exc), wait_s=time.monotonic() - req.enqueued))
                continue
            if self.on_batch_ok is not None:
                self.on_batch_ok()
            self._finish_scored(entry, group, model, [req], outs, bucket,
                                flight)
        for run in fill:
            block, lo, hi = run
            if block.error is not None:
                continue                  # an earlier run already failed it
            tile = self._tile_for(model, hi - lo)
            try:
                with tenancy.pool().slot(tenant=block.tenant or None,
                                         on_wait=flight.tick):
                    outs = entry.score_lines(block.lines[lo:hi], tile)
            except Exception as exc:
                if self.on_batch_error is not None and \
                        not isinstance(exc, ServingError):
                    self.on_batch_error(exc)
                self.counters.increment(group, "errors")
                self._fail_blocks(model, [block],
                                  self._attribute(_typed(exc)))
                continue
            self._finish_scored(entry, group, model, [], outs, tile, flight,
                                fill=[run])

    def _finish_scored(self, entry, group: str, model: str,
                       live: List[PendingRequest], outs: List[str],
                       bucket: int, flight: _Flight,
                       dispatch_s: Optional[float] = None,
                       fill: Sequence[_Fill] = ()) -> None:
        with tel.tracer().span("serve.reply"):
            self._reply(entry, group, model, live, outs, bucket, flight,
                        dispatch_s, fill)

    def _reply(self, entry, group: str, model: str,
               live: List[PendingRequest], outs: List[str], bucket: int,
               flight: _Flight, dispatch_s: Optional[float],
               fill: Sequence[_Fill]) -> None:
        # a shape outside the warmed set means this batch paid a compile
        # on the hot path — the invariant violation the counter exposes
        # (the monitor's key feed also registers each key as a GraftProf
        # program under site=<model>)
        self._monitors[model].observe(entry.compile_keys)
        done = time.monotonic()
        tracer = tel.tracer()
        prof = prof_mod.profiler()
        pid = None
        if prof.enabled:
            # the program this batch dispatched: the entry's compile key
            # for this bucket (every entry keys on (bucket, ...))
            # (a snapshot: the other dispatch in flight may add a key)
            pkey = next((k for k in tuple(entry.compile_keys)
                         if k and k[0] == bucket), (bucket,))
            pid = prof_mod.program_id(model, pkey)
            if dispatch_s is not None:
                prof.sample(pkey, model, dispatch_s)
        # release the group in one pass: result and latch, nothing else —
        # a woken caller can do nothing while this thread holds the
        # interpreter, so whatever stands between two releases only delays
        # the last caller's wake-up.  A request ``finish`` does not win was
        # failed over by ``_die`` while this batch scored: it is another
        # replica's request now, never reported here too
        won = [req for req, out in zip(live, outs) if req.finish(out)]
        answered = len(won)
        if won:
            # the bookkeeping of the requests released, after: every sample
            # and span carries the value it had inside the pass (``done``)
            waits = [done - req.enqueued for req in won]
            self.latency[model].record_many(waits)
            if tracer.enabled:
                self._request_spans(model, bucket, pid, won, waits)
        backfill = self._deliver(model, fill, outs[len(live):]) if fill else 0
        if not answered and not backfill:
            return
        if answered:
            self.counters.increment(group, "requests", answered)
            # of ``batches``, those whose online rows went out in one pass
            self.counters.increment(group, "reply_passes")
            if self.tenant:
                self.counters.increment(f"Tenant.{self.tenant}", "rows",
                                        answered)
        self.counters.increment(group, "batches")
        if flight.ahead:
            # counted where ``batches`` is, so the two are one population
            self.counters.increment(group, "overlapped")
        self.counters.increment(group, f"bucket.{bucket}")
        if backfill:
            self.counters.increment(group, "backfill_rows", backfill)
            if not live:
                # device time the blocks took from nobody's online rows
                self.counters.increment(group, "backfill_only")
        if tracer.enabled:
            tracer.gauge(f"serve.queue.{model}", len(self._queues[model]))

    def _request_spans(self, model: str, bucket: int, pid: Optional[str],
                       reqs: List[PendingRequest],
                       waits: List[float]) -> None:
        """One retroactive ``serve.request`` span a request just released.
        FleetServe attribution: which replica scored the request and how
        long it sat queued — a shed storm or p99 excursion is triaged to
        ONE replica from the merged fleet journal."""
        tracer = tel.tracer()
        for req, wait_s in zip(reqs, waits):
            attrs = {"model": model, "bucket": bucket,
                     "wait_ms": round(wait_s * 1e3, 3)}
            if self.name:
                attrs["replica"] = self.name
            if req.rid is not None:
                attrs["rid"] = req.rid
            if req.tenant:
                attrs["tenant"] = req.tenant
            if pid is not None:
                attrs["program"] = pid
            tracer.emit_span("serve.request", wait_s,
                             parent=req.trace_ctx, attrs=attrs)

    # -- blocks: replies, in order, whole -------------------------------------
    def _deliver(self, model: str, fill: Sequence[_Fill],
                 outs: Sequence[str]) -> int:
        """Put a dispatch's reply lines into their blocks and release every
        block that is now whole AND has no earlier block of the model still
        open; returns the rows delivered."""
        at, delivered = 0, 0
        with self._cond:
            for block, lo, hi in fill:
                if block.error is None:
                    block.results[lo:hi] = outs[at:at + hi - lo]
                    block.answered += hi - lo
                    delivered += hi - lo
                at += hi - lo
            released = self._release(model)
        self._closed_blocks(model, released)
        return delivered

    def _release(self, model: str) -> List[PendingBlock]:
        """Pop and signal, oldest first, the model's open blocks that are
        answered whole or failed (under ``_cond``, so that no later block
        is ever signalled before an earlier one)."""
        open_, released = self._open[model], []
        while open_ and (open_[0].error is not None
                         or open_[0].answered == len(open_[0].lines)):
            block = open_.popleft()
            block.finished = time.perf_counter()
            block._set = True
            block._latch.release()
            released.append(block)
        return released

    def _closed_blocks(self, model: str,
                       released: Sequence[PendingBlock]) -> None:
        """Counters and the ``serve.backfill.block`` span of blocks just
        released (outside the lock)."""
        tracer = tel.tracer()
        for block in released:
            ok = block.error is None
            self.counters.increment(
                f"Serving.{model}",
                "backfill_blocks" if ok else "backfill_failed")
            if ok and block.tenant:
                self.counters.increment(f"Tenant.{block.tenant}", "rows",
                                        len(block.lines))
            attrs = {"model": model, "block": block.seq,
                     "rows": len(block.lines)}
            if block.tenant:
                attrs["tenant"] = block.tenant
            if block.rid is not None:
                attrs["rid"] = block.rid
            tracer.emit_span("serve.backfill.block",
                             block.finished - block.queued,
                             parent=block.trace_ctx, attrs=attrs,
                             status="ok" if ok else "error",
                             start=block.queued)

    def _fail_blocks(self, model: str, blocks: Sequence[PendingBlock],
                     err: ServingError) -> None:
        """Fail ``blocks`` whole: rows not yet taken are dropped, replies
        still in flight are discarded when they come."""
        with self._cond:
            for block in blocks:
                if block.error is None and not block.done():
                    block.error = err
                    if block in self._blocks[model]:
                        self._blocks[model].remove(block)
            released = self._release(model)
        self._closed_blocks(model, released)

    # -- replica failure machinery (FleetServe, round 17) --------------------
    def _attribute(self, err: ServingError,
                   wait_s: Optional[float] = None) -> ServingError:
        """Stamp a typed error with this replica's identity, its tenant
        and the request's queue wait, so client-visible failures triage
        to the replica (and owner) that caused them without the journal."""
        err.replica = self.name or None
        if self.tenant and getattr(err, "tenant", None) in (None, ""):
            err.tenant = self.tenant
        if wait_s is not None:
            err.queue_wait_ms = round(wait_s * 1e3, 3)
        return err

    def drain_estimate_s(self, model: str) -> float:
        """How long this model's pending queue needs to drain: queued
        batches × (EWMA batch dispatch + the flush deadline) — the
        ``Retry-After`` a tenant-scoped shed carries.  Bounded by the
        arbiter's shared clamp policy; no dispatch observed yet reads as
        a nominal 50 ms batch."""
        from avenir_tpu.tenancy.arbiter import (
            RETRY_AFTER_MAX_S,
            RETRY_AFTER_MIN_S,
        )

        depth = len(self._queues[model])
        batches = max((depth + self.max_bucket - 1) // self.max_bucket, 1)
        room = self._tiles.get(model, (0,))[-1] - self.max_bucket
        if room > 0:
            # block rows ride in what the online rows leave of the widest
            # tile: a backlog is what there is to drain
            rows = sum(len(b.lines) - b.taken for b in self._blocks[model])
            batches = max(batches, -(-rows // room))
        est = batches * (self._dispatch_ewma.get(model, 0.05)
                         + self.flush_deadline_s)
        return min(max(est, RETRY_AFTER_MIN_S), RETRY_AFTER_MAX_S)

    def _down_error(self, reason: str,
                    req: Optional[PendingRequest] = None) -> ReplicaDownError:
        err = ReplicaDownError(
            (f"replica {self.name!r}: " if self.name else "") + reason)
        return self._attribute(
            err, wait_s=(time.monotonic() - req.enqueued)
            if req is not None else None)

    def _die(self) -> None:
        """serve.dispatch kill: mark the replica failed (new submissions
        are refused at the door), end both dispatcher threads and fail
        every unfinished request — those of EVERY dispatch in flight
        (popped but unanswered) plus everything still queued — with the
        RETRYABLE :class:`ReplicaDownError`, the pool's cue to re-enqueue
        them on survivors.  ``finish`` is idempotent, so a request that
        already scored can never be re-failed here."""
        with self._cond:
            self.failed = True
            self._halt = True
            stranded = [r for f in self._flights for r in f.requests()]
            queued = [r for q in self._queues.values() for r in q]
            for q in self._queues.values():
                q.clear()
            blocks = {m: list(open_) for m, open_ in self._open.items()}
            self._cond.notify_all()
        for req in stranded + queued:
            req.finish(error=self._down_error("died mid-batch", req))
        for model, open_ in blocks.items():
            # every block not yet replied, taken or not
            self._fail_blocks(model, open_,
                              self._down_error("died mid-batch"))

    def mark_failed(self) -> None:
        """Pool-side declaration that this replica is dead (missed
        heartbeat deadline): refuse new submissions from now on."""
        with self._cond:
            self.failed = True
            self._cond.notify_all()

    def fail_pending(self, reason: str = "replica down") -> int:
        """Fail every QUEUED request with :class:`ReplicaDownError` (the
        pool reaps a wedged replica's stranded queue with this); returns
        how many requests were failed over."""
        with self._cond:
            reqs = [r for q in self._queues.values() for r in q]
            for q in self._queues.values():
                q.clear()
            blocks = {m: list(q) for m, q in self._blocks.items() if q}
        for req in reqs:
            req.finish(error=self._down_error(reason, req))
        for model, waiting in blocks.items():
            # a block with rows still to take: what was taken of it is in
            # a dispatch nobody will finish
            self._fail_blocks(model, waiting, self._down_error(reason))
        return len(reqs) + sum(map(len, blocks.values()))

    def stalled(self, deadline_s: float) -> bool:
        """True when the batcher has WORK but a heartbeat older than
        ``deadline_s`` — a wedged (or silently dead) dispatcher.  With
        dispatches in flight it is the OLDEST of their heartbeats that
        counts, so one wedged dispatch shows while the other beats; with
        none, the idle threads' last wake.  An idle batcher is never
        stalled: with nothing to dispatch a stale heartbeat is just
        sleep."""
        with self._cond:
            busy = self._dispatching or any(self._queues.values()) \
                or any(self._blocks.values())
            beat = min((f.beat for f in self._flights),
                       default=self.heartbeat)
            return busy and \
                (time.monotonic() - beat) > float(deadline_s)

    def probe(self, timeout_s: float = 5.0) -> bool:
        """Breaker half-open liveness probe: push a no-op request through
        the REAL dispatch queue and wait for a dispatcher to answer it
        (a short bucket of the first model: it rides in that model's next
        full bucket, or goes out alone once the model has nothing in flight
        and a thread is free; a dispatch in flight that has not beaten for
        ``timeout_s`` fails the probe whichever thread answered it).  True
        = the dispatch threads are alive and draining (the breaker may
        close); False = dead, wedged, or closed (stay open)."""
        if self.failed or not all(t.is_alive() for t in self._threads):
            return False
        model = next(iter(self._queues), None)
        if model is None:
            return False
        req = PendingRequest(model, "", rid="probe", probe=True)
        with self._cond:
            if self._stop or self.failed:
                return False
            self._queues[model].append(req)
            self._cond.notify()
        try:
            req.wait(timeout_s)
        except ServingError:
            return False
        # answered by one thread: the other's dispatch, silent for longer
        # than the probe would have waited, is a wedged dispatcher still
        return not self.stalled(timeout_s)

    def health(self) -> Dict[str, object]:
        """The ``/healthz`` body: readiness (warmed AND not failed),
        loaded models, per-model queue depth vs cap, and each model's
        registry version — what a prober needs to see backpressure and
        rollout state at a glance."""
        ready = bool(self.ready) and not self.failed
        return {
            "status": "ok" if ready else "unavailable",
            "ready": ready,
            "models": self.registry.names(),
            "buckets": self.buckets,
            "queue": {name: {"depth": depth, "cap": self.queue_depth}
                      for name, depth in self.queue_depths().items()},
            "versions": {name: self.registry.version(name)
                         for name in self.registry.names()},
        }

    # -- observability / shutdown --------------------------------------------
    def stats(self, identity: Optional[Dict[str, str]] = None
              ) -> Dict[str, dict]:
        """Per-model serving stats; ``identity`` (process/replica — the
        frontend's scrape identity) rides into every row so N workers'
        stats stay distinguishable after fleet aggregation."""
        return serving_stats(self.counters, self.latency, identity=identity)

    def queue_depths(self) -> Dict[str, int]:
        """Per-model pending-queue depth — the ``/metrics`` gauges."""
        with self._cond:
            return {name: len(q) for name, q in self._queues.items()}

    def _blackbox_inflight(self) -> List[Dict[str, object]]:
        """The forensics bundle's in-flight table: every request this
        replica holds — popped-but-unscored first, then queued — with
        rid, tenant and queue age (capped: a flooded replica's bundle
        stays readable)."""
        now = time.monotonic()

        def row(req: PendingRequest, state: str) -> Dict[str, object]:
            return {"rid": req.rid, "model": req.model,
                    "tenant": req.tenant, "state": state,
                    "age_ms": round((now - req.enqueued) * 1e3, 1)}

        with self._cond:
            rows = [row(r, "dispatching")
                    for f in self._flights for r in f.requests()]
            for q in self._queues.values():
                rows.extend(row(r, "queued") for r in q)
            for open_ in self._open.values():
                rows.extend(
                    {"rid": b.rid, "model": b.model, "tenant": b.tenant,
                     "state": "block", "rows": len(b.lines),
                     "answered": b.answered} for b in open_)
        return rows[:512]

    def close(self) -> None:
        """Flush every pending request, then stop the dispatchers.  Dead
        or wedged dispatchers cannot flush — their leftovers fail typed
        (:class:`ReplicaDownError`) instead of hanging their callers."""
        with self._cond:
            if self._stop:
                return
            self._stop = True
            self._cond.notify_all()
        deadline = time.monotonic() + 60.0
        for thread in self._threads:
            thread.join(timeout=max(deadline - time.monotonic(), 0.0))
        if self.fail_pending("batcher closed with a dead dispatcher"):
            self.failed = True
        blackbox.unregister_provider(self._bb_name)

    def __enter__(self) -> "BucketedMicrobatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
