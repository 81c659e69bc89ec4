"""Run-scoped structured tracing — spans, the process tracer, and the
generalized recompile monitor.

One trace id per run, one span per unit of work: ``Pipeline.run`` opens a
root span, each stage/job/chunk/dispatch/serving-request opens a child, and
every open/close is journaled (``telemetry/journal.py``) so a slow or
wedged run reads as ONE tree (``python -m avenir_tpu.telemetry <journal>``)
instead of five unrelated artifacts.  Design constraints:

- **off by default is free**: the process :class:`Tracer` is a no-op until
  ``trace.on`` enables it or a JAX profiler session starts — ``span()``
  otherwise returns a shared inert span object, so the hot paths pay one
  attribute check, one static call and no allocation (asserted against
  the published nb_mi band; measured in
  ``benchmarks/telemetry_overhead.py``).
- **one span, three sinks**: a live span is journaled when ``trace.on``
  is set; while a profiler session runs
  (``jax.profiler.TraceAnnotation.is_enabled()``) it also enters a
  ``TraceAnnotation`` of its name, so it lands on the host plane of the
  xplane on the trace's own clock beside ``XLA Ops``; and either way it
  is appended at close to a bounded in-memory recorder
  (:meth:`Tracer.recorded`) stamped with ``time.perf_counter()`` — the
  clock a benchmark's own host records use.
- **contextvar propagation**: the current span rides a ``contextvars``
  variable, so nesting needs no plumbing and concurrent threads never
  share a current span.  Work that *crosses* threads (DeviceFeeder
  workers, the serving dispatch thread) captures the submitting context
  explicitly and emits its spans retroactively (:meth:`Tracer.emit_span`)
  with that parent — the seam that lets a serving request join the
  pipeline trace through the ScoringPlane stage.
- **honest wall times**: JAX dispatch is async, so a span measuring
  device work registers its output via :meth:`Span.block_on` and the
  close performs the host fetch through the existing
  ``profiling.device_sync`` discipline (one host read per shard).
- **single-writer journal SHARDS** (GraftFleet, round 15): in
  multi-process runs every process journals to its OWN shard
  (``run-<id>.proc-<k>.jsonl``, each single-writer under its own
  FileLock) instead of process 0 journaling and the workers dropping
  their spans; serving replicas and fleet workers that are not
  jax-distributed get the same treatment via ``trace.writer.suffix``.
  Every event is stamped with ``proc``/``host`` (and ``replica`` when a
  suffix is set), all shards share one conf-derived run id and root
  trace id, and ``python -m avenir_tpu.telemetry merge <dir>``
  time-orders the shards into one fleet view.

:class:`CompileKeyMonitor` generalizes the serving batcher's compile-key
diff (round 9) so *batch* chunk loops get the same measured ``recompiles``
counter: feed each dispatch's shape/compile keys through ``observe`` and
any key outside the primed set increments the counter and journals a
``recompile`` event.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import random
import sys
import threading
import time
from collections import deque
from typing import (Any, Deque, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional)

from avenir_tpu.telemetry import blackbox as _blackbox
from avenir_tpu.telemetry.journal import Journal

_CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "avenir_tpu_current_span", default=None)

# the in-memory recorder keeps this many closed spans and drops the oldest
# beyond it (a 20 s serving window at 6 000 requests/s makes ~146 k: PR 33)
RECORDER_CAPACITY = 1 << 19


class SpanRecord(NamedTuple):
    """One closed span as the recorder keeps it; ``start``/``end`` are
    ``time.perf_counter()`` seconds."""

    name: str
    start: float
    end: float
    span_id: str
    parent_id: Optional[str]
    trace_id: str
    thread: int
    attrs: Dict[str, Any]


_ANNOTATION = None      # jax.profiler.TraceAnnotation, bound on first use


def profiler_session() -> bool:
    """True while a JAX profiler session is running.  jax is looked up,
    never imported here: a process that has not imported it has no
    session (the journal CLI stays stdlib-only)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION.is_enabled()

# GraftPool (round 18): ambient journal labels.  A tenant's workload runs
# under ``label_scope(tenant=...)`` and EVERY event emitted from inside —
# span opens/closes, counter snapshots, gauges, recompiles, sheds — is
# stamped with the label at emit time, so one merged fleet journal
# attributes every span and every shed to its tenant without per-seam
# plumbing.  Independent of ``trace.on``: the tenancy arbiter reads the
# ambient ``tenant`` label even when nothing journals.
_LABELS: "contextvars.ContextVar[Optional[Dict[str, Any]]]" = \
    contextvars.ContextVar("avenir_tpu_trace_labels", default=None)


def current_labels() -> Dict[str, Any]:
    """A copy of the ambient label set ({} outside any scope)."""
    return dict(_LABELS.get() or {})


def current_label(key: str) -> Optional[Any]:
    """One ambient label (no dict copy — the arbiter's hot-path read)."""
    labels = _LABELS.get()
    return labels.get(key) if labels else None


@contextlib.contextmanager
def label_scope(**labels) -> Iterator[None]:
    """Attach journal labels to everything emitted inside the scope.
    Scopes nest (inner wins on a shared key); ``None`` values are
    dropped, so ``label_scope(tenant=conf.get("tenant.id"))`` is a
    no-op scope when the conf names no tenant."""
    live = {k: v for k, v in labels.items() if v is not None}
    merged = {**(_LABELS.get() or {}), **live}
    token = _LABELS.set(merged)
    try:
        yield
    finally:
        _LABELS.reset(token)


class Span:
    """One unit of work: identity (trace/span/parent ids), a name, attrs,
    and wall times.  Mutate attrs via :meth:`set`; register async device
    output via :meth:`block_on` so the close time is honest."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "attrs", "ts", "_t0", "dur_ms", "status", "_pending")

    def __init__(self, tracer: "Tracer", trace_id: str, span_id: str,
                 parent_id: Optional[str], name: str,
                 attrs: Optional[Dict[str, Any]]):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.attrs: Dict[str, Any] = dict(attrs or {})
        self.ts = time.time()
        self._t0 = time.perf_counter()
        self.dur_ms: Optional[float] = None
        self.status = "ok"
        self._pending = None

    @property
    def enabled(self) -> bool:
        return True

    @property
    def start(self) -> float:
        """``time.perf_counter()`` at open."""
        return self._t0

    def set(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def block_on(self, value):
        """Register the span's device output; host-synced at close so the
        recorded duration covers the compute, not just the dispatch."""
        self._pending = value
        return value

    def event(self, ev: str, **fields) -> None:
        """Journal an event carrying this span's identity."""
        self.tracer._journal_emit(ev, trace=self.trace_id,
                                  span=self.span_id, **fields)

    def _close(self) -> float:
        if self._pending is not None:
            from avenir_tpu.utils.profiling import device_sync

            device_sync(self._pending)
            self._pending = None
        end = time.perf_counter()
        self.dur_ms = (end - self._t0) * 1e3
        return end


class _NoopSpan:
    """The shared inert span handed out while tracing is off — every
    operation is a no-op, so instrumented code needs no ``if`` guards."""

    __slots__ = ()
    enabled = False
    trace_id = span_id = parent_id = None
    attrs: Dict[str, Any] = {}

    def set(self, key, value):
        return self

    def block_on(self, value):
        return value

    def event(self, ev, **fields):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


# ids come from a generator seeded from the system's entropy once a process
# (again in a forked child), not from a system call an id: a root span pays
# for its trace id on the hot path, and a sandboxed host charges tens of
# microseconds for ``os.urandom`` (my chip call, PR 27: 64 root spans cost a
# dispatch 5 ms)
_IDS = random.Random()
os.register_at_fork(after_in_child=_IDS.seed)


def _new_id(prefix: str) -> str:
    return f"{prefix}{_IDS.getrandbits(48):012x}"


class Tracer:
    """Process-wide span factory + journal front.  ``enabled`` is the
    journal's switch (:meth:`enable`; ``configure(conf)`` wires it from
    ``trace.*`` keys); spans are live while it is set OR while a JAX
    profiler session runs, and free otherwise."""

    def __init__(self, capacity: int = RECORDER_CAPACITY):
        self.enabled = False
        self.journal: Optional[Journal] = None
        # closed live spans, oldest dropped (and counted) at capacity
        self._recorded: Deque[SpanRecord] = deque(maxlen=int(capacity))
        self._rec_lock = threading.Lock()
        self.dropped = 0
        self._seq = itertools.count(1)           # thread-safe in CPython
        self._lock = threading.Lock()
        self._once: set = set()                  # event_once keys, per journal
        # GraftFleet identity (round 15): the journal stamp every event
        # carries, the span-id prefix that keeps ids unique across a
        # fleet's shards, and the shared root trace id that makes a
        # multi-process run ONE trace in the merged view
        self.stamp: dict = {}
        self.process_index = 0
        self.writer_suffix = ""
        self._span_prefix = ""
        self._root_trace: Optional[str] = None

    # -- lifecycle -----------------------------------------------------------
    def enable(self, journal_dir: Optional[str] = None,
               max_bytes: int = 64 << 20, run_id: Optional[str] = None,
               suffix: str = "", tenant: str = "") -> "Tracer":
        """Turn tracing on; with ``journal_dir``, open the run journal
        there (single-writer, rotation-bounded).

        Plain form (no ``run_id``/``suffix``, process 0): the legacy
        ``run-<random>.jsonl`` single-process journal.  Fleet form — a
        shared ``run_id`` (every process of a run must agree; ``configure``
        derives it from the conf), a ``suffix`` naming a replica/worker
        that is not jax-distributed, or a non-zero ``jax.process_index()``
        — opens this writer's SHARD ``run-<id>.proc-<k>[-<suffix>].jsonl``,
        stamps every event with ``proc``/``host``/``replica``, prefixes
        span ids with the writer identity (ids stay unique across the
        merged fleet view), and roots new traces at the run-derived trace
        id so all shards share ONE trace."""
        proc = 0
        try:
            import jax

            proc = jax.process_index()
        except Exception:                          # pragma: no cover
            pass
        import socket

        with self._lock:
            if self.enabled:
                return self
            self.process_index = proc
            self.writer_suffix = suffix or ""
            self.stamp = {"proc": proc, "host": socket.gethostname()}
            if suffix:
                self.stamp["replica"] = suffix
            if tenant:
                # GraftPool (round 18): a process dedicated to one tenant
                # (tenant.id in its conf) stamps every record — the
                # multi-process twin of the in-process label_scope
                self.stamp["tenant"] = tenant
            fleet = bool(run_id) or bool(suffix) or proc != 0
            if fleet:
                writer = f"proc-{proc}" + (f"-{suffix}" if suffix else "")
                name = f"run-{run_id or _new_id('')}.{writer}.jsonl"
                self._span_prefix = f"p{proc}" + \
                    (f"-{suffix}" if suffix else "") + "."
                self._root_trace = f"t{run_id}" if run_id else None
            else:
                name = f"run-{_new_id('')}.jsonl"
                self._span_prefix = ""
                self._root_trace = None
            if journal_dir:
                self.journal = Journal(os.path.join(journal_dir, name),
                                       max_bytes=max_bytes,
                                       stamp=self.stamp)
            self._once.clear()                   # fresh journal, fresh onces
            self.enabled = True
        return self

    def disable(self) -> None:
        """Turn tracing off and close the journal (tests, run teardown).
        The profiler flushes its cumulative program.profile totals into
        the journal FIRST (its accounting rides this journal), then drops
        its state — the two planes share one lifecycle."""
        from avenir_tpu.telemetry import profile as _profile

        prof = _profile.profiler()
        prof.flush()
        prof.disable()
        with self._lock:
            self.enabled = False
            self._once.clear()
            self._span_prefix = ""
            self._root_trace = None
            self.writer_suffix = ""
            self.stamp = {}
            if self.journal is not None:
                self.journal.close()
                self.journal = None

    @property
    def journal_path(self) -> Optional[str]:
        return self.journal.path if self.journal is not None else None

    # -- span factory --------------------------------------------------------
    def current(self) -> Optional[Span]:
        """The context's live span (cross-thread parent capture), or None
        when tracing is off or no span is open."""
        return _CURRENT.get() if self.enabled else None

    def span(self, name: str, attrs: Optional[Dict[str, Any]] = None,
             parent: Optional[Span] = None):
        """Open a child of the context's current span (or of ``parent``
        when crossing a thread); a span with no parent roots a new trace.
        With no journal and no profiler session: returns the shared NOOP
        span directly — one attribute check, one static call, no
        generator frame, no allocation (the off-is-free contract;
        benchmarks/telemetry_overhead.py)."""
        if not self.enabled and not profiler_session():
            return NOOP_SPAN
        return self._live_span(name, attrs, parent)

    @contextlib.contextmanager
    def _live_span(self, name: str, attrs: Optional[Dict[str, Any]],
                   parent: Optional[Span]) -> Iterator[Span]:
        up = parent if parent is not None else _CURRENT.get()
        trace_id = (up.trace_id if up is not None
                    else self._root_trace or _new_id("t"))
        sp = Span(self, trace_id, self._next_span_id(),
                  up.span_id if up is not None else None, name, attrs)
        token = _CURRENT.set(sp)
        # the sinks are chosen at open and hold to the close, whatever
        # starts or stops meanwhile
        journaled = self.enabled
        if journaled:
            self._journal_emit("span.open", trace=sp.trace_id,
                               span=sp.span_id, parent=sp.parent_id,
                               name=sp.name, attrs=sp.attrs)
        annotation = _ANNOTATION(name) if profiler_session() else None
        if annotation is not None:
            annotation.__enter__()
        try:
            yield sp
        except BaseException as exc:
            sp.status = f"error:{type(exc).__name__}"
            raise
        finally:
            _CURRENT.reset(token)
            end = sp._close()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            self._record(SpanRecord(
                sp.name, sp.start, end, sp.span_id, sp.parent_id,
                sp.trace_id, threading.get_ident(), sp.attrs))
            if journaled:
                self._journal_emit("span.close", trace=sp.trace_id,
                                   span=sp.span_id, name=sp.name,
                                   dur_ms=round(sp.dur_ms, 3),
                                   status=sp.status, attrs=sp.attrs)

    def emit_span(self, name: str, dur_s: float,
                  parent: Optional[Span] = None,
                  attrs: Optional[Dict[str, Any]] = None,
                  status: str = "ok",
                  start: Optional[float] = None) -> None:
        """Retroactively emit a completed span — the cross-thread form
        (feeder workers, the serving dispatcher) where the work finished
        on a thread that never held the submitting context.  ``start``
        (``time.perf_counter()`` seconds) keeps the span's real interval
        when it was measured elsewhere; without it the span ends now.
        A retroactive span goes to the recorder and, under ``trace.on``,
        to the journal — never to the profiler's trace: a ``TraceMe``
        cannot be back-dated."""
        if not self.enabled and not profiler_session():
            return
        trace_id = (parent.trace_id if parent is not None
                    else self._root_trace or _new_id("t"))
        span_id = self._next_span_id()
        parent_id = parent.span_id if parent else None
        now = time.perf_counter()
        if start is None:
            start = now - dur_s
        self._record(SpanRecord(name, start, start + dur_s, span_id,
                                parent_id, trace_id, threading.get_ident(),
                                dict(attrs or {})))
        if not self.enabled:
            return
        ts = time.time() - (now - start - dur_s)      # wall time of the end
        self._journal_emit("span.open", trace=trace_id, span=span_id,
                           parent=parent_id,
                           name=name, attrs=dict(attrs or {}), ts=ts - dur_s)
        self._journal_emit("span.close", trace=trace_id, span=span_id,
                           name=name, dur_ms=round(dur_s * 1e3, 3),
                           status=status, attrs=dict(attrs or {}), ts=ts)

    # -- the in-memory recorder ----------------------------------------------
    def _record(self, rec: SpanRecord) -> None:
        with self._rec_lock:
            if len(self._recorded) == self._recorded.maxlen:
                self.dropped += 1
            self._recorded.append(rec)

    def recorded(self, clear: bool = False) -> List[SpanRecord]:
        """A copy of the closed spans the recorder holds, in the order
        they closed; ``clear=True`` also empties it (``dropped`` counts
        on)."""
        with self._rec_lock:
            out = list(self._recorded)
            if clear:
                self._recorded.clear()
        return out

    def _next_span_id(self) -> str:
        """Fleet-unique span id: the writer prefix (``p<k>[-<suffix>].``,
        empty single-process) plus the process-local sequence — two
        shards of one run can never collide on a span id in the merged
        view."""
        return f"{self._span_prefix}s{next(self._seq)}"

    # -- journal shorthands --------------------------------------------------
    def _journal_emit(self, ev: str, **fields) -> None:
        # GraftBox: every journaled event also lands in the always-on
        # flight ring (a dead process's last moments survive the journal's
        # file buffer); copied because the labels/ts mutation below would
        # otherwise alias the ring's stored record
        _blackbox.ring_record(ev, dict(fields))
        if self.journal is not None:
            ts = fields.pop("ts", None)
            if ts is not None:
                # retroactive events carry their own timestamp
                fields["at"] = round(ts, 6)
            labels = _LABELS.get()
            if labels:
                # ambient labels (GraftPool tenant attribution) ride every
                # record; an explicit field of the same name wins
                for key, value in labels.items():
                    fields.setdefault(key, value)
            self.journal.emit(ev, **fields)

    def event(self, ev: str, **fields) -> None:
        """Journal a free event stamped with the current span's identity
        (if any) — checkpoint saves, canary readings, stage skips."""
        if not self.enabled:
            # GraftBox: the flight ring records this seam even with
            # tracing off (the kwargs dict is fresh per call — safe to
            # keep without a copy); the journal still sees nothing
            _blackbox.ring_record(ev, fields)
            return
        cur = _CURRENT.get()
        if cur is not None:
            fields.setdefault("trace", cur.trace_id)
            fields.setdefault("span", cur.span_id)
        self._journal_emit(ev, **fields)

    def event_once(self, ev: str, key, **fields) -> None:
        """Journal an event at most once per journal per ``(ev, key)`` —
        for run-identity facts (e.g. ``shard.topology``) that several
        seams may announce; later duplicates are dropped, and a run
        carrying genuinely distinct facts (different keys) journals each."""
        if not self.enabled:
            _blackbox.ring_record(ev, fields)   # ring only; no once-latch
            return
        with self._lock:
            if (ev, key) in self._once:
                return
            self._once.add((ev, key))
        self.event(ev, **fields)

    def counters(self, scope: str, counters) -> None:
        """Journal a named counter snapshot (the CLI renders per-scope
        deltas between successive snapshots)."""
        if not self.enabled:
            return
        self.event("counters", scope=scope, groups=counters.as_dict())

    def gauge(self, name: str, value: float) -> None:
        """Journal a point-in-time gauge reading (queue depths)."""
        if not self.enabled:
            _blackbox.ring_record("gauge", {"name": name, "value": value})
            return
        self.event("gauge", name=name, value=value)


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process tracer (disabled, hence free, until configured)."""
    return _TRACER


def fleet_run_id(conf) -> str:
    """The fleet-shared run identity every journal shard of one run
    carries: ``trace.run.id`` when set, else a fingerprint of the conf's
    workload properties.  Observability knobs (``trace.*``, ``profile.*``,
    ``slo.*`` — including the per-replica ``trace.writer.suffix``) are
    EXCLUDED: two replicas differing only in their writer suffix, or a
    relaunch that turns profiling on, must land in the same run's shard
    set.  Distinct from ``StreamCheckpointer.run_id_from_conf`` (which
    keeps these keys — a checkpoint's identity is stricter than a
    journal's)."""
    explicit = conf.get("trace.run.id")
    if explicit:
        return explicit
    import hashlib

    drop = ("trace.", "profile.", "slo.", "telemetry.")
    stable = sorted(
        (k, v) for k, v in conf.props.items()
        if not any((k[len(conf.prefix) + 1:] if k.startswith(
            conf.prefix + ".") else k).startswith(p) for p in drop))
    return hashlib.blake2s(repr(stable).encode(),
                           digest_size=6).hexdigest()


def configure(conf) -> Tracer:
    """Enable the process tracer from ``trace.*`` config keys; a no-op —
    and one dict lookup — when ``trace.on`` is unset.

    GraftFleet (round 15): EVERY process of a multi-process run gets an
    enabled tracer writing its own journal shard (previously workers'
    spans were silently dropped by a process-0-only gate).  All shards of
    one run share a conf-derived run id (``fleet_run_id``) and root trace
    id, so ``telemetry merge`` + the span-tree CLI render the fleet as
    ONE trace with per-process attribution.  Single-machine replica
    pools and fleet workers that are not jax-distributed opt into the
    same sharding with ``trace.writer.suffix`` (each writer suffix is a
    distinct shard + ``replica`` stamp).  Idempotent: a pipeline and the
    jobs it runs all call this with the same conf; the first enable wins.

    GraftProf (round 14) rides the same entry point: ``profile.on`` is
    checked here too, so every seam that configures tracing — driver,
    jobs, the serving CLI — configures the device-cost profiler from the
    same conf (one dict lookup when off)."""
    from avenir_tpu.telemetry import profile as _profile

    _profile.configure(conf)
    # GraftBox rides the same entry point: blackbox.dir arms the
    # forensics bundle writer and blackbox.watchdog.sec the progress
    # watchdog INDEPENDENTLY of trace.on — crash forensics must not
    # require tracing (a few dict lookups when unset)
    _blackbox.configure(conf)
    t = _TRACER
    if not conf.get_bool("trace.on", False) or t.enabled:
        return t
    nprocs = 1
    try:
        import jax

        nprocs = jax.process_count()
    except Exception:                              # pragma: no cover
        pass
    # GraftPool (round 18): a tenant-dedicated process (tenant.id) shards
    # its journal like a replica — the tenant names the writer suffix when
    # no explicit one is set — and stamps every record with the tenant, so
    # a merged fleet view attributes each shard's events without parsing
    # filenames.  In-process multi-tenant runs use label_scope instead.
    tenant = conf.get("tenant.id", "") or ""
    # GlobalServe (this round): a launcher-spawned serving worker gets its
    # shard suffix via AVENIR_WRITER_SUFFIX (the launch env contract) when
    # the conf file — shared by the whole fleet — can't name one per
    # process; an explicit conf key still wins, then the env, then the
    # tenant id.
    suffix = (conf.get("trace.writer.suffix", "")
              or os.environ.get("AVENIR_WRITER_SUFFIX", "")
              or tenant)
    fleet = nprocs > 1 or bool(suffix) or bool(conf.get("trace.run.id"))
    max_mb = conf.get_float("telemetry.journal.max.mb", 64.0)
    t.enable(conf.get("trace.journal.dir") or ".",
             max_bytes=int(max_mb * (1 << 20)),
             run_id=fleet_run_id(conf) if fleet else None,
             suffix=suffix, tenant=tenant)
    return t


class CompileKeyMonitor:
    """The serving batcher's compile-key diff, generalized (this round) so
    every dispatch loop — batch chunk streams included — publishes a
    measured ``recompiles`` counter instead of assuming shape stability.

    ``prime`` registers expected keys (serving warmup; a stream's first
    chunk) without counting; ``observe`` counts any key outside the known
    set as a recompile, increments ``<group>::recompiles`` and journals a
    ``recompile`` event carrying the fresh keys.  With ``auto_prime`` the
    first observation primes instead of counting — the batch-stream mode,
    where the first chunk's compile is the expected one and only
    *subsequent* fresh shapes (e.g. a ragged tail chunk) are noteworthy.

    GraftProf (round 14): every key that enters the known set — primed or
    observed — is also registered with the
    :class:`~avenir_tpu.telemetry.profile.CompiledProgramRegistry` under
    this monitor's scope, so the seams that already feed the recompile
    diff (batch chunk streams, stream panes, the serving batcher)
    populate the compiled-program table for free: one ``program.compiled``
    event per distinct key, recompile-monitor parity by construction (a
    ragged tail chunk is one recompile AND one extra program)."""

    def __init__(self, counters=None, group: str = "Telemetry",
                 scope: str = "", auto_prime: bool = False):
        self.counters = counters
        self.group = group
        self.scope = scope
        self.auto_prime = auto_prime
        self._known: set = set()
        self._primed = False
        # the serving batcher observes from two dispatcher threads: a key
        # is fresh to exactly one of them
        self._lock = threading.Lock()

    def prime(self, keys: Iterable) -> None:
        keys = set(keys)
        with self._lock:
            self._known |= keys
            self._primed = True
        self._register_programs(keys)

    def _register_programs(self, keys) -> None:
        """Feed keys entering the known set to the program registry (one
        attribute check when profiling is off)."""
        from avenir_tpu.telemetry import profile as _profile

        prof = _profile.profiler()
        if prof.enabled:
            for key in keys:
                prof.observe(key, site=self.scope or self.group)

    @staticmethod
    def shape_key(*arrays) -> tuple:
        """A dispatch-shape key for array operands: (shape, dtype) per
        operand — a fresh one implies a fresh XLA compile of the jitted
        step consuming them."""
        return tuple((tuple(a.shape), str(a.dtype))
                     for a in arrays if a is not None)

    def observe(self, keys: Iterable) -> int:
        """Fold ``keys`` into the known set; returns (and accounts) how
        many were fresh."""
        with self._lock:
            fresh = set(keys) - self._known
            if not fresh:
                return 0
            self._known |= fresh
            first = self.auto_prime and not self._primed
            self._primed = True
        self._register_programs(fresh)
        if first:
            return 0
        if self.counters is not None:
            self.counters.increment(self.group, "recompiles", len(fresh))
        _TRACER.event("recompile", scope=self.scope,
                      keys=sorted(repr(k) for k in fresh))
        return len(fresh)
