"""The kNN deployment that serves while it scores the day's file: one resident
index, one serving plane, two classes of request on its one model
(``configs/elearn_knn_serve_backfill.json``).

Everything but the second class is ``families/knn.py``'s: the same set-up from
the seed, the same online entry (``BucketedMicrobatcher.submit``), wrappers,
counters and comparison.  Beside the online callers a thread of this file
hands the server one block of the traffic file's ``backfill`` every
``period_ms`` from the window's start — an open schedule: block *i* is due at
start + *i* x period whether or not earlier blocks are done — through the
program's own bulk entry, ``BucketedMicrobatcher.submit_block`` (what
``POST /score`` with a ``class`` calls).  The blocks are cut round and round
from a second pool of lines, made from a stream of the seed that neither the
references nor the online rows use.

**The entry check.**  The cell measures one plane answering both classes — not
a side script scoring a file past the batcher.  So before a reference row is
made ``System`` asks the program for the bulk entry and exits with status 3
and one line on standard error where there is none: a commit without it fails
the cell in seconds.

**The guarantees are part of ``correct``.**  Beside the sibling's numbers
(over the online sample AND a sample of block rows, against the same plain
reference) the comparison gets ``backfill_behind_blocks`` — blocks due by the
schedule when the window closed, less blocks replied whole by then — and
``backfill_lost_rows`` — rows of due blocks never handed in, shed, failed,
replied out of place or out of file order — with limits 2 and 0 in the
configuration's ``limits``.

The window closes with the last online reply (``lib/traffic.py``).  The
schedule stops there: the counters are read at that instant, blocks in flight
are awaited, and the calls the readers see are the window's.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from avenir_tpu import tenancy
from avenir_tpu.core.config import JobConfig
from avenir_tpu.serving.batcher import BucketedMicrobatcher
from avenir_tpu.serving.errors import ServingError
from avenir_tpu.serving.registry import ModelRegistry

from families import knn
from families.knn import MODEL, SPAN_NAMES  # noqa: F401 — run.py reads it
from lib import data

NO_ENTRY_STATUS = 3
# second words of the seed sequence: lib/data.py has 0 (references) and 1
# (online rows), run.py 2 (its sample)
_BACKFILL, _BACKFILL_SAMPLE = 3, 4
# the window has closed once no online request has been out for this long
QUIET_S = 0.1
POLL_S = 0.01
DRAIN_S = 120.0


def check_bulk_entry() -> None:
    """Exit with status 3 unless the program's batcher has the bulk entry."""
    if callable(getattr(BucketedMicrobatcher, "submit_block", None)):
        return
    print("perfbench knn_backfill: the cell hands its blocks to "
          "avenir_tpu.serving.batcher.BucketedMicrobatcher.submit_block; "
          "the program has no such entry", file=sys.stderr)
    raise SystemExit(NO_ENTRY_STATUS)


def backfill_lines(n: int, seed: int) -> List[str]:
    """``n`` rows of the day's file as CSV text (``userID`` from 3 000 000 up,
    so that no line is also an online row's), from a stream of the seed of
    their own."""
    rng = np.random.default_rng([int(seed), _BACKFILL])
    sig = data._signals(rng, n)
    ids = 3000000 + rng.integers(0, 1000000, size=n)
    return [",".join(map(str, row))
            for row in np.column_stack([ids, sig]).tolist()]


def schedule(spec: Dict, pool: Sequence[str], i: int) -> List[str]:
    """Block ``i`` of the file: ``block_rows`` lines cut from the pool, round
    and round — a function of the seed (the pool) and the traffic file."""
    rows = int(spec["block_rows"])
    per_round = len(pool) // rows
    at = (i % per_round) * rows
    return list(pool[at:at + rows])


def due_blocks(spec: Dict, opened: float, closed: float) -> int:
    """Blocks the schedule owes by ``closed``: one at ``opened`` and one every
    ``period_ms`` after."""
    return int((closed - opened) / (float(spec["period_ms"]) / 1e3)) + 1


class _WindowServable(knn._SpannedServable):
    """The sibling's wrapper, but only a call that begins inside the window
    AND carries an online row (they go first) is one of the window's.  The
    readers pair the ``score_lines`` annotations of the trace with the calls
    stamped on the host, one for one, and the trace stops with the last
    online reply: a call of block rows alone may outlast it (what is in
    flight at the close is awaited), so it gets no annotation and no stamp
    (``t0`` None) — only what it computed is kept, for the comparison.  Its
    tile is the servable's."""

    window_open = False
    file: frozenset = frozenset()         # the day's file: no online row
    tile_rows = property(lambda self: self.inner.tile_rows)

    def score_lines(self, lines: Sequence[str], pad_to: int) -> List[str]:
        t0 = t1 = None
        if self.window_open and lines[0] not in self.file:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_NAMES[0]):
                out = self.inner.score_lines(lines, pad_to)
            t1 = time.perf_counter()
        else:
            out = self.inner.score_lines(lines, pad_to)
        if self.keep:
            # a tuple of strings: the collector stops visiting it after one
            # pass, a list of 512 is walked by every full collection
            self.calls.append({"t0": t0, "t1": t1, "lines": tuple(lines),
                               "pad_to": pad_to,
                               "result": self.est.last.result})
        return out


class _Watch:
    """Which online requests are out, seen from the entry: when the window
    opened (the first request) and when it closed (the last reply, with what
    ``snapshot`` read at that instant); ``servable`` is told both."""

    def __init__(self, snapshot: Callable[[], Dict[str, float]],
                 servable: _WindowServable):
        self._snapshot = snapshot
        self._servable = servable
        self._lock = threading.Lock()
        self.out = 0
        self.opened: Optional[float] = None
        self.idle_since: Optional[float] = None
        self.at_idle: Dict[str, float] = {}
        self.last_reply = 0.0
        self.widest_gap = 0.0             # between two online replies,
        self.widest_gap_at = 0.0          # and where in the window it began
        self.started = threading.Event()

    def enter(self) -> None:
        with self._lock:
            self.out += 1
            self._servable.window_open = True
            if self.opened is None:
                self.opened = time.perf_counter()
                self.started.set()

    def leave(self) -> None:
        with self._lock:
            now = time.perf_counter()
            if self.last_reply and now - self.last_reply > self.widest_gap:
                self.widest_gap = now - self.last_reply
                self.widest_gap_at = self.last_reply - self.opened
            self.last_reply = now
            self.out -= 1
            if self.out == 0:
                self._servable.window_open = False
                self.idle_since = now
                self.at_idle = self._snapshot()

    def closed(self, now: float) -> bool:
        with self._lock:
            return (self.opened is not None and self.out == 0
                    and now - self.idle_since >= QUIET_S)


class System(knn.System):
    def __init__(self, config: Dict, seed: int, refs: Optional[int] = None):
        check_bulk_entry()
        super().__init__(config, seed, refs)
        self.servable = _WindowServable(self.servable.inner, self.est)
        self.spec: Dict = {}
        self.file: List[str] = []
        self.handed: List[Dict] = []
        self._watch = _Watch(self._counters, self.servable)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._sample: List[Dict] = []

    # -- entries -------------------------------------------------------------
    def open(self, traffic: Dict) -> Callable[[Sequence[str]], List[str]]:
        """The plane as the deployment runs it — the contracts of the two
        classes armed, the batcher as tenant ``serving`` — warmed on its
        buckets, on the tile (one block through the bulk entry) and on the
        fallback shapes; returns the ONLINE entry."""
        if traffic["entry"] != "batcher_submit" or "backfill" not in traffic:
            raise ValueError("family knn_backfill serves batcher_submit "
                             "traffic with a backfill block")
        b, self.spec = traffic["batcher"], traffic["backfill"]
        keys = self.config["tenancy"]
        tenancy.reset()
        tenancy.configure(JobConfig({k: str(v) for k, v in keys.items()}))
        at = time.perf_counter()
        self.file = backfill_lines(int(self.spec["pool_rows"]), self.seed)
        self.servable.file = frozenset(self.file)
        at = knn._phase(f"the day's file ({len(self.file)} lines)", at)
        registry = ModelRegistry().add(MODEL, self.servable)
        self.servable.warmup(int(b["serve.bucket.sizes"][0]))
        at = knn._phase("pack + upload of the index, first warm bucket", at)
        self.batcher = BucketedMicrobatcher(
            registry, bucket_sizes=b["serve.bucket.sizes"],
            flush_deadline_ms=b["serve.flush.deadline.ms"],
            queue_depth=b["serve.queue.depth"],
            request_timeout_ms=b["serve.request.timeout.ms"], warmup=True,
            tenant=keys["tenant.id"])
        at = knn._phase("batcher warm-up of its buckets", at)
        warm = self.batcher.submit_block(
            MODEL, schedule(self.spec, self.file, 0), klass=self.spec["class"])
        submit = self.batcher.submit_nowait
        for req in [submit(MODEL, line) for line in
                    self.query_pool(int(b["serve.bucket.sizes"][-1]))]:
            req.wait(DRAIN_S)             # a mixed tile among the warm ones
        warm.wait(DRAIN_S)
        knn._phase("the tile's shape and one warm block", at)
        self._warm_fallback()

        def entry(lines: Sequence[str]) -> List[str]:
            self._watch.enter()
            try:
                return [r.wait(30.0) for r in
                        [submit(MODEL, ln) for ln in lines]]
            finally:
                self._watch.leave()

        return entry

    # -- the schedule ----------------------------------------------------------
    def _run_schedule(self) -> None:
        """Hand in block i at the window's start + i x period until the
        window closes; then wait for what is in flight."""
        watch, period = self._watch, float(self.spec["period_ms"]) / 1e3
        while not watch.started.wait(POLL_S):
            if self._stop.is_set():
                return
        i = 0
        while not self._stop.is_set():
            due = watch.opened + i * period
            now = time.perf_counter()
            if now < due:
                if watch.closed(now):
                    break
                time.sleep(min(due - now, POLL_S))
                continue
            if watch.out == 0:
                # nobody is out: the window may be closing.  A block due
                # after its end is not handed in; one due before it (this
                # thread woke late) is owed, and goes in late
                if not watch.closed(now):
                    time.sleep(POLL_S / 10)
                    continue
                if due > watch.idle_since:
                    break
            rec = {"i": i, "due": due, "at": time.perf_counter(),
                   "block": None, "error": None, "replies": None}
            try:
                rec["block"] = self.batcher.submit_block(
                    MODEL, schedule(self.spec, self.file, i),
                    klass=self.spec["class"])
            except ServingError as exc:   # shed at the door: counted as lost
                rec["error"] = f"{type(exc).__name__}: {exc}"
            self.handed.append(rec)
            self._harvest(wait=False)
            i += 1
        self._harvest(wait=True)

    def _harvest(self, wait: bool) -> None:
        """Move what replied blocks brought back out of the program's
        objects (a block holds two lists of ``block_rows`` strings; what is
        kept for the comparison is one tuple): blocks are replied in order,
        so the first one still open ends the sweep unless ``wait``."""
        for rec in self.handed:
            block = rec["block"]
            if block is None:
                continue
            if not block.done() and not wait:
                return
            try:
                rec["replies"] = tuple(block.wait(DRAIN_S))
            except ServingError as exc:
                rec["error"] = f"{type(exc).__name__}: {exc}"
            rec["queued"], rec["finished"] = block.queued, block.finished
            rec["block"] = None

    def start_window(self) -> None:
        """Open the window with the benchmark process's FULL collections
        held off until it closes.  The harness keeps a record of every
        request (and the recorder of every span) for what comes after the
        window, so a full collection walks a heap that grows with the
        window: twelve passes of 58 -> 222 ms in a traced 20 s window of this
        cell (PERF.md, PR 34), each of them both dispatchers and every
        caller standing still — pauses of the yardstick's bookkeeping, which
        a server that keeps no such records does not have, and at this
        request rate enough of them to sit on the 95th percentile.  Young
        collections go on; the thresholds come back in ``end_window``."""
        super().start_window()
        gc.collect()
        self._gc_thresholds = gc.get_threshold()
        gc.set_threshold(*self._gc_thresholds[:2], 1 << 30)
        self._thread = threading.Thread(target=self._run_schedule,
                                        daemon=True, name="backfill-schedule")
        self._thread.start()

    def end_window(self) -> Dict:
        """The sibling's snapshot, cut to the window: counters as they stood
        at the last online reply, calls that began by then."""
        self._stop.set()
        self._thread.join(DRAIN_S + 30.0)
        gc.set_threshold(*self._gc_thresholds)
        snap = super().end_window()
        closed = self._watch.idle_since
        if closed is not None and self._watch.out == 0:
            snap["counters"] = {k: v - self._base.get(k, 0.0)
                                for k, v in self._watch.at_idle.items()}
        snap["calls"] = [c for c in snap["calls"] if c["t0"] is not None]
        return snap

    def close(self) -> None:
        super().close()
        tenancy.reset()

    # -- after the window ------------------------------------------------------
    def _delivery(self) -> Dict[str, float]:
        """The schedule's account at the window's close."""
        rows = int(self.spec["block_rows"])
        opened, closed = self._watch.opened, self._watch.idle_since
        if opened is None or closed is None:
            return {"backfill_behind_blocks": float("inf"),
                    "backfill_lost_rows": float("inf")}
        due = due_blocks(self.spec, opened, closed)
        owed = {rec["i"]: rec for rec in self.handed if rec["i"] < due}
        lost, whole_by_close, last = 0, 0, 0.0
        for i in range(due):
            rec = owed.get(i)
            if rec is None or rec["error"] is not None \
                    or rec["replies"] is None:
                lost += rows
                continue
            lines, replies = schedule(self.spec, self.file, i), rec["replies"]
            bad = sum(1 for line, reply in zip(lines, replies)
                      if not (isinstance(reply, str)
                              and reply.startswith(line + ",")))
            bad += abs(len(replies) - len(lines))
            if rec["finished"] < last:
                bad = rows                # replied before an earlier block
            last = max(last, rec["finished"])
            lost += bad
            whole_by_close += bad == 0 and rec["finished"] <= closed
        late = [rec["at"] - rec["due"] for rec in owed.values()]
        done = [rec["finished"] - rec["queued"] for rec in owed.values()
                if rec["replies"] is not None]
        return {"backfill_behind_blocks": float(due - whole_by_close),
                "backfill_lost_rows": float(lost),
                "backfill_due_blocks": float(due),
                "backfill_rows_per_s": rows * whole_by_close
                / max(closed - opened, 1e-9),
                "backfill_handed_late_ms_p50":
                    1e3 * float(np.median(late)) if late else 0.0,
                "backfill_handed_late_ms_max": 1e3 * max(late, default=0.0),
                "backfill_block_ms_max": 1e3 * max(done, default=0.0),
                # a process-wide stall shows here in an untraced run too
                "online_reply_gap_ms_max": 1e3 * self._watch.widest_gap,
                "online_reply_gap_at_s": self._watch.widest_gap_at}

    def _backfill_sample(self) -> List[Dict]:
        """``check_rows`` block rows drawn from the seed among the blocks
        replied whole (the last block's last row always among them), each
        with its line and its reply."""
        whole = [rec for rec in self.handed
                 if rec["error"] is None and rec["replies"] is not None]
        if not whole:
            return []
        rows = int(self.spec["block_rows"])
        rng = np.random.default_rng([int(self.seed), _BACKFILL_SAMPLE])
        total = rows * len(whole)
        flat = rng.choice(total, size=min(int(self.spec["check_rows"]), total),
                          replace=False)
        flat[0] = total - 1
        return [{"line": schedule(self.spec, self.file,
                                  whole[f // rows]["i"])[f % rows],
                 "reply": whole[f // rows]["replies"][f % rows]}
                for f in sorted(set(int(x) for x in flat))]

    def produced(self, requests: Sequence[Dict]) -> Dict:
        self._sample = self._backfill_sample()
        return super().produced(list(requests) + self._sample)

    def check(self, requests: Sequence[Dict], produced: Dict,
              precision: str = "f32") -> Dict[str, float]:
        numbers = super().check(list(requests) + self._sample, produced,
                                precision)
        numbers.update(self._delivery())
        numbers["backfill_checked_rows"] = float(len(self._sample))
        return numbers
