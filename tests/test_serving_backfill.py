"""Two classes of request on one model (PR 34): single rows through
``submit`` and whole blocks through ``submit_block``.

What is pinned: online rows go first in every dispatch and block rows fill
the rest of the tile; block rows go alone only when the model has nothing in
flight; a block advances under always-full online buckets; per-class bounds
(a block never counts against ``serve.queue.depth``, an online shed never
touches a block, a class's own ``queue.depth`` sheds blocks); blocks are
replied whole and in the order handed in whichever dispatch comes back
first; ``close`` drains them, a dead replica fails them; and — on the real
kNN servable over seeded data — a row's reply, neighbours and distances are
the same bits whichever way it travelled, and agree with the benchmark's
plain reference.
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from avenir_tpu import tenancy
from avenir_tpu.core.config import JobConfig
from avenir_tpu.serving import (
    BucketedMicrobatcher,
    ModelRegistry,
    ReplicaDownError,
    RequestError,
    RequestTimeout,
    ScoreHTTPServer,
    ServableModel,
    ShedError,
)
from avenir_tpu.serving.errors import TenantShedError
from avenir_tpu.telemetry import spans as tel
from avenir_tpu.telemetry.journal import read_events
from avenir_tpu.utils.retry import FaultPlan

BUCKET, TILE = 4, 16
ROOM = TILE - BUCKET


class _TileServable(ServableModel):
    """Sweeps ``TILE`` rows whatever it is handed.  Gated: ``score_lines``
    blocks until the test releases the call by its index; not gated: it
    sleeps ``hold_s``."""

    family = "tile"
    tile_rows = (TILE,)

    def __init__(self, gated=True, hold_s=0.0):
        super().__init__()
        self.gated, self.hold_s = gated, hold_s
        self.calls, self.pads, self.gates = [], [], []
        self.entered = threading.Semaphore(0)
        self._lock = threading.Lock()

    def score_lines(self, lines, pad_to):
        gate = threading.Event()
        with self._lock:
            self.calls.append(list(lines))
            self.pads.append(pad_to)
            self.gates.append(gate)
        self.entered.release()
        if self.gated:
            assert gate.wait(20.0), "the test never released this call"
        elif self.hold_s:
            time.sleep(self.hold_s)
        if any(line == "BAD" for line in lines):
            raise RequestError("a bad row")
        self.compile_keys.add((pad_to,))
        return [f"{line},ok" for line in lines]

    def warmup(self, pad_to):
        self.compile_keys.add((pad_to,))

    def wait_entered(self, timeout=5.0):
        return self.entered.acquire(timeout=timeout)

    def release(self, call):
        self.gates[call].set()


def _plane(gated=True, hold_s=0.0, **kwargs):
    servable = _TileServable(gated, hold_s)
    kwargs.setdefault("request_timeout_ms", 20_000.0)
    batcher = BucketedMicrobatcher(
        ModelRegistry().add("m", servable), bucket_sizes=(1, 2, BUCKET),
        flush_deadline_ms=5.0, **kwargs)
    return servable, batcher


def _rows(tag, n):
    return [f"{tag}{i}" for i in range(n)]


def _online(batcher, tag, n):
    return [batcher.submit_nowait("m", line) for line in _rows(tag, n)]


def _close(servable, batcher):
    closer = threading.Thread(target=batcher.close)
    closer.start()
    deadline = time.monotonic() + 20.0
    while closer.is_alive() and time.monotonic() < deadline:
        for gate in list(servable.gates):
            gate.set()
        time.sleep(0.005)
    closer.join(5.0)
    assert not closer.is_alive()


@pytest.fixture(autouse=True)
def _no_pool():
    tenancy.reset()
    yield
    tenancy.reset()


@pytest.fixture
def traced(tmp_path):
    tracer = tel.tracer().enable(str(tmp_path))
    try:
        yield tracer
    finally:
        tel.tracer().disable()


# -- the take ---------------------------------------------------------------------

def test_block_rows_alone_go_in_whole_tiles_one_dispatch_at_a_time():
    servable, b = _plane()
    block = b.submit_block("m", _rows("f", 2 * TILE + 3), klass="backfill")
    assert servable.wait_entered()
    time.sleep(0.05)                      # the second thread is free and
    assert len(servable.calls) == 1       # takes nothing: one at a time
    assert servable.calls[0] == _rows("f", TILE) and servable.pads == [TILE]
    servable.release(0)
    assert servable.wait_entered()
    servable.release(1)
    assert servable.wait_entered()
    assert servable.calls[2] == _rows("f", 2 * TILE + 3)[2 * TILE:]
    assert servable.pads[2] == TILE       # the file's tail: the same shape
    assert not block.done()
    servable.release(2)
    assert block.wait(5.0) == [f"{r},ok" for r in _rows("f", 2 * TILE + 3)]
    got = b.counters.as_dict()["Serving.m"]
    assert got["backfill_rows"] == 2 * TILE + 3
    assert got["backfill_only"] == 3 and got["batches"] == 3
    assert got["backfill_blocks"] == 1 and "requests" not in got
    _close(servable, b)


def test_online_request_behind_three_waiting_blocks_rides_the_next_dispatch():
    servable, b = _plane()
    blocks = [b.submit_block("m", _rows(tag, 3 * TILE), klass="backfill")
              for tag in "xyz"]
    assert servable.wait_entered()        # block x's first tile is on its way
    lone = b.submit_nowait("m", "q")
    time.sleep(0.03)                      # past the flush deadline: a short
    assert len(servable.calls) == 1       # bucket waits for the flight
    servable.release(0)
    assert servable.wait_entered()
    assert servable.calls[1][0] == "q"                     # first in the tile
    assert servable.calls[1][1:] == _rows("x", 3 * TILE)[TILE:2 * TILE - 1]
    servable.release(1)
    assert lone.wait(5.0) == "q,ok"
    assert not any(blk.done() for blk in blocks)           # 8 tiles to go
    _close(servable, b)
    assert [blk.wait(5.0)[0] for blk in blocks] == ["x0,ok", "y0,ok", "z0,ok"]


def test_full_online_bucket_overlaps_a_tile_of_block_rows():
    """A full bucket is always ready: it is taken by the free thread while
    block rows alone are in flight, and takes block rows along."""
    servable, b = _plane()
    block = b.submit_block("m", _rows("f", 3 * TILE), klass="backfill")
    assert servable.wait_entered()
    reqs = _online(b, "o", BUCKET)
    assert servable.wait_entered()
    assert servable.calls[1] == _rows("o", BUCKET) + \
        _rows("f", 3 * TILE)[TILE:TILE + ROOM]
    assert servable.pads[1] == TILE
    servable.release(1)                   # the later dispatch comes back first
    assert [r.wait(5.0) for r in reqs] == [f"o{i},ok" for i in range(BUCKET)]
    assert not block.done()
    _close(servable, b)
    assert block.wait(5.0) == [f"{r},ok" for r in _rows("f", 3 * TILE)]
    got = b.counters.as_dict()["Serving.m"]
    assert got["requests"] == BUCKET and got["backfill_only"] == got["batches"] - 1


def test_fill_takes_the_cheapest_tile_and_widens_while_a_backlog_waits():
    """One block waiting: its rows fill what the online rows leave of the
    servable's FIRST tile.  A second block handed in before the first is
    taken whole: the LAST tile, until one block is left again.  Both
    shapes are warmed by the first block, and a dispatch is padded to the
    smallest tile that holds it."""
    servable, b = _plane()
    wide = 2 * TILE
    servable.tile_rows = (TILE, wide)
    with b._cond:
        first = b.submit_block("m", _rows("f", 2 * TILE), klass="backfill")
        _online(b, "o", BUCKET)
    assert {(TILE,), (wide,)} <= servable.compile_keys
    assert servable.wait_entered()        # on pace: the cheapest tile
    assert servable.calls[0] == _rows("o", BUCKET) + _rows("f", ROOM)
    assert servable.pads == [TILE]
    with b._cond:
        second = b.submit_block("m", _rows("g", wide), klass="backfill")
        _online(b, "p", BUCKET)
    assert servable.wait_entered()        # behind: the widest, over both
    assert servable.calls[1] == _rows("p", BUCKET) + \
        _rows("f", 2 * TILE)[ROOM:] + _rows("g", wide - 2 * BUCKET - TILE)
    assert servable.pads[1] == wide
    _online(b, "q", BUCKET)
    servable.release(0)
    assert servable.wait_entered()        # one block left: the cheapest again
    assert len(servable.calls[2]) == TILE and servable.pads[2] == TILE
    _close(servable, b)
    assert first.wait(5.0) == [f"{r},ok" for r in _rows("f", 2 * TILE)]
    assert second.wait(5.0) == [f"{r},ok" for r in _rows("g", wide)]
    assert set(servable.pads) == {TILE, wide}


def test_no_block_waiting_dispatches_as_before():
    """With no block the plane compiles and dispatches what it did: buckets
    of the ladder, no tile shape warmed, no new counter."""
    servable, b = _plane(gated=False)
    reqs = _online(b, "o", BUCKET) + _online(b, "p", 1)
    assert [r.wait(5.0) for r in reqs][-1] == "p0,ok"
    assert sorted(servable.pads) == [1, BUCKET]
    assert (TILE,) not in servable.compile_keys
    got = b.counters.as_dict()["Serving.m"]
    assert set(got) == {"requests", "batches", "reply_passes",
                        f"bucket.{BUCKET}", "bucket.1"}
    b.close()


@pytest.mark.parametrize("first_back", [0, 1], ids=["in-order", "reversed"])
def test_two_mixed_flights_reply_in_either_order(first_back):
    """Rows of one block ride in both dispatches in flight; whichever comes
    back first, the block is replied whole, in the order of its lines."""
    servable, b = _plane()
    with b._cond:                         # one take sees both classes
        block = b.submit_block("m", _rows("f", 2 * ROOM), klass="backfill")
        a = _online(b, "a", BUCKET)
    assert servable.wait_entered()
    c = _online(b, "c", BUCKET)
    assert servable.wait_entered()
    assert servable.calls[0] == _rows("a", BUCKET) + _rows("f", ROOM)
    assert servable.calls[1] == _rows("c", BUCKET) + \
        _rows("f", 2 * ROOM)[ROOM:]
    servable.release(first_back)
    early = (a, c)[first_back]
    assert [r.wait(5.0) for r in early] == \
        [f"{'ac'[first_back]}{i},ok" for i in range(BUCKET)]
    assert not block.done()
    servable.release(1 - first_back)
    assert block.wait(5.0) == [f"{r},ok" for r in _rows("f", 2 * ROOM)]
    got = b.counters.as_dict()["Serving.m"]
    assert got["overlapped"] == 1 and got["backfill_rows"] == 2 * ROOM
    assert "backfill_only" not in got
    _close(servable, b)


def test_blocks_are_released_in_the_order_handed_in():
    """Block 2 is answered whole before block 1's last rows come back: it is
    not released before block 1."""
    servable, b = _plane()
    with b._cond:
        one = b.submit_block("m", _rows("x", ROOM + 2), klass="backfill")
        two = b.submit_block("m", _rows("y", 3), klass="backfill")
        a = _online(b, "a", BUCKET)
    assert servable.wait_entered()
    c = _online(b, "c", BUCKET)
    assert servable.wait_entered()
    assert servable.calls[1] == _rows("c", BUCKET) + \
        _rows("x", ROOM + 2)[ROOM:] + _rows("y", 3)
    servable.release(1)
    assert [r.wait(5.0) for r in c][0] == "c0,ok"
    assert two.answered == 3 and not two.done() and not one.done()
    servable.release(0)
    assert one.wait(5.0)[-1] == f"x{ROOM + 1},ok"
    assert two.wait(5.0) == ["y0,ok", "y1,ok", "y2,ok"]
    assert one.finished <= two.finished
    assert [r.wait(5.0) for r in a][0] == "a0,ok"
    _close(servable, b)


@pytest.mark.parametrize("ending", ["replied", "failed"])
def test_a_blocks_latch_times_out_then_opens_for_every_later_wait(ending):
    """A block's hand-over is the request's: one raw lock.  ``wait`` with a
    time-out on a block still in flight raises and leaves it whole; once
    released — replied, or failed by a dying replica — ``done`` holds and
    every later ``wait``, from any thread, returns (or raises) the same."""
    fault = FaultPlan({"serve.dispatch": 2}) if ending == "failed" else None
    servable, b = _plane(fault=fault)
    block = b.submit_block("m", _rows("x", TILE + 2), klass="backfill")
    assert servable.wait_entered()
    for timeout in (0.01, 0, -1.0):
        with pytest.raises(RequestTimeout):
            block.wait(timeout)
    assert not block.done()
    servable.release(0)                   # ``failed``: hit 2 kills the replica
    got = []

    def wait():
        try:
            got.append(block.wait(5.0))
        except ReplicaDownError as exc:
            got.append(exc)

    waiters = [threading.Thread(target=wait) for _ in range(3)]
    for t in waiters:
        t.start()
    if ending == "replied":
        assert servable.wait_entered()
        servable.release(1)
    for t in waiters:
        t.join(5.0)
        assert not t.is_alive()
    wait()                                # and once more, after the others
    assert block.done() and len(got) == 4
    if ending == "replied":
        assert all(g == [f"{r},ok" for r in _rows("x", TILE + 2)] for g in got)
    else:
        assert all(g is block.error for g in got)
    _close(servable, b)


def test_block_completes_under_callers_that_keep_every_bucket_full():
    """The starvation test: 2 x BUCKET closed-loop callers always have a
    full bucket ready, so no dispatch is ever the block's alone — it rides
    with them, ROOM rows a dispatch."""
    servable, b = _plane(gated=False, hold_s=0.004)
    stop = threading.Event()

    def caller(c):
        i = 0
        while not stop.is_set():
            b.submit("m", f"c{c}-{i}")
            i += 1

    callers = [threading.Thread(target=caller, args=(c,), daemon=True)
               for c in range(2 * BUCKET)]
    for t in callers:
        t.start()
    deadline = time.monotonic() + 5.0
    while b.counters.as_dict().get("Serving.m", {}).get("batches", 0) < 6 \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    before = b.counters.as_dict()["Serving.m"]["batches"]
    rows = _rows("f", 10 * ROOM)
    block = b.submit_block("m", rows, klass="backfill")
    assert block.wait(10.0) == [f"{r},ok" for r in rows]
    took = b.counters.as_dict()["Serving.m"]["batches"] - before
    stop.set()
    for t in callers:
        t.join(5.0)
    b.close()
    # 10 dispatches of ROOM rows, + the two that may have been in flight
    # when it was handed in, + slack for a short bucket at a hiccup
    assert took <= 10 + 2 + 4, took
    got = b.counters.as_dict()["Serving.m"]
    assert got.get("backfill_only", 0) <= 2
    assert got["backfill_rows"] == 10 * ROOM


# -- per-class bounds -----------------------------------------------------------------

def test_block_of_four_queue_depths_is_admitted_and_sheds_no_online_row():
    depth = 8
    servable, b = _plane(queue_depth=depth)
    held = _online(b, "h", 1)             # in flight: nothing else is taken
    assert servable.wait_entered()
    block = b.submit_block("m", _rows("f", 4 * depth), klass="backfill")
    reqs = _online(b, "o", depth)         # the online queue, to its brim
    assert b.queue_depths() == {"m": depth}
    with pytest.raises(ShedError):        # the online bound is the online
        b.submit_nowait("m", "one-too-many")        # queue's alone
    more = b.submit_block("m", _rows("g", 4 * depth), klass="backfill")
    _close(servable, b)
    assert [r.wait(5.0) for r in held + reqs][-1] == f"o{depth - 1},ok"
    assert len(block.wait(5.0)) == 4 * depth == len(more.wait(5.0))
    got = b.counters.as_dict()["Serving.m"]
    assert got["shed"] == 1 and "backfill_shed" not in got
    assert got["backfill_rows"] == 8 * depth


def test_blocks_are_never_timed_out_by_the_request_timeout():
    servable, b = _plane(request_timeout_ms=20.0)
    held = _online(b, "h", 1)
    assert servable.wait_entered()
    block = b.submit_block("m", _rows("f", 5), klass="backfill")
    late = b.submit_nowait("m", "late")
    time.sleep(0.06)                      # both waited past the timeout
    _close(servable, b)
    assert held[0].wait(5.0) == "h0,ok"
    with pytest.raises(Exception, match="waited past"):
        late.wait(5.0)
    assert block.wait(5.0) == [f"f{i},ok" for i in range(5)]
    assert b.counters.as_dict()["Serving.m"]["timeouts"] == 1


def test_class_queue_depth_sheds_blocks_and_only_blocks():
    tenancy.configure(JobConfig({
        "tenant.serving.share": "4", "tenant.serving.priority": "1",
        "tenant.backfill.share": "1", "tenant.backfill.queue.depth": "2",
        "tenant.pool.concurrency": "2"}))
    servable, b = _plane(tenant="serving")
    held = _online(b, "h", 1)
    assert servable.wait_entered()
    blocks = [b.submit_block("m", _rows(t, 3), klass="backfill")
              for t in "xy"]
    with pytest.raises(TenantShedError) as shed:
        b.submit_block("m", _rows("z", 3), klass="backfill")
    assert shed.value.tenant == "backfill"
    assert shed.value.quota == "queue.depth" and shed.value.retry_after_s > 0
    ok = b.submit_nowait("m", "still-served")
    _close(servable, b)
    assert ok.wait(5.0) == "still-served,ok" and held[0].wait(5.0)
    assert [blk.wait(5.0)[0] for blk in blocks] == ["x0,ok", "y0,ok"]
    counters = b.counters.as_dict()
    assert counters["Serving.m"]["backfill_shed"] == 1
    assert "shed" not in counters["Serving.m"]
    assert counters["Tenant.backfill"] == {"shed": 1, "rows": 6}
    assert counters["Tenant.serving"] == {"rows": 2}


def test_a_model_without_a_tile_beyond_its_bucket_has_no_bulk_entry():
    servable, b = _plane(gated=False)
    for none in ((), (BUCKET,)):
        servable.tile_rows = none
        with pytest.raises(RequestError, match="no bulk entry"):
            b.submit_block("m", _rows("f", 3), klass="backfill")
    with pytest.raises(RequestError, match="empty block"):
        servable.tile_rows = (TILE,)
        b.submit_block("m", [], klass="backfill")
    b.close()


# -- shutdown, failure, isolation -------------------------------------------------------

def test_close_drains_blocks_in_flight_and_waiting():
    servable, b = _plane(gated=False, hold_s=0.002)
    blocks = [b.submit_block("m", _rows(t, 5 * TILE + 1), klass="backfill")
              for t in "xyz"]
    reqs = _online(b, "o", 3)
    b.close()
    assert all(blk.done() for blk in blocks)
    for tag, blk in zip("xyz", blocks):
        assert blk.wait(0) == [f"{r},ok" for r in _rows(tag, 5 * TILE + 1)]
    assert [r.wait(0) for r in reqs] == ["o0,ok", "o1,ok", "o2,ok"]
    with pytest.raises(Exception, match="closed"):
        b.submit_block("m", _rows("late", 2), klass="backfill")


def test_a_dead_replica_fails_every_open_block_retryable():
    servable, b = _plane(fault=FaultPlan({"serve.dispatch": 2}))
    with b._cond:
        one = b.submit_block("m", _rows("x", 2 * TILE), klass="backfill")
        two = b.submit_block("m", _rows("y", 3), klass="backfill")
    assert servable.wait_entered()        # hit 1: the first tile scores
    servable.release(0)                   # hit 2 kills the replica
    for blk in (one, two):
        with pytest.raises(ReplicaDownError):
            blk.wait(5.0)
    assert one.answered == TILE           # what came back is not a reply
    with pytest.raises(ReplicaDownError):
        b.submit_block("m", _rows("z", 2), klass="backfill")
    assert b.counters.as_dict()["Serving.m"]["backfill_failed"] == 2
    b.close()


def test_a_bad_row_fails_its_block_whole_and_nobody_else():
    servable, b = _plane(gated=False)
    rows = _rows("f", ROOM)
    rows[3] = "BAD"
    with b._cond:
        bad = b.submit_block("m", rows, klass="backfill")
        good = b.submit_block("m", _rows("g", TILE), klass="backfill")
        reqs = _online(b, "o", BUCKET)
    assert [r.wait(5.0) for r in reqs] == [f"o{i},ok" for i in range(BUCKET)]
    with pytest.raises(RequestError, match="a bad row"):
        bad.wait(5.0)
    assert good.wait(5.0) == [f"{r},ok" for r in _rows("g", TILE)]
    got = b.counters.as_dict()["Serving.m"]
    assert got["backfill_failed"] == 1 and got["backfill_blocks"] == 1
    assert got["requests"] == BUCKET
    b.close()


def test_first_block_warms_the_tile_and_no_dispatch_recompiles():
    servable, b = _plane(gated=False)
    assert (TILE,) not in servable.compile_keys
    block = b.submit_block("m", _rows("f", 3 * TILE), klass="backfill")
    assert (TILE,) in servable.compile_keys
    block.wait(5.0)
    incoming = _TileServable(gated=False)
    b.swap("m", incoming)                 # the swap barrier warms it too
    assert (TILE,) in incoming.compile_keys
    b.submit_block("m", _rows("g", TILE), klass="backfill").wait(5.0)
    assert "recompiles" not in b.counters.as_dict()["Serving.m"]
    b.close()


# -- spans --------------------------------------------------------------------------------

def test_spans_of_the_second_class(traced):
    servable, b = _plane()
    with b._cond:
        block = b.submit_block("m", _rows("f", TILE), klass="backfill",
                               rid="file-7")
        reqs = _online(b, "o", 2)
    assert servable.wait_entered()
    servable.release(0)
    assert servable.wait_entered()
    servable.release(1)
    block.wait(5.0)
    _close(servable, b)
    closed = [e for e in read_events(traced.journal_path)
              if e.get("ev") == "span.close"]
    by_name = {}
    for e in closed:
        by_name.setdefault(e["name"], []).append(e["attrs"])
    assert [(a["online_rows"], a["backfill_rows"], a["rows"], a["bucket"])
            for a in by_name["serve.dispatch"]] == [
                (2, TILE - 2, 2, TILE), (0, 2, 0, TILE)]
    assert [a["rows"] for a in by_name["serve.backfill.queue"]] == \
        [TILE - 2, 2]
    assert all(a["block"] == 0 and a["dispatch"]
               for a in by_name["serve.backfill.queue"])
    assert by_name["serve.backfill.block"] == [
        {"model": "m", "block": 0, "rows": TILE, "tenant": "backfill",
         "rid": "file-7"}]
    assert len(by_name["serve.queue"]) == 2       # online rows alone
    assert [r.wait(1.0) for r in reqs] == ["o0,ok", "o1,ok"]


# -- the bulk entry on /score -------------------------------------------------------------

def test_bulk_entry_on_score():
    servable, b = _plane(gated=False)
    with ScoreHTTPServer(b) as server:
        host, port = server.address

        def post(body):
            req = urllib.request.Request(
                f"http://{host}:{port}/score", json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.loads(resp.read())

        rows = _rows("f", 3 * TILE + 5)
        got = post({"model": "m", "rows": rows, "class": "backfill"})
        assert got["results"] == [f"{r},ok" for r in rows]
        assert post({"model": "m", "rows": ["a"]})["results"] == ["a,ok"]
        with pytest.raises(urllib.error.HTTPError) as bad:
            post({"model": "m", "rows": ["a"], "class": 7})
        assert bad.value.code == 400
    stats = b.counters.as_dict()["Serving.m"]
    assert stats["backfill_rows"] == 3 * TILE + 5 and stats["requests"] == 1
    b.close()


# -- the real servable: the same bits whichever way a row travels -----------------------

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.join(os.path.dirname(HERE), "perfbench")
REFS, SEED = 8192, 2 ** 31 + 34


@pytest.fixture(scope="module")
def elearn():
    """The benchmark's kNN deployment at a small size, through its own
    family (set-up from the seed, calls recorded with what they computed)."""
    sys.path[:0] = [p for p in (PERFBENCH,) if p not in sys.path]
    from families import knn as family
    from lib import data

    with open(os.path.join(PERFBENCH, "configs",
                           "elearn_knn_classcond.json")) as fh:
        config = json.load(fh)
    system = family.System(config, SEED, REFS)
    servable = system.servable
    servable.tile_rows = servable.inner.tile_rows
    servable.keep = True
    batcher = BucketedMicrobatcher(ModelRegistry().add("knn", servable))
    lines = data.make_query_lines(700, SEED)
    yield system, servable, batcher, lines
    batcher.close()


def _answers(servable, call_index):
    """line -> (distances, neighbours, shares) of one recorded call."""
    call = servable.calls[call_index]
    res = call["result"]
    return {line: (res.neighbor_dist[i], res.neighbor_idx[i],
                   res.class_scores[i])
            for i, line in enumerate(call["lines"])}


def test_a_row_reads_the_same_whichever_way_it_travels(elearn):
    system, servable, b, lines = elearn
    tile, wide = servable.tile_rows
    assert (tile, wide) == (256, 512)
    probe = lines[:40]                    # the rows sent every way
    # 1. direct
    direct = servable.score_lines(probe, 64)
    n0 = len(servable.calls)
    # 2. an online-only bucket
    online = [r.wait(60.0) for r in
              [b.submit_nowait("knn", ln) for ln in probe]]
    n1 = len(servable.calls)
    # 3. block rows alone, whole tiles
    alone = b.submit_block("knn", probe + lines[100:100 + tile - 40],
                           klass="backfill").wait(60.0)
    n2 = len(servable.calls)
    # 4. a mixed tile: 24 online rows first, the probe among the block rows
    with b._cond:
        block = b.submit_block("knn", lines[200:260] + probe,
                               klass="backfill")
        reqs = [b.submit_nowait("knn", ln) for ln in lines[300:324]]
    mixed = block.wait(60.0)
    assert [r.wait(60.0) for r in reqs]
    # 5. a mixed tile with the probe as the online rows
    with b._cond:
        block = b.submit_block("knn", lines[400:400 + tile],
                               klass="backfill")
        reqs = [b.submit_nowait("knn", ln) for ln in probe]
    first = [r.wait(60.0) for r in reqs]
    block.wait(60.0)
    n3 = len(servable.calls)
    # 6. a backlog of two blocks: the widest tile, the probe among its rows
    with b._cond:
        blocks = [b.submit_block("knn", rows, klass="backfill") for rows in
                  (lines[100:400] + probe, lines[400:700])]
        reqs = [b.submit_nowait("knn", ln) for ln in lines[:24]]
    behind = blocks[0].wait(60.0)
    blocks[1].wait(60.0)
    assert [r.wait(60.0) for r in reqs]
    assert direct == online == alone[:40] == mixed[60:] == first \
        == behind[300:]
    calls = servable.calls
    assert len(calls[n3]["lines"]) == calls[n3]["pad_to"] == wide
    assert [len(c["lines"]) for c in calls[n2:n2 + 1]] == [24 + 60 + 40]
    assert calls[n2]["pad_to"] == tile and calls[n1]["pad_to"] == tile
    ways = {"direct": _answers(servable, n0 - 1),
            "online": {}, "alone": _answers(servable, n1),
            "mixed": _answers(servable, n2),
            "behind": _answers(servable, n3)}
    for i in range(n0, n1):
        ways["online"].update(_answers(servable, i))
    last = next(i for i in range(len(calls) - 1, -1, -1)
                if calls[i]["lines"][:1] == probe[:1])
    ways["online-first"] = _answers(servable, last)
    for line in probe:
        d0, i0, s0 = ways["direct"][line]
        for name, found in ways.items():
            d, i, s = found[line]
            assert np.array_equal(d, d0) and np.array_equal(i, i0), name
            assert np.array_equal(s, s0), name
    # and all of it agrees with the plain reference, within the cell's limits
    sample = [{"line": ln, "reply": rp} for ln, rp in
              list(zip(probe, mixed[60:])) + list(zip(lines[200:260],
                                                      mixed[:60]))]
    numbers = system.check(sample, system.produced(sample))
    for name, limit in system.config["limits"].items():
        assert numbers[name] <= limit, (name, numbers[name])


def test_pad_rows_that_fail_the_certificate_are_not_rescanned(monkeypatch):
    """A dispatch padded to its bucket or tile carries zero rows nobody
    reads.  Where such a row fails the fused search's certificate it must
    not go to the exact scan: 384 pad rows of a block's last tile would
    compile a 384-row scan inside the window (a chip run of PR 34 stalled
    2 s on one seed) and count as refused rows of the search."""
    from avenir_tpu.core.encoding import EncodedDataset, pad_ballast
    from avenir_tpu.models import knn as mknn
    from avenir_tpu.ops import pallas_knn

    rng = np.random.default_rng(5)
    refs = EncodedDataset(
        codes=np.zeros((64, 0), np.int32),
        cont=rng.uniform(size=(64, 3)).astype(np.float32),
        labels=rng.integers(0, 2, 64).astype(np.int32),
        n_bins=np.zeros(0, np.int32), class_values=["a", "b"],
        binned_ordinals=[], cont_ordinals=[0, 1, 2])
    model = mknn.fit_knn(refs)
    real, padded, k = 3, 8, 2
    test = pad_ballast(refs.slice(0, real), padded, fill=0)
    assert test.valid_rows == real and test.num_rows == padded

    def search(codes_q, *_a, **_k):
        m = codes_q.shape[0]
        cert = np.ones(m, bool)
        cert[1] = False                   # one real row refused
        cert[real:] = False               # and every pad row
        return (np.zeros((m, k), np.float32), np.zeros((m, k), np.int32),
                cert)

    scanned = []

    def scan(_model, sub, kk, *_a, **_k):
        scanned.append(sub.num_rows)
        return (np.ones((sub.num_rows, kk), np.float32),
                np.ones((sub.num_rows, kk), np.int32))

    monkeypatch.setattr(mknn, "_pallas_available", lambda metric, kk: True)
    monkeypatch.setattr(pallas_knn, "fused_serves", lambda n, kk: True)
    monkeypatch.setattr(pallas_knn, "search_fused", search)
    monkeypatch.setattr(mknn, "_nearest_neighbors_xla", scan)
    monkeypatch.setattr(mknn.KNNModel, "device_packed",
                        lambda self, nb: (None, None, None, self.num_refs))
    counts = {}
    d, idx = mknn.nearest_neighbors(model, test, k, counts=counts)
    assert scanned == [1] and counts == {"refused": 1}
    assert (model.fused_rows, model.tourney_rows,
            model.cert_fallback_rows) == (real, real, 1)
    assert d[1].tolist() == [1.0, 1.0] and d[real:].sum() == 0.0
    # an unpadded batch is counted and rescanned whole, as before (the
    # fake refuses rows 1 and 3 of it)
    mknn.nearest_neighbors(model, refs.slice(0, 4), k)
    assert model.fused_rows == real + 4 and scanned == [1, 2]
