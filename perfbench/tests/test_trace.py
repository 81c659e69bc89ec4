"""(c) The trace reduction on a small trace recorded on one TPU v5e
(data/tiny_v5e.xplane.pb, my chip run, PR 26: three rounds of a matmul
program under ``knn.predict`` and a tanh program, both under ``score_lines``,
all under ``window``).  The expected numbers were worked out by hand from the
events' starts and durations, in nanoseconds:

window [45 754 130, 75 183 499]; the first matmul program (44 872 949, before
the window opens: the device's clock runs ~0.9 ms ahead of the host's) is cut
off.  Operations inside: tanh 13 196; then copy-start 13, copy-done 11 441,
matmul fusion 90 087, tanh 13 207; then 13, 11 667, 90 087, 13 331.
"""

import os

import pytest

from lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9
SPANS = ("window", "score_lines", "knn.predict")


@pytest.fixture(scope="module")
def tiny():
    return T.load_xplane(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"),
                         SPANS)


def test_planes_lines_and_spans(tiny):
    assert len(tiny.ops) == len(tiny.modules) == 1
    assert len(tiny.ops[0]) == 12 and len(tiny.modules[0]) == 6
    assert [s[0] for s in tiny.spans] == [
        "window", "score_lines", "knn.predict", "score_lines", "knn.predict",
        "score_lines", "knn.predict"]


def test_busy_union_program_time_and_gaps(tiny):
    window = next(s for s in tiny.spans if s[0] == "window")
    t0, t1 = window[1], window[1] + window[2]
    assert t0 == pytest.approx(45754130 * NS) and \
        t1 == pytest.approx(75183499 * NS)
    ops = T.clip(tiny.ops[0], t0, t1)
    assert len(ops) == 9
    busy = T.busy_seconds(ops)
    assert busy == pytest.approx(243042 * NS, rel=1e-9)
    total, calls = T.program_time(T.clip(tiny.modules[0], t0, t1),
                                  "14530554794882571194")
    assert calls == 2 and total == pytest.approx(203318 * NS, rel=1e-9)
    top = T.top_ops(ops, 2)
    assert top[0][0].startswith("%convolution_reduce_fusion")
    assert top[0][1] == pytest.approx(180174 * NS, rel=1e-9)
    assert top[1][1] == pytest.approx(39734 * NS, rel=1e-9)
    gaps = dict(T.idle_gaps(ops, [s for s in tiny.spans if s[0] != "window"],
                            t0, t1))
    assert gaps["knn.predict"] == pytest.approx(2759220 * NS, rel=1e-9)
    assert gaps["score_lines"] == pytest.approx(9094516 * NS, rel=1e-9)
    assert gaps["outside_spans"] == pytest.approx(17332591 * NS, rel=1e-9)
    assert sum(gaps.values()) == pytest.approx((29429369 - 243042) * NS,
                                               rel=1e-9)


def test_union_merges_overlaps_and_innermost_span_owns_a_gap():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 2.0), ("c", 5.0, 1.0)]
    assert T.union_intervals(ev) == [(0.0, 3.0), (5.0, 6.0)]
    assert T.busy_seconds(ev) == 4.0
    spans = [("outer", 0.0, 10.0), ("inner", 2.5, 2.0)]
    gaps = dict(T.idle_gaps(ev, spans, 0.0, 8.0))
    assert gaps == {"inner": 1.5, "outer": 2.5}       # gaps [3, 5) and [6, 8)
    assert dict(T.idle_gaps(ev, spans[1:], 0.0, 8.0)) == \
        {"inner": 1.5, "outside_spans": 2.5}


def test_readers_of_the_device_trace_on_the_recorded_trace(tiny):
    """The per-layer readers, fed the recorded trace as a traced run feeds
    them: 2 executions of the 'search' program inside the window."""
    from lib import peaks, work
    from readers import (device_idle, host_ms_per_call, knn_roofline,
                         program_device_ms)

    window = next(s for s in tiny.spans if s[0] == "window")
    t0, t1 = window[1], window[1] + window[2]
    calls = [{"t0": 0.0, "t1": 0.004, "rows": 64, "pad_to": 64}] * 2
    ctx = {"trace": tiny, "trace_window": (t0, t1),
           "snapshot": {"calls": calls, "attrs": 9, "refs": 1 << 20, "k": 10},
           "device": {"kind": "TPU v5 lite", "busy_s": 243042 * NS,
                      "window_s": 29429369 * NS}}
    prog = "14530554794882571194"
    assert program_device_ms.read(ctx, prog) == pytest.approx(
        203318 * NS * 1e3 / 2, rel=1e-9)
    least = 2 * work.least_time_s(*work.knn_search_work(9, 64, 1 << 20, 10),
                                  peaks.peak_for("TPU v5 lite"))[0]
    assert knn_roofline.read(ctx, prog) == pytest.approx(
        100 * least / (203318 * NS), rel=1e-9)
    # both programs' executions inside the window: 203 318 + 13 198 + 13 212
    # + 13 335 ns of device time against 8 ms of spans
    assert host_ms_per_call.read(ctx) == pytest.approx(
        1e3 * (0.008 - 243063 * NS) / 2, rel=1e-9)
    assert device_idle.read(ctx) == pytest.approx(
        100 * (1 - 243042 / 29429369), rel=1e-9)
    # nothing to read: no trace, or a program that never ran
    assert program_device_ms.read({"snapshot": ctx["snapshot"]}, prog) is None
    assert knn_roofline.read(ctx, "no_such_program") is None
    assert device_idle.read({"device": {}}) is None
