"""(e) The readers of the program's own spans, against hand-made span lists
and the trace recorded on one TPU v5e (data/tiny_v5e.xplane.pb; its events
are worked out in test_trace.py): the alignment of recorder time to trace
time, self time, the phases of a call, and the idle share that no leaf span
covers."""

import os
from collections import namedtuple

import pytest

from lib import program_spans as P
from lib import trace as T
from readers import (idle_unexplained, launch_readback, score_phase_ms,
                     span_attr_ratio, span_median_ms, span_self_ms,
                     span_window_share)

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9
R = namedtuple("R", "name start end span_id parent_id trace_id thread attrs")


def rec(name, start, end, sid, parent=None, **attrs):
    return R(name, start, end, sid, parent, "t", 1, attrs)


def one_call(at, n, refused=0.0):
    """The spans of one dispatch that took the fused search, ``at`` seconds
    on the recorder's clock: 1 ms slot, 2 ms parse + encode, 70 ms stage ->
    readback end, ``refused`` seconds of exact scan, 3 ms vote + format, 1 ms
    reply; and two queued requests, 80 and 40 ms before it."""
    d, s, c, k = f"d{n}", f"s{n}", f"c{n}", f"k{n}"
    end = at + 0.076 + refused
    out = [
        rec("serve.queue", at - 0.080, at, f"q{n}a", dispatch=d),
        rec("serve.queue", at - 0.040, at, f"q{n}b", dispatch=d),
        rec("serve.slot", at, at + 0.001, f"l{n}", d),
        rec("servable.parse", at + 0.001, at + 0.002, f"p{n}", s),
        rec("servable.encode", at + 0.002, at + 0.003, f"e{n}", s),
        rec("knn.stage", at + 0.003, at + 0.004, f"g{n}", k),
        rec("knn.readback", at + 0.004, at + 0.073, f"b{n}", k),
    ]
    if refused:
        out.append(rec("knn.fallback", at + 0.073, at + 0.073 + refused,
                       f"f{n}", k, rows=1, new_program=True))
    out += [
        rec("knn.search", at + 0.003, at + 0.073 + refused, k, c,
            path="fused", rows=64, kernel_rows=512, refused=int(bool(refused))),
        rec("knn.classify", at + 0.003, end - 0.002, c, s),
        rec("servable.format", end - 0.002, end - 0.001, f"o{n}", s),
        rec("servable.score", at + 0.001, end - 0.001, s, d, rows=64,
            pad_to=64),
        rec("serve.reply", end - 0.001, end, f"r{n}", d),
        rec("serve.dispatch", at, end, d, model="knn", rows=64, bucket=64),
    ]
    return out


@pytest.fixture()
def ctx():
    spans = one_call(100.0, 0) + one_call(100.1, 1, refused=0.010)
    return {"program_spans": spans,
            "window": {"start": 99.9, "end": 100.9, "requests": []},
            "snapshot": {"calls": []}}


def test_span_readers_on_a_hand_made_list(ctx):
    assert span_median_ms.read(ctx, "serve.queue") == pytest.approx(60.0)
    # dispatch 76 (86) ms minus servable.score 74 (84): slot + reply
    assert span_self_ms.read(ctx, "serve.dispatch", "servable.score") == \
        pytest.approx(2.0)
    assert span_attr_ratio.read(ctx, "knn.search", "rows", "kernel_rows",
                                100.0) == pytest.approx(12.5)
    assert span_window_share.read(ctx, "knn.fallback") == pytest.approx(1.0)
    assert score_phase_ms.read(ctx, "parse_encode") == pytest.approx(2.0)
    # readback end -> score end is 2 ms (12 with the exact scan, left out)
    assert score_phase_ms.read(ctx, "vote_format") == pytest.approx(2.0)
    with pytest.raises(ValueError):
        score_phase_ms.read(ctx, "no_such_phase")
    # the parts of a call add up to its servable.score span
    for call in P.calls(ctx["program_spans"]):
        s, g, b = call["score"], call["stage"], call["readback"]
        fall = sum(f.end - f.start for f in call["fallback"])
        assert (g.start - s.start) + (b.end - g.start) + fall \
            + (s.end - b.end - fall) == pytest.approx(s.end - s.start)


def test_nothing_to_read_gives_none_and_a_drop_in_the_window_raises(
        monkeypatch):
    """A program without a recorder (the parent), a recorder that kept
    nothing of the window, or spans of another name: every reader leaves
    its metric out and none raises."""
    window = {"start": 10.0, "end": 20.0, "requests": []}
    empty = {"program_spans": None, "window": window,
             "snapshot": {"calls": []}}
    assert span_median_ms.read(dict(empty), "serve.queue") is None
    assert span_self_ms.read(dict(empty), "serve.dispatch", "x") is None
    assert span_attr_ratio.read(dict(empty), "knn.search", "a", "b") is None
    assert span_window_share.read(dict(empty), "knn.fallback") is None
    assert score_phase_ms.read(dict(empty), "parse_encode") is None
    assert launch_readback.read(dict(empty), "_search_fused") is None
    assert idle_unexplained.read(dict(empty)) is None
    # off the fused path (the CPU's XLA scan) a call has no stage/readback
    xla = {**empty, "program_spans": [
        rec("servable.score", 11.0, 11.5, "s"),
        rec("knn.search", 11.1, 11.4, "k", "s", path="xla", rows=4,
            kernel_rows=4)]}
    assert score_phase_ms.read(xla, "vote_format") is None
    assert span_window_share.read(xla, "knn.fallback") == 0.0
    assert span_attr_ratio.read(xla, "knn.search", "rows", "kernel_rows",
                                100.0) == 100.0

    class Recorder:
        def __init__(self, records, dropped):
            self._records, self.dropped = records, dropped

        def recorded(self):
            return list(self._records)

    inside = [rec("a", 9.0, 10.5, "1"), rec("b", 12.0, 13.0, "2"),
              rec("c", 25.0, 26.0, "3")]
    monkeypatch.setattr(P, "_recorder", lambda: None)
    assert P.window_spans({"window": window}) is None
    monkeypatch.setattr(P, "_recorder", lambda: Recorder(inside, 0))
    assert [r.name for r in P.window_spans({"window": window})] == ["b"]
    monkeypatch.setattr(P, "_recorder", lambda: Recorder(inside[2:], 0))
    assert P.window_spans({"window": window}) is None     # nothing of ours
    # 7 dropped, all older than the oldest kept: harmless while that one
    # closed before the window opened, else spans of the window may be gone
    early = [rec("z", 8.0, 9.0, "0")] + inside
    monkeypatch.setattr(P, "_recorder", lambda: Recorder(early, 7))
    assert [r.name for r in P.window_spans({"window": window})] == ["b"]
    for kept in (inside, inside[1:], inside[2:]):
        monkeypatch.setattr(P, "_recorder", lambda: Recorder(kept, 7))
        with pytest.raises(RuntimeError, match="dropped 7"):
            P.window_spans({"window": window})


@pytest.fixture(scope="module")
def tiny():
    return T.load_xplane(os.path.join(HERE, "data", "tiny_v5e.xplane.pb"),
                         ("window", "score_lines", "knn.predict"))


def _tiny_ctx(tiny, spans, jitter=(0.0, 0.0, 0.0)):
    """The recorded trace with host stamps 1000 s behind the trace's clock
    (the offset a reader has to find), each stamp ``jitter`` early."""
    window = next(s for s in tiny.spans if s[0] == "window")
    t0, t1 = window[1], window[1] + window[2]
    calls = [{"t0": s - 1000.0 - j, "t1": s + d - 1000.0, "rows": 64,
              "pad_to": 64}
             for (_n, s, d), j in zip(
                 [s for s in tiny.spans if s[0] == "score_lines"], jitter)]
    return {"trace": tiny, "trace_window": (t0, t1),
            "window": {"start": t0 - 1000.0, "end": t1 - 1000.0},
            "snapshot": {"calls": calls}, "program_spans": spans,
            "device": {"kind": "TPU v5 lite"}}


def test_alignment_finds_the_offset_and_reports_the_residual(tiny, capsys):
    ctx = _tiny_ctx(tiny, [rec("x", 0.0, 1.0, "1")],
                    jitter=(1e-6, 2e-6, 9e-6))
    assert P.offset(ctx) == pytest.approx(1000.0 + 2e-6, abs=1e-9)
    assert ctx["program_span_residual_ms"] == pytest.approx(8e-3, rel=1e-3)
    assert "residual inter-quartile" in capsys.readouterr().err
    # a stamp whose annotation the trace lost cannot be paired in order
    lost = _tiny_ctx(tiny, [rec("x", 0.0, 1.0, "1")])
    lost["snapshot"]["calls"].append({"t0": 5.0, "t1": 5.1})
    with pytest.raises(RuntimeError, match="3 score_lines annotations"):
        P.offset(lost)
    # no trace, no offset, no aligned spans
    assert P.offset({"snapshot": {"calls": []}}) is None
    assert P.aligned({"program_spans": [rec("x", 0.0, 1.0, "1")],
                      "snapshot": {"calls": []}}) is None


def test_idle_unexplained_with_a_span_covering_and_not_covering_a_gap(
        tiny, capsys):
    """The window is 29 429 369 ns with 243 042 ns busy.  A leaf span over the
    whole window explains every idle moment; leaves over the three
    ``knn.predict`` intervals explain the 2 759 220 ns the device idled
    inside them (test_trace.py); a parent alone explains nothing."""
    window = next(s for s in tiny.spans if s[0] == "window")
    t0, t1 = window[1] - 1000.0, window[1] + window[2] - 1000.0
    idle = (29429369 - 243042) * NS
    whole = [rec("serve.slot", t0, t1, "1")]
    assert idle_unexplained.read(_tiny_ctx(tiny, whole)) == \
        pytest.approx(0.0, abs=1e-9)
    predicts = [s for s in tiny.spans if s[0] == "knn.predict"]
    leaves = [rec("serve.dispatch", t0, t1, "d")] + [
        rec("knn.readback", s - 1000.0, s + d - 1000.0, f"b{i}", "d")
        for i, (_n, s, d) in enumerate(predicts)]
    capsys.readouterr()
    assert idle_unexplained.read(_tiny_ctx(tiny, leaves)) == pytest.approx(
        100 * (idle - 2759220 * NS) / (29429369 * NS), rel=1e-6)
    err = capsys.readouterr().err
    assert "knn.readback 0.0028" in err and "serve.dispatch 0.0264" in err
    # a request's queue span is a wait, never the host's work
    queued = [rec("serve.dispatch", t0, t1, "d"),
              rec("serve.slot", t0, t0, "l", "d"),
              rec("serve.queue", t0, t1, "q", dispatch="d")]
    parent_only = _tiny_ctx(tiny, queued)
    assert idle_unexplained.read(parent_only) == pytest.approx(
        100 * idle / (29429369 * NS), rel=1e-6)


def test_launch_readback_takes_device_time_off_and_checks_the_count(tiny):
    """Two executions of the 'search' program inside the window (203 318 ns
    of device time, test_trace.py) against two fused searches of 1 ms each
    stage start -> readback end."""
    prog = "14530554794882571194"
    window = next(s for s in tiny.spans if s[0] == "window")
    at = window[1] - 1000.0 + 0.001
    spans = []
    for n in range(2):
        a = at + 0.005 * n
        spans += [rec("servable.score", a, a + 0.002, f"s{n}"),
                  rec("knn.search", a, a + 0.0015, f"k{n}", f"s{n}"),
                  rec("knn.stage", a + 0.0002, a + 0.0004, f"g{n}", f"k{n}"),
                  rec("knn.readback", a + 0.0004, a + 0.0012, f"b{n}",
                      f"k{n}")]
    ctx = _tiny_ctx(tiny, spans)
    assert launch_readback.read(ctx, prog) == pytest.approx(
        1e3 * (0.002 - 203318 * NS) / 2, rel=1e-9)
    with pytest.raises(RuntimeError, match="2 executions"):
        launch_readback.read(_tiny_ctx(tiny, spans[:4]), prog)


def test_traced_rehearsal_prints_the_span_metrics_by_their_own_files():
    """The whole wiring on the CPU: BENCHMARK.json entry -> metrics/<name>.json
    -> reader -> the program's recorder.  Off a TPU the search takes the XLA
    scan, so the two metrics that need the device trace and the two that need
    the fused path's spans are left out; the others are counts and host
    times of a rehearsal, not device numbers."""
    import run

    line = run.run(["--workload", "classcond_serve_c128", "--seed",
                    str(2 ** 31 + 23), "--seconds", "1", "--trace", "1"],
                   rehearse={"refs": 1 << 12})
    got = set(line["metrics"])
    assert {"batcher_queue_ms", "batcher_self_ms", "tile_fill_pct",
            "fallback_window_pct"} <= got
    assert not got & {"launch_readback_ms", "idle_unexplained_pct",
                      "parse_encode_ms", "vote_format_ms"}
    assert line["metrics"]["tile_fill_pct"]["value"] == 100.0   # no tile here
    assert line["metrics"]["batcher_queue_ms"]["value"] > 0
    assert line["correct"] is True
