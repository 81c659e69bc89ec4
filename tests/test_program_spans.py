"""The serving path's own spans (PR 27): one span primitive with three sinks
— the journal under ``trace.on``, the profiler's trace while a JAX profiler
session runs, and the tracer's in-memory recorder either way — and the span
sites from the batcher's queue down to the kNN vote.

Everything runs on the CPU at a tiny size; the fused search is stood in for
by a fake ``search_fused`` (exact answers from the XLA scan, a certificate of
the test's choosing), so the fused path's spans and counts are exercised
without the TPU kernel.
"""

import glob
import statistics
import threading
import time

import jax
import numpy as np
import pytest

from avenir_tpu.core.encoding import DatasetEncoder, EncodedDataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.models import knn as mknn
from avenir_tpu.ops import pallas_knn
from avenir_tpu.serving.batcher import BucketedMicrobatcher
from avenir_tpu.serving.registry import KNNServable, ModelRegistry
from avenir_tpu.telemetry import spans as tel

SIGNALS = 3
REFS = 4096


@pytest.fixture()
def recorder():
    """The process tracer's recorder, emptied before and after."""
    tel.tracer().recorded(clear=True)
    yield tel.tracer()
    tel.tracer().recorded(clear=True)


@pytest.fixture()
def session(tmp_path):
    """A JAX profiler session on the CPU, as a traced benchmark run or an
    operator's xprof capture starts one; yields the trace directory."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield str(tmp_path)
    finally:
        if tel.profiler_session():
            jax.profiler.stop_trace()


def _host_events(trace_dir, names):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events if e.name in names)
    return sorted(out, key=lambda ev: ev[1])


def _servable(rng, class_cond=False):
    schema = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        *({"name": f"s{i}", "ordinal": i + 1, "dataType": "int",
           "feature": True} for i in range(SIGNALS)),
        {"name": "status", "ordinal": SIGNALS + 1,
         "dataType": "categorical", "cardinality": ["P", "F"]}]})
    enc = DatasetEncoder(schema)
    cont = rng.integers(0, 200, size=(REFS, SIGNALS)).astype(np.float32)
    ds = EncodedDataset(
        codes=np.zeros((REFS, 0), np.int32), cont=cont,
        labels=rng.integers(0, 2, size=REFS).astype(np.int32),
        n_bins=np.zeros(0, np.int32), class_values=list(enc.class_values),
        binned_ordinals=[],
        cont_ordinals=[f.ordinal for f in enc.cont_fields])
    est = mknn.KNN(k=5, kernel="gaussian", class_cond_weighting=class_cond)
    probs = rng.random((REFS, 2)).astype(np.float32) if class_cond else None
    return KNNServable(est, est.fit(ds, class_probs=probs), enc)


def _lines(rng, n, tag="u"):
    return [",".join([f"{tag}{i}", *map(str, rng.integers(0, 200, SIGNALS))])
            for i in range(n)]


@pytest.fixture()
def fused(monkeypatch):
    """Route the search through ``_nearest_neighbors_fused`` with a fake
    ``search_fused``: exact answers, and the rows listed in ``refuse``
    failing their certificate.  The fake needs no candidate pool, so the
    route's size predicate is answered for it."""
    refuse = []

    def fake(codes_q, cont01_q, r_mat, codes_r, cont01_r, n, nb, k, attrs):
        sub = np.asarray(cont01_q)
        refs = np.asarray(cont01_r)
        d2 = ((sub[:, None, :] - refs[None, :, :]) ** 2).sum(-1)
        idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
        d = np.sqrt(np.take_along_axis(d2, idx, 1) / max(attrs, 1))
        cert = np.ones(sub.shape[0], bool)
        cert[[r for r in refuse if r < cert.size]] = False
        return d.astype(np.float32), idx.astype(np.int32), cert

    monkeypatch.setattr(mknn, "_pallas_available", lambda metric, k: True)
    monkeypatch.setattr(pallas_knn, "fused_serves", lambda n_real, k: True)
    monkeypatch.setattr(pallas_knn, "search_fused", fake)
    return refuse


def _by_name(records):
    out = {}
    for rec in records:
        out.setdefault(rec.name, []).append(rec)
    return out


# -- the primitive -------------------------------------------------------------

def test_span_is_noop_with_no_journal_and_no_profiler_session():
    t = tel.Tracer()
    assert not tel.profiler_session()
    assert t.span("x") is tel.NOOP_SPAN
    with t.span("x", {"a": 1}) as sp:
        assert not sp.enabled
    t.emit_span("y", 0.5)
    assert t.recorded() == [] and t.dropped == 0


def test_span_site_is_live_under_a_profiler_session_on_the_trace_clock(
        session):
    t = tel.Tracer()
    marks = []
    with t.span("outer.live", {"a": 1}) as outer:
        assert outer.enabled and tel.tracer().current() is None
        for _ in range(5):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("mark"):
                with t.span("inner.live"):
                    time.sleep(0.002)
            marks.append(t0)
    t.emit_span("retro.live", 0.001)
    jax.profiler.stop_trace()
    assert t.span("after") is tel.NOOP_SPAN          # session over: free again

    rec = _by_name(t.recorded())
    assert [len(rec[n]) for n in ("outer.live", "inner.live", "retro.live")] \
        == [1, 5, 1]
    assert all(r.parent_id == rec["outer.live"][0].span_id
               and r.trace_id == rec["outer.live"][0].trace_id
               for r in rec["inner.live"])
    assert rec["outer.live"][0].attrs == {"a": 1}
    assert rec["outer.live"][0].thread == threading.get_ident()

    events = _host_events(session, ("outer.live", "inner.live", "mark",
                                    "retro.live"))
    names = [e[0] for e in events]
    assert names.count("outer.live") == 1 and names.count("inner.live") == 5
    assert "retro.live" not in names       # a TraceMe cannot be back-dated
    # recorder time -> trace time: the offset the benchmark's reader takes
    # (annotation start minus the host stamp before it), then the program's
    # own spans must sit where the profiler put them
    ann = [e for e in events if e[0] == "mark"]
    offset = statistics.median(a[1] - t0 for a, t0 in zip(ann, marks))
    inner = [e for e in events if e[0] == "inner.live"]
    for ev, r in zip(inner, rec["inner.live"]):
        assert abs(r.start + offset - ev[1]) < 5e-4
        assert abs((r.end - r.start) - ev[2]) < 5e-4


def test_recorder_drops_oldest_and_counts():
    t = tel.Tracer(capacity=4)
    t.enable()                                # live without a journal file
    try:
        for i in range(7):
            with t.span("s", {"i": i}):
                pass
    finally:
        t.disable()
    assert [r.attrs["i"] for r in t.recorded()] == [3, 4, 5, 6]
    assert t.dropped == 3
    assert len(t.recorded(clear=True)) == 4 and t.recorded() == []
    assert t.dropped == 3                      # the count outlives a clear


def test_emit_span_keeps_an_explicit_start(tmp_path):
    t = tel.Tracer()
    t.enable(str(tmp_path))
    try:
        begin = time.perf_counter() - 2.0
        t.emit_span("measured.elsewhere", 0.25, attrs={"k": "v"},
                    start=begin)
        t.emit_span("ends.now", 0.25)
        now = time.perf_counter()
        path = t.journal_path
    finally:
        t.disable()
    first, second = t.recorded()
    assert (first.start, first.end) == (begin, begin + 0.25)
    assert first.attrs == {"k": "v"}
    assert second.end == pytest.approx(now, abs=0.05)
    assert second.end - second.start == pytest.approx(0.25)
    from avenir_tpu.telemetry.journal import read_events

    evs = [e for e in read_events(path) if e["ev"].startswith("span.")]
    stamps = {(e["name"], e["ev"]): e.get("at", e["ts"]) for e in evs}
    # the journal's wall stamps keep the interval too: it ended 1.75 s ago
    assert stamps[("ends.now", "span.close")] - \
        stamps[("measured.elsewhere", "span.close")] == \
        pytest.approx(1.75, abs=0.05)
    assert stamps[("measured.elsewhere", "span.close")] - \
        stamps[("measured.elsewhere", "span.open")] == \
        pytest.approx(0.25, abs=1e-3)


# -- the span sites --------------------------------------------------------------

def test_batcher_round_trip_is_one_tree_a_request(rng, recorder, session):
    servable = _servable(rng)
    registry = ModelRegistry().add("knn", servable)
    lines = _lines(rng, 12)
    latency = {}
    with BucketedMicrobatcher(registry, bucket_sizes=(1, 4, 8),
                              flush_deadline_ms=2.0) as batcher:
        recorder.recorded(clear=True)          # the warm-up's spans
        t0 = time.perf_counter()
        reqs = [batcher.submit_nowait("knn", ln, rid=f"r{i}")
                for i, ln in enumerate(lines)]
        for i, req in enumerate(reqs):
            req.wait(30.0)
            latency[f"r{i}"] = time.perf_counter() - t0
    rec = _by_name(recorder.recorded())
    assert recorder.dropped == 0
    by_id = {r.span_id: r for rs in rec.values() for r in rs}
    dispatches = {r.span_id: r for r in rec["serve.dispatch"]}
    assert sorted(q.attrs["rid"] for q in rec["serve.queue"]) == \
        sorted(latency)
    for q in rec["serve.queue"]:
        d = dispatches[q.attrs["dispatch"]]
        assert q.attrs["model"] == d.attrs["model"] == "knn"
        assert q.end == d.start                # taken where the dispatch opens
        # queue + dispatch lie inside the caller's own latency
        assert (q.end - q.start) + (d.end - d.start) <= latency[q.attrs["rid"]]
    assert sum(d.attrs["rows"] for d in dispatches.values()) == len(lines)
    assert all(d.attrs["bucket"] >= d.attrs["rows"]
               for d in dispatches.values())

    def chain(record):
        names = []
        while record is not None:
            names.append(record.name)
            record = by_id.get(record.parent_id)
        return names[::-1]

    for search in rec["knn.search"]:
        assert chain(search) == ["serve.dispatch", "servable.score",
                                 "knn.classify", "knn.search"]
        assert search.attrs["path"] == "xla"   # the CPU's route
        assert search.attrs["kernel_rows"] == search.attrs["rows"]
    for name, parent in (("serve.slot", "serve.dispatch"),
                         ("serve.reply", "serve.dispatch"),
                         ("servable.encode", "servable.score"),
                         ("servable.format", "servable.score"),
                         ("knn.weights", "knn.classify"),
                         ("knn.vote", "knn.classify")):
        assert len(rec[name]) == len(dispatches)
        assert all(by_id[r.parent_id].name == parent for r in rec[name])
    # every line took the native encoder: no Python parse opened
    assert "servable.parse" not in rec
    for e in rec["servable.encode"]:
        d = dispatches[by_id[e.parent_id].parent_id]
        assert e.attrs["rows"] == e.attrs["native_rows"] == d.attrs["rows"]
    scores = rec["servable.score"]
    assert [s.attrs["pad_to"] for s in scores] == \
        [d.attrs["bucket"] for d in dispatches.values()]
    # no program span takes a name the benchmark's own wrappers use
    assert not set(rec) & {"window", "score_lines", "encode.transform",
                           "knn.predict"}


def test_fused_search_spans_tile_the_call_and_count_the_tile(
        rng, recorder, fused):
    servable = _servable(rng, class_cond=True)
    tracer = tel.tracer()
    tracer.enable()
    try:
        fused.extend([1, 5])
        servable.score_lines(_lines(rng, 7), pad_to=8)
        del fused[:]
        servable.score_lines(_lines(rng, 600, "v"), pad_to=600)
    finally:
        tracer.disable()
    rec = _by_name(recorder.recorded())
    first, second = rec["knn.search"]
    assert (first.attrs["path"], first.attrs["rows"],
            first.attrs["kernel_rows"], first.attrs["refused"]) == \
        ("fused", 8, pallas_knn.query_rows(8), 2)
    assert pallas_knn.query_rows(8) == 128      # the smallest tile
    # a block over 512 rows still reads whole 512-row tiles
    assert (second.attrs["rows"], second.attrs["kernel_rows"],
            second.attrs["refused"]) == (600, 2 * pallas_knn.TM, 0)
    assert [(s.attrs["rows"], s.attrs["pad_to"])
            for s in rec["servable.score"]] == [(7, 8), (600, 600)]
    # the parts add up: start -> stage | stage -> readback end | fallback |
    # the rest tile the whole servable.score span, nothing overlapping
    score, stage, back = (rec[n][0] for n in
                          ("servable.score", "knn.stage", "knn.readback"))
    (fall,) = rec["knn.fallback"]
    assert score.start <= stage.start <= stage.end <= back.start \
        <= back.end <= fall.start <= fall.end <= score.end
    parts = ((stage.start - score.start) + (back.end - stage.start)
             + (fall.end - fall.start)
             + (fall.start - back.end) + (score.end - fall.end))
    assert parts == pytest.approx(score.end - score.start, abs=1e-9)
    assert {r.parent_id for r in (stage, back, fall)} == {first.span_id}
    assert len(rec["knn.stage"]) == len(rec["knn.readback"]) == 2


def test_forced_certificate_failure_records_the_fallback_once_as_new(
        rng, recorder, fused):
    servable = _servable(rng)
    tracer = tel.tracer()
    tracer.enable()
    try:
        fused.extend([0, 3, 4])
        for _ in range(2):
            servable.score_lines(_lines(rng, 8), 8)
        fused.pop()
        servable.score_lines(_lines(rng, 8), 8)
    finally:
        tracer.disable()
    falls = _by_name(recorder.recorded())["knn.fallback"]
    assert [(f.attrs["rows"], f.attrs["new_program"]) for f in falls] == \
        [(3, True), (3, False), (2, True)]
    assert servable.model.cert_fallback_rows == 8
    assert servable.model.fused_rows == 24


def test_recompiles_counts_the_first_dispatch_of_each_refused_count(
        rng, fused, tmp_path):
    from avenir_tpu.telemetry.journal import read_events

    servable = _servable(rng)
    registry = ModelRegistry().add("knn", servable)
    tracer = tel.tracer()
    tracer.enable(str(tmp_path))
    try:
        with BucketedMicrobatcher(registry, bucket_sizes=(4,),
                                  flush_deadline_ms=1.0) as batcher:
            def recompiles():
                return batcher.counters.as_dict().get(
                    "Serving.knn", {}).get("recompiles", 0)

            batcher.submit("knn", _lines(rng, 1)[0])
            assert recompiles() == 0           # certified: warmed shapes only
            fused.append(0)
            batcher.submit("knn", _lines(rng, 1)[0])
            assert recompiles() == 1           # first dispatch with 1 refused
            batcher.submit("knn", _lines(rng, 1)[0])
            assert recompiles() == 1           # that program is known now
        path = tracer.journal_path
    finally:
        tracer.disable()
        tracer.recorded(clear=True)
    assert ("fallback", 1) in servable.compile_keys
    events = [e for e in read_events(path) if e["ev"] == "recompile"]
    assert len(events) == 1 and "fallback" in events[0]["keys"][0]
