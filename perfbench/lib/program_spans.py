"""The program's own spans as the per-layer readers use them.

``avenir_tpu/telemetry/spans.py`` keeps every span that closed while a
profiler session ran in a bounded in-memory recorder, stamped with
``time.perf_counter()`` — the clock of the benchmark's own host records
(``window.start/end``, ``requests[].t0/t1``, ``calls[].t0/t1``).  This file
fetches them, keeps those of the window, ties one call's spans together, and
maps recorder time to the trace's clock.  A program with no recorder (the
parent of the PR that brought it) gives ``None`` everywhere, and every reader
built on this file then leaves its metric out.

A record has ``name, start, end, span_id, parent_id, trace_id, thread,
attrs``; a test may put a hand-made list under ``ctx["program_spans"]``.
"""

from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Optional, Sequence

from lib.trace import Event

QUEUE = "serve.queue"        # a request's wait, not work of a host thread
SCORE, SEARCH = "servable.score", "knn.search"
STAGE, READBACK, FALLBACK = "knn.stage", "knn.readback", "knn.fallback"
CALL_ANNOTATION = "score_lines"   # the benchmark's own span around a call


def _recorder():
    try:
        from avenir_tpu.telemetry.spans import tracer
    except ImportError:
        return None
    t = tracer()
    return t if hasattr(t, "recorded") else None


def window_spans(ctx: Dict) -> Optional[List]:
    """The recorded spans that started inside the window, in the order they
    closed; ``None`` where there is no recorder or it holds none of them.
    Raises where the recorder dropped spans of the window."""
    if "program_spans" not in ctx:
        rec = _recorder()
        spans = None
        if rec is not None:
            records = rec.recorded()
            w = ctx["window"]
            if rec.dropped and records and records[0].end >= w["start"]:
                raise RuntimeError(
                    f"the span recorder dropped {rec.dropped} spans and its "
                    f"oldest record closed after the window opened")
            spans = [r for r in records
                     if w["start"] <= r.start <= w["end"]] or None
            print(f"program spans: {len(spans or ())} of {len(records)} "
                  f"recorded spans started in the window, {rec.dropped} "
                  f"dropped", file=sys.stderr)
        ctx["program_spans"] = spans
    return ctx["program_spans"]


def named(spans: Sequence, name: str) -> List:
    return [r for r in spans if r.name == name]


def calls(spans: Sequence) -> List[Dict]:
    """One entry a ``servable.score`` span, with the spans of the search
    under it: ``score``, and where the call took them ``search``, ``stage``,
    ``readback`` and ``fallback`` (a list)."""
    by_id = {r.span_id: r for r in spans}
    out = {r.span_id: {"score": r, "fallback": []}
           for r in spans if r.name == SCORE}
    for r in spans:
        if r.name not in (SEARCH, STAGE, READBACK, FALLBACK):
            continue
        up = r
        while up is not None and up.name != SCORE:
            up = by_id.get(up.parent_id)
        if up is None:
            continue
        call = out[up.span_id]
        if r.name == FALLBACK:
            call["fallback"].append(r)
        else:
            call[r.name.split(".")[1]] = r
    return list(out.values())


def fused_calls(ctx: Dict) -> Optional[List[Dict]]:
    """The window's calls whose search took the fused path (they have a
    ``knn.stage`` and a ``knn.readback``); ``None`` where there are none."""
    spans = window_spans(ctx)
    if spans is None:
        return None
    return [c for c in calls(spans)
            if "stage" in c and "readback" in c] or None


def offset(ctx: Dict) -> Optional[float]:
    """Seconds to add to a recorder time to get the trace's time: the median
    over the window's calls of (start of the benchmark's ``score_lines``
    annotation in the trace) - (the host stamp ``calls[i].t0`` taken just
    before it), paired in order; a trace that holds another number of
    annotations than calls were stamped is an error.  Prints the residual
    spread on standard error."""
    if "program_span_offset" in ctx:
        return ctx["program_span_offset"]
    trace, stamps = ctx.get("trace"), ctx["snapshot"]["calls"]
    starts = sorted(s for n, s, _d in (trace.spans if trace else ())
                    if n == CALL_ANNOTATION)
    t0s = sorted(c["t0"] for c in stamps)
    result = None
    if starts and t0s:
        if len(starts) != len(t0s):
            raise RuntimeError(
                f"{len(starts)} {CALL_ANNOTATION} annotations in the trace "
                f"but {len(t0s)} calls stamped on the host")
        deltas = [a - t for a, t in zip(starts, t0s)]
        result = statistics.median(deltas)
        resid = sorted(d - result for d in deltas)
        q = (statistics.quantiles(resid, n=4) if len(resid) > 1
             else [0.0, 0.0, 0.0])
        ctx["program_span_residual_ms"] = 1e3 * (q[2] - q[0])
        print(f"program spans: recorder -> trace clock over "
              f"{len(deltas)} calls, residual inter-quartile "
              f"{ctx['program_span_residual_ms']:.4f} ms, widest "
              f"{1e3 * max(abs(resid[0]), abs(resid[-1])):.4f} ms",
              file=sys.stderr)
    ctx["program_span_offset"] = result
    return result


def aligned(ctx: Dict, leaves_only: bool = False) -> Optional[List[Event]]:
    """The window's spans as trace events ``(name, start_s, duration_s)`` on
    the trace's clock, ``serve.queue`` left out; with ``leaves_only`` only
    the spans that have no child."""
    spans = window_spans(ctx)
    off = offset(ctx) if spans is not None else None
    if off is None:
        return None
    parents = {r.parent_id for r in spans}
    return [(r.name, r.start + off, r.end - r.start) for r in spans
            if r.name != QUEUE
            and not (leaves_only and r.span_id in parents)]
