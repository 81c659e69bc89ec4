"""What the trace readers share: the window's device events."""

from lib import trace as tracelib


def window_events(ctx, line):
    trace = ctx.get("trace")
    if trace is None or "trace_window" not in ctx:
        return None
    t0, t1 = ctx["trace_window"]
    events = getattr(trace, line)
    if not events or not events[0]:
        return None
    return tracelib.clip(events[0], t0, t1)
