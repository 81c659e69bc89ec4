"""Share of the traced window in which the device is idle (``XLA Ops``, as
``device_idle_pct``) and no leaf span of the program is open on the aligned
clock: idle time the program's spans cannot put a name of a piece of work
to.  Also prints the device's idle seconds by innermost program span."""

import sys

from lib import program_spans
from lib import trace as tracelib
from readers._device import window_events

OUTSIDE = "outside_spans"


def read(ctx):
    ops = window_events(ctx, "ops")
    if ops is None:
        return None
    spans = program_spans.aligned(ctx)
    if spans is None:
        return None
    t0, t1 = ctx["trace_window"]
    by_span = tracelib.idle_gaps(ops, spans, t0, t1, outside=OUTSIDE, n=64)
    print("program spans: device idle seconds by innermost program span: "
          + ", ".join(f"{name} {secs:.4f}" for name, secs in by_span),
          file=sys.stderr)
    leaves = program_spans.aligned(ctx, leaves_only=True)
    unexplained = dict(tracelib.idle_gaps(
        ops, leaves, t0, t1, outside=OUTSIDE, n=64)).get(OUTSIDE, 0.0)
    return 100.0 * unexplained / (t1 - t0)
