"""Least time the chip could take for the searches of the window, over the
device time the search program took.  The work is the algorithm's
(lib/work.py: 3*A*M*N operations over REAL query rows), not the packed
operand's."""

from lib import peaks, work
from lib import trace as tracelib
from readers._device import window_events


def read(ctx, program):
    modules = window_events(ctx, "modules")
    snap = ctx["snapshot"]
    if modules is None or not snap["calls"]:
        return None
    total, calls = tracelib.program_time(modules, program)
    if not calls:
        return None
    peak = peaks.peak_for(ctx["device"]["kind"])
    least = sum(work.least_time_s(*work.knn_search_work(
        snap["attrs"], c["rows"], snap["refs"], snap["k"]), peak)[0]
        for c in snap["calls"])
    return 100.0 * least / total
