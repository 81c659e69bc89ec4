"""Least time ONE chip could take for its share of the window's searches —
``lib/work.py::knn_search_work`` with the references divided by the cell's
chips, the same count whatever implements the search — over the device time
the slowest chip spent in the search program."""

from lib import peaks, work
from readers._chips import slowest_program


def read(ctx, program):
    snap = ctx["snapshot"]
    hit = slowest_program(ctx, program)
    if hit is None or not snap["calls"]:
        return None
    peak = peaks.peak_for(ctx["device"]["kind"])
    refs = snap["refs"] / int(ctx["cell"]["chips"])
    least = sum(work.least_time_s(*work.knn_search_work(
        snap["attrs"], c["rows"], refs, snap["k"]), peak)[0]
        for c in snap["calls"])
    return 100.0 * least / hit[0]
