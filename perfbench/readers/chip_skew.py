"""(Busy time of the cell's busiest chip - of its least busy chip) / the
busiest's, over the traced window (union of ``XLA Ops`` intervals a chip):
how unevenly a program that runs on every chip at once loads them."""

from lib import trace as tracelib
from readers._chips import window_events_per_chip


def read(ctx):
    ops = window_events_per_chip(ctx, "ops")
    if ops is None or len(ops) < 2:
        return None
    busy = [tracelib.busy_seconds(o) for o in ops]
    return 100.0 * (max(busy) - min(busy)) / max(busy)
