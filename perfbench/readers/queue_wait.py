"""Median, per request, of (submit -> reply on the caller's clock) minus the
span of the ``score_lines`` call that answered it: what the request spent
waiting for a bucket and for the dispatch in flight."""

import bisect
import statistics


def read(ctx):
    calls = sorted(ctx["snapshot"]["calls"], key=lambda c: c["t1"])
    ends = [c["t1"] for c in calls]
    waits = []
    for r in ctx["window"]["requests"]:
        if r["error"] is not None:
            continue
        i = bisect.bisect_right(ends, r["t1"]) - 1
        if i < 0 or calls[i]["t0"] < r["t0"] - 1.0:
            continue
        waits.append((r["t1"] - r["t0"]) - (calls[i]["t1"] - calls[i]["t0"]))
    return statistics.median(waits) * 1e3 if waits else None
