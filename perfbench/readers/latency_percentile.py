"""A percentile of submit -> reply over ALL requests of the window, on the
caller's clock.  A request that failed, was shed or timed out misses any
limit: it counts with the request timeout or its own wait, whichever is
longer."""

from lib.traffic import percentile


def read(ctx, q):
    timeout_s = ctx["traffic"].get("batcher", {}).get(
        "serve.request.timeout.ms", 0.0) / 1e3
    waits = [(r["t1"] - r["t0"]) if r["error"] is None
             else max(r["t1"] - r["t0"], timeout_s)
             for r in ctx["window"]["requests"]]
    if not waits:
        return None
    return percentile(waits, q) * 1e3
