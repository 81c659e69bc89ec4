"""One program counter, counted over the window, over the query rows the
window's calls handed to the program (``snapshot.calls[].pad_to``: a call
scores its padded block)."""


def read(ctx, num, scale=1.0):
    snap = ctx["snapshot"]
    rows = sum(c["pad_to"] for c in snap["calls"])
    if num not in snap["counters"] or not rows:
        return None
    return scale * snap["counters"][num] / rows
