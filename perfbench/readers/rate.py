"""Rows answered in the window over the whole window's seconds."""


def read(ctx):
    w = ctx["window"]
    rows = sum(len(r["lines"]) for r in w["requests"] if r["error"] is None)
    return rows / (w["end"] - w["start"]) if rows else None
