"""One program counter over another, both counted over the window."""


def read(ctx, num, den, scale=1.0):
    c = ctx["snapshot"]["counters"]
    if not c.get(den):
        return None
    return scale * c.get(num, 0.0) / c[den]
