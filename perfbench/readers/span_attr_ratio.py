"""One attribute of the program's spans of one name over another, both
summed over the window."""

from lib import program_spans


def read(ctx, span, num, den, scale=1.0):
    spans = program_spans.window_spans(ctx)
    own = program_spans.named(spans or (), span)
    total = sum(r.attrs.get(den, 0) for r in own)
    if not total:
        return None
    return scale * sum(r.attrs.get(num, 0) for r in own) / total
