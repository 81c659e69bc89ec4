"""Share of the window (host clock) that the program's spans of one name
took; 0 where the program recorded spans but none of that name."""

from lib import program_spans


def read(ctx, span):
    spans = program_spans.window_spans(ctx)
    if spans is None:
        return None
    w = ctx["window"]
    return 100.0 * sum(r.end - r.start
                       for r in program_spans.named(spans, span)) \
        / (w["end"] - w["start"])
