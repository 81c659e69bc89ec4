"""Mean, per span of one name, of its duration minus the part its child
spans of another name cover: the span's own time around that child."""

from lib import program_spans


def read(ctx, span, child):
    spans = program_spans.window_spans(ctx)
    own = program_spans.named(spans or (), span)
    if not own:
        return None
    covered = {}
    for r in program_spans.named(spans, child):
        covered[r.parent_id] = covered.get(r.parent_id, 0.0) + r.end - r.start
    return 1e3 * sum((r.end - r.start) - covered.get(r.span_id, 0.0)
                     for r in own) / len(own)
