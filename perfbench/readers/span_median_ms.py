"""Median duration of the program's spans of one name over the window."""

import statistics

from lib import program_spans


def read(ctx, span):
    spans = program_spans.window_spans(ctx)
    durs = [r.end - r.start
            for r in program_spans.named(spans or (), span)]
    return statistics.median(durs) * 1e3 if durs else None
