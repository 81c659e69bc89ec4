"""Mean, per call that took the fused search, of one phase of
``servable.score``: ``parse_encode`` is its start -> ``knn.stage`` start
(parse, encode, pad, the way down to the search); ``vote_format`` is
``knn.readback`` end -> its end, the exact scan of refused rows
(``knn.fallback``) left out (counters, kernel weights, posterior gather,
vote, reply lines)."""

from lib import program_spans


def read(ctx, phase):
    calls = program_spans.fused_calls(ctx)
    if calls is None:
        return None
    if phase == "parse_encode":
        total = sum(c["stage"].start - c["score"].start for c in calls)
    elif phase == "vote_format":
        total = sum(c["score"].end - c["readback"].end
                    - sum(f.end - f.start for f in c["fallback"])
                    for c in calls)
    else:
        raise ValueError(f"no phase {phase!r}")
    return 1e3 * total / len(calls)
