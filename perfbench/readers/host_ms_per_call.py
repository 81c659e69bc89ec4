"""The benchmark's span around ``score_lines`` minus the device time of the
programs that ran in the window, per call: parse, encode, vote, formatting
and the launch path."""

from readers._device import window_events


def read(ctx):
    modules = window_events(ctx, "modules")
    calls = ctx["snapshot"]["calls"]
    if modules is None or not calls:
        return None
    span = sum(c["t1"] - c["t0"] for c in calls)
    device = sum(dur for _n, _s, dur in modules)
    return 1e3 * (span - device) / len(calls)
