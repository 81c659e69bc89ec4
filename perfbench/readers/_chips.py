"""What the readers of a cell on several chips share: the window's device
events of EVERY chip the cell holds (``readers/_device.py`` gives the first
chip's alone)."""

from lib import trace as tracelib


def window_events_per_chip(ctx, line):
    """One list of events a chip, cut to the traced window; ``None`` where
    the run was not traced or a chip's line is empty."""
    trace = ctx.get("trace")
    if trace is None or "trace_window" not in ctx:
        return None
    t0, t1 = ctx["trace_window"]
    chips = getattr(trace, line)[:int(ctx["cell"]["chips"])]
    if not chips or not all(chips):
        return None
    return [tracelib.clip(events, t0, t1) for events in chips]


def slowest_program(ctx, program):
    """(device seconds, executions) of the programs whose name holds
    ``program`` on the chip that spent longest in them; ``None`` where no
    chip ran one."""
    chips = window_events_per_chip(ctx, "modules")
    if chips is None:
        return None
    total, calls = max(tracelib.program_time(m, program) for m in chips)
    return (total, calls) if calls else None
