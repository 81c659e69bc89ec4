"""Device milliseconds per execution of the programs whose name holds
``program``, from the profiler trace's module line."""

from lib import trace as tracelib
from readers._device import window_events


def read(ctx, program):
    modules = window_events(ctx, "modules")
    if modules is None:
        return None
    total, calls = tracelib.program_time(modules, program)
    return 1e3 * total / calls if calls else None
