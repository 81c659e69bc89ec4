"""Host milliseconds a fused search spends around the device's own work:
(sum over the window's fused searches of ``knn.stage`` start ->
``knn.readback`` end, minus the device time of the window's executions of
the search program on ``XLA Modules``) / searches.  Durations only, so the
skew between the host's and the device's clock in the trace does not enter.
An execution count that differs from the count of searches is an error."""

from lib import program_spans
from lib import trace as tracelib
from readers._device import window_events


def read(ctx, program):
    modules = window_events(ctx, "modules")
    calls = program_spans.fused_calls(ctx)
    if modules is None or calls is None:
        return None
    device, executions = tracelib.program_time(modules, program)
    if executions != len(calls):
        raise RuntimeError(
            f"{executions} executions of {program} in the traced window "
            f"but {len(calls)} fused searches in the program's spans")
    host = sum(c["readback"].end - c["stage"].start for c in calls)
    return 1e3 * (host - device) / len(calls)
