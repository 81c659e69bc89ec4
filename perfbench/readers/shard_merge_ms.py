"""Device milliseconds a block spends merging the shards' answers: the
``XLA Ops`` events of the sharded search whose instruction is an all-gather
or works on the gathered candidates — an array of [rows, chips·k] or with an
axis of ``chips`` after ``rows`` (the final ``top_k`` and its gathers) —
over the executions of ``program``.  Of the cell's chips the one that spent
LEAST there: an all-gather lasts until the last chip arrives, so every other
chip's reading also holds its wait for that one (``shard_skew_pct``)."""

from lib import trace as tracelib
from readers._chips import window_events_per_chip


def read(ctx, program):
    ops = window_events_per_chip(ctx, "ops")
    modules = window_events_per_chip(ctx, "modules")
    calls = ctx["snapshot"]["calls"]
    if ops is None or modules is None or not calls:
        return None
    chips, k = int(ctx["cell"]["chips"]), int(ctx["snapshot"]["k"])
    rows = {c["pad_to"] for c in calls}
    marks = ["all-gather"] + [m for r in rows
                              for m in (f"[{r},{chips * k}]", f"[{r},{chips},")]
    per_chip = []
    for chip_ops, chip_modules in zip(ops, modules):
        runs = tracelib.program_time(chip_modules, program)[1]
        merge = [dur for name, _s, dur in chip_ops
                 if any(m in name for m in marks)]
        if not runs or not merge:
            return None
        per_chip.append(sum(merge) / runs)
    return 1e3 * min(per_chip)
