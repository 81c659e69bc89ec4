"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and, by the names given there, its
configuration file, its traffic file and its metrics' readers; builds the
system from the seed, warms the cell's shapes, measures for ``--seconds``,
compares a sample of what the timed path produced with the plain reference,
and prints one JSON object as the last line of standard output.  See
``perfbench/README.md`` for the files a cell is made of.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
from typing import Dict, List, Optional   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "window"


def _load(path: str, root: Optional[str] = None) -> Dict:
    with open(os.path.join(root or ROOT, path)) as fh:
        return json.load(fh)


def load_cell(workload: str, root: Optional[str] = None) -> Dict:
    """The cell with everything it names: configuration and traffic.
    ``root`` is the checkout (a test may point it at a copy of its own)."""
    bench = _load("BENCHMARK.json", root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {"cell": cell, "bench": bench, "config": _load(conf["file"], root),
            "traffic": _load(f"perfbench/traffic/{cell['traffic']}.json",
                             root)}


def metrics_for(bench: Dict, section: str, workload: str) -> List[Dict]:
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, ctx: Dict) -> Optional[float]:
    """A metric's value by its own files: ``metrics/<name>.json`` names the
    reader (``readers/<reader>.py``) and its arguments."""
    spec = _load(f"perfbench/metrics/{name}.json", ctx.get("root"))
    reader = importlib.import_module(f"readers.{spec['reader']}")
    return reader.read(ctx, **spec.get("args", {}))


def setup_jax(chips: int, rehearse: bool):
    """Pin what has to be pinned before JAX is first used, and refuse to go
    on where there is no TPU with the chips the cell asks for."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a Pallas program's cache key must not move with the caller's line
    # numbers (PERF.md, PR 23 Finding 5)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        print(f"perfbench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        raise SystemExit(2)
    return jax, devices


def sample_rows(requests: List[Dict], n: int, seed: int) -> List[Dict]:
    """``n`` answered request rows drawn from the seed (the window's last
    request always among them), each with its line and its reply."""
    import numpy as np

    done = [r for r in requests if r["error"] is None]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 2])
    total = sum(len(r["lines"]) for r in done)
    n = min(n, total)
    starts = np.cumsum([0] + [len(r["lines"]) for r in done])
    flat = rng.choice(total, size=n, replace=False)
    flat[0] = total - 1
    out = []
    for f in sorted(set(int(x) for x in flat)):
        ri = int(np.searchsorted(starts, f, side="right")) - 1
        row = f - int(starts[ri])
        out.append({"line": done[ri]["lines"][row],
                    "reply": done[ri]["replies"][row]})
    return out


def run(argv=None, rehearse: Optional[Dict] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = (rehearse or {}).get("root")
    loaded = load_cell(args.workload, root)
    cell, bench = loaded["cell"], loaded["bench"]
    config, traffic = loaded["config"], loaded["traffic"]
    jax, devices = setup_jax(int(cell["chips"]), rehearse is not None)
    from lib import trace as tracelib, traffic as trafficlib

    family = importlib.import_module(f"families.{config['family']}")
    system = family.System(config, args.seed, (rehearse or {}).get("refs"))
    pool = system.query_pool(int(traffic["pool_rows"]))
    entry = system.open(traffic)

    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_kw: compiles.append(secs)
        if name == COMPILE_EVENT else None)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    system.start_window()
    before = len(compiles)
    setup_s = time.perf_counter() - T_PROCESS
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        window = trafficlib.run_closed_loop(entry, pool, traffic,
                                            args.seconds)
    in_window = len(compiles) - before
    trace = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
        trace = tracelib.load_xplane(tracelib.find_xplane(trace_dir),
                                     (WINDOW_SPAN, *family.SPAN_NAMES))
        keep = os.environ.get("PERFBENCH_KEEP_TRACE")
        if keep:                      # a builder looking at a trace by hand
            shutil.copytree(trace_dir, keep, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    snapshot = system.end_window()
    stats = [d.memory_stats() or {} for d in devices[:int(cell["chips"])]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  int(s.get("peak_bytes_in_use", 0)) for s in stats)}

    requests = window["requests"]
    failed = [r for r in requests if r["error"] is not None]
    sample = sample_rows(requests, int(traffic["check_rows"]), args.seed)
    produced = system.produced(sample)
    system.close()
    t_ref = time.perf_counter()
    numbers = system.check(sample, produced) if sample else {}
    reference_s = time.perf_counter() - t_ref
    limits = config["limits"]
    # a number that is missing or not finite has failed; 1e30 keeps the line
    # valid JSON
    compared = {k: {"value": v if math.isfinite(v) else 1e30, "limit": lim}
                for k, lim in limits.items()
                for v in [float(numbers.get(k, math.inf))]}
    correct = bool(sample) and all(
        c["value"] <= c["limit"] for c in compared.values())

    ctx = {"args": args, "root": root, "cell": cell, "config": config,
           "traffic": traffic, "window": window, "snapshot": snapshot,
           "setup_s": setup_s, "window_compiles": in_window, "trace": trace,
           "device": device}
    window_ops = None
    win = [ev for ev in trace.spans if ev[0] == WINDOW_SPAN] if trace else []
    if win and any(trace.ops):
        t0, t1 = win[0][1], win[0][1] + win[0][2]
        window_ops = [tracelib.clip(o, t0, t1)
                      for o in trace.ops[:int(cell["chips"])]]
        ctx["trace_window"] = (t0, t1)
        device["busy_s"] = sum(map(tracelib.busy_seconds,
                                   window_ops)) / len(window_ops)
        device["window_s"] = t1 - t0
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, section, cell["name"]):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(requests),
              "failed": len(failed), "metrics": metrics, "device": device}
    if window_ops is not None:
        spans = [s for s in trace.spans if s[0] != WINDOW_SPAN]
        result["breakdown"] = {
            "device_ops": [list(x) for x in tracelib.top_ops(window_ops[0])],
            "idle_gaps": [list(x) for x in tracelib.idle_gaps(
                window_ops[0], spans, t0, t1)]}
    result["checked"] = {k: v for k, v in numbers.items() if k not in limits}
    result["checked"]["reference_s"] = reference_s
    if failed:
        result["checked"]["first_error"] = failed[0]["error"]
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    result = run(argv)
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
