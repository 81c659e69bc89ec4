"""The ``knn_x4`` family and the readers of its cell: the route check (a
program without the predicate, or whose predicate answers another route,
does not run the cell: status 3 before a reference row is made), the five
per-chip readers on a hand-made four-chip trace, and a rehearsal of the whole
cell on four forced host devices — sound, and with three shards answered."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import PERFBENCH, ROOT
from lib import trace as tracelib

CELL = "knn_shard4_b4096"


# -- the benchmark's entries ------------------------------------------------------

def test_the_cell_reports_thirteen_per_layer_metrics():
    loaded = run.load_cell(CELL)
    assert loaded["cell"]["chips"] == 4
    assert loaded["config"]["family"] == "knn_x4"
    assert loaded["config"]["refs"] == 4 * 13 * 2 ** 20
    one = run.load_cell("knn_bulk_b4096")["config"]
    for key in ("schema", "settings", "limits", "reduced"):
        assert loaded["config"][key] == one[key], key
    per_layer = {m["name"] for m in
                 run.metrics_for(loaded["bench"], "per_layer", CELL)}
    assert per_layer == {
        "host_ms_per_call", "cert_fallback_pct", "device_idle_pct",
        "window_compiles", "parse_encode_ms", "vote_format_ms",
        "tile_fill_pct", "fallback_window_pct", "shard_search_device_ms",
        "knn_x4_roofline", "merge_device_ms", "shard_skew_pct",
        "sharded_fused_pct"}
    assert {m["name"] for m in run.metrics_for(
        loaded["bench"], "end_to_end", CELL)} == {"queries_per_s", "setup_s"}


# -- the route check ----------------------------------------------------------------

@pytest.fixture()
def family(monkeypatch):
    from families import knn_x4
    from lib import data

    def no_rows(*_a, **_k):
        raise AssertionError("reference rows made before the route check")

    monkeypatch.setattr(data, "make_refs", no_rows)
    return knn_x4


def _config():
    return run.load_cell(CELL)["config"]


def test_program_without_the_predicate_does_not_run_the_cell(
        family, monkeypatch, capsys):
    monkeypatch.delattr(family.mknn, "sharded_route")
    with pytest.raises(SystemExit) as exit_:
        family.System(_config(), 7)
    assert exit_.value.code == 3
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "no avenir_tpu.models.knn.sharded_route" in err[0]


def test_another_route_does_not_run_the_cell(family, capsys):
    """Here, off a TPU, the program's own predicate answers the scan (or no
    sharded route at all on one device)."""
    with pytest.raises(SystemExit) as exit_:
        family.System(_config(), 7)
    assert exit_.value.code == 3
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "sharded_route answers" in err[0]


def test_sharded_fused_route_goes_on_to_the_references(family, monkeypatch):
    monkeypatch.setattr(family.mknn, "sharded_route",
                        lambda mesh, metric, k, refs: "sharded_fused")
    with pytest.raises(AssertionError, match="before the route check"):
        family.System(_config(), 7)


# -- the readers ----------------------------------------------------------------------

SEARCH = "jit__shard_search(123)"
MERGE_OPS = [
    ("%all-gather.5 = s32[4,21,4096]{2,1,0} all-gather(s32[21,4096] %p)", 2),
    ("%top_k.28 = (f32[4096,40]{0,1}, s32[4096,40]{0,1}) sort(%a, %b)", 3),
    ("%slice_fusion = f32[4096,4,10]{0,2,1} fusion(s32[4096,4,21] %r)", 1),
]
KERNEL_OP = "%tpu_custom_call.1 = (s32[4096,6656]{1,0}) custom-call(%q, %r)"


def _ctx(wait_ms=(0.0, 0.4, 0.1, 0.2), kernel_ms=(150.0, 151.0, 150.5, 152.0)):
    """Two blocks on four chips inside a window [10, 11]: chip c's kernel
    takes kernel_ms[c], its all-gather 0.2 ms + wait_ms[c]."""
    trace = tracelib.Trace()
    for chip in range(4):
        ops, modules = [], []
        for block in range(2):
            at = 10.1 + 0.4 * block
            ops.append((KERNEL_OP, at, kernel_ms[chip] * 1e-3))
            t = at + kernel_ms[chip] * 1e-3
            for name, tenths in MERGE_OPS:
                dur = tenths * 1e-4 + (wait_ms[chip] * 1e-3
                                       if "all-gather" in name else 0.0)
                ops.append((name, t, dur))
                t += dur
            modules.append((SEARCH, at, t - at))
        modules.append(("jit_step(9)", 10.95, 0.01))
        trace.ops.append(ops)
        trace.modules.append(modules)
    calls = [{"t0": 0.0, "t1": 0.2, "rows": 4096, "pad_to": 4096}] * 2
    return {"trace": trace, "trace_window": (10.0, 11.0),
            "cell": {"chips": 4}, "device": {"kind": "TPU v5 lite"},
            "snapshot": {"calls": calls, "attrs": 9, "refs": 4 * (13 << 20),
                         "k": 10, "counters": {"shard_fused_rows": 8192.0}}}


def test_readers_take_the_slowest_chip_and_the_least_waiting_merge():
    ctx = _ctx()
    # chip 3: 152.0 ms of kernel + 0.6 ms of merge ops + 0.2 ms of wait
    assert run.read_metric("shard_search_device_ms", ctx) == \
        pytest.approx(152.8, abs=1e-6)
    # chip 0 waits for nobody: 0.2 + 0.3 + 0.1 ms
    assert run.read_metric("merge_device_ms", ctx) == \
        pytest.approx(0.6, abs=1e-6)
    busy = [2 * (k + 0.6 + w) for k, w in
            zip((150.0, 151.0, 150.5, 152.0), (0.0, 0.4, 0.1, 0.2))]
    assert run.read_metric("shard_skew_pct", ctx) == pytest.approx(
        100.0 * (max(busy) - min(busy)) / max(busy), rel=1e-9)
    # one chip's share: 3 * 9 * 4096 * 13 * 2^20 operations at 197e12 a second
    least = 2 * 3.0 * 9 * 4096 * (13 << 20) / 197e12
    assert run.read_metric("knn_x4_roofline", ctx) == pytest.approx(
        100.0 * least / (2 * 152.8e-3), rel=1e-9)
    assert run.read_metric("sharded_fused_pct", ctx) == 100.0


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """An untraced run, a program that ran no sharded search (the parent's
    scan), a program without the counter: the metric is left out."""
    untraced = dict(_ctx(), trace=None)
    other = _ctx()
    for chip in other["trace"].modules:
        chip[:] = [("jit_step(9)", s, d) for _n, s, d in chip]
    for chip in other["trace"].ops:
        chip[:] = [(KERNEL_OP, s, d) for _n, s, d in chip]
    other["snapshot"]["counters"] = {}
    for name in ("shard_search_device_ms", "knn_x4_roofline",
                 "merge_device_ms"):
        assert run.read_metric(name, untraced) is None, name
        assert run.read_metric(name, other) is None, name
    assert run.read_metric("shard_skew_pct", untraced) is None
    assert run.read_metric("sharded_fused_pct", other) is None
    one_chip = dict(_ctx(), cell={"chips": 1})
    assert run.read_metric("shard_skew_pct", one_chip) is None


# -- the whole cell, rehearsed on four host devices -------------------------------------

def _rehearsal_root(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    json.dump({"loop": "closed", "clients": 1, "rows_per_request": 512,
               "entry": "score_lines", "pool_rows": 2048, "check_rows": 256},
              open(tmp_path / "perfbench/traffic/bulk_b512.json", "w"))
    bench["workloads"].append({"name": "x4_b512", "config": "elearn_knn_x4",
                               "traffic": "bulk_b512", "chips": 4,
                               "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("x4_b512")
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("three_shards", False)])
def test_rehearsal_of_the_cell_on_four_host_devices(tmp_path, fault, correct):
    p = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "tests", "x4_fault_run.py"),
         "--fault", fault, "--rehearse-refs", str(1 << 17),
         "--root", _rehearsal_root(tmp_path), "--workload", "x4_b512",
         "--seed", str(2 ** 31 + 30), "--seconds", "1", "--trace", "1"],
        env=dict(os.environ,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache")),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["count"] == 4
    metrics = line["metrics"]
    assert metrics["sharded_fused_pct"]["value"] == 100.0
    assert metrics["tile_fill_pct"]["value"] == 100.0
    assert "cert_fallback_pct" in metrics and "parse_encode_ms" in metrics
    for name in ("shard_search_device_ms", "knn_x4_roofline",
                 "merge_device_ms", "shard_skew_pct", "device_idle_pct"):
        assert name not in metrics          # no device plane off a TPU
    if not correct:
        c = line["compared"]["dist_gap"]
        assert c["value"] > c["limit"]
