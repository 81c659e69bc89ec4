"""The ``knn_backfill`` family and its cell: the configuration is
``elearn_knn_classcond``'s but for the second class; the schedule is a
function of the seed and the traffic file; a program without the bulk entry
does not run the cell (status 3 before a reference row is made); and a
rehearsal of the whole cell on the CPU — sound, with blocks dropped, and with
blocks held back past the limit."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import PERFBENCH, ROOT

CELL = "classcond_serve_c128_backfill"
SIBLING = "classcond_serve_c128"
NEW_METRICS = {"backfill_rows_per_dispatch", "backfill_only_dispatch_pct",
               "backfill_block_ms"}
NOT_JOINED = {"launch_readback_ms", "host_ms_per_call", "queue_wait_ms"}


# -- the benchmark's entries --------------------------------------------------------

def test_the_configuration_is_the_siblings_but_for_the_second_class():
    loaded, sib = run.load_cell(CELL), run.load_cell(SIBLING)
    config, twin = loaded["config"], sib["config"]
    assert loaded["cell"]["chips"] == 1
    assert config["family"] == "knn_backfill"
    for key in ("refs", "schema", "settings", "reduced"):
        assert config[key] == twin[key], key
    for key, limit in twin["limits"].items():
        assert config["limits"][key] == limit, key
    assert {k: v for k, v in config["limits"].items()
            if k not in twin["limits"]} == {"backfill_behind_blocks": 2,
                                            "backfill_lost_rows": 0}
    for key, text in twin["guarantees"].items():
        assert config["guarantees"][key] == text, key   # none weakened
    assert config["assumed"]["refs"] == twin["assumed"]["refs"]
    entry = next(c for c in loaded["bench"]["configs"]
                 if c["name"] == "elearn_knn_serve_backfill")
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == []


def test_the_traffic_is_the_siblings_plus_the_schedule():
    traffic, twin = run.load_cell(CELL)["traffic"], \
        run.load_cell(SIBLING)["traffic"]
    for key in ("loop", "clients", "rows_per_request", "entry", "pool_rows",
                "check_rows", "batcher"):
        assert traffic[key] == twin[key], key
    assert traffic["backfill"] == {
        "class": "backfill", "block_rows": 4096, "period_ms": 250.0,
        "pool_rows": 131072, "check_rows": 256}


def test_the_cells_metrics():
    bench = run.load_cell(CELL)["bench"]
    per_layer = {m["name"] for m in run.metrics_for(bench, "per_layer", CELL)}
    twin = {m["name"] for m in run.metrics_for(bench, "per_layer", SIBLING)}
    assert per_layer == (twin - NOT_JOINED) | NEW_METRICS
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "serving entry"
            assert m["moves"] == "queries_per_s"
    assert {m["name"] for m in run.metrics_for(bench, "end_to_end", CELL)} \
        == {"queries_per_s", "p95_ms", "setup_s"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 4


# -- the schedule ------------------------------------------------------------------------

def test_the_schedule_is_a_function_of_the_seed_and_the_traffic_file():
    from families import knn_backfill as fam
    from lib import data

    spec = {"block_rows": 64, "period_ms": 250.0, "pool_rows": 256}
    pool = fam.backfill_lines(256, 2 ** 31 + 5)
    assert pool == fam.backfill_lines(256, 2 ** 31 + 5)
    assert pool != fam.backfill_lines(256, 2 ** 31 + 6)
    assert not set(pool) & set(data.make_query_lines(256, 2 ** 31 + 5))
    assert all(len(line.split(",")) == 10 for line in pool)
    blocks = [fam.schedule(spec, pool, i) for i in range(9)]
    assert blocks[0] == pool[:64] and blocks[3] == pool[192:]
    assert blocks[4] == blocks[0] and blocks[8] == blocks[0]    # round again
    assert fam.due_blocks(spec, 10.0, 10.0) == 1
    assert fam.due_blocks(spec, 10.0, 10.249) == 1
    assert fam.due_blocks(spec, 10.0, 10.251) == 2
    assert fam.due_blocks(spec, 10.0, 30.01) == 81


# -- the entry check ---------------------------------------------------------------------

def test_program_without_the_bulk_entry_does_not_run_the_cell(
        monkeypatch, capsys):
    from families import knn_backfill as fam
    from lib import data

    def no_rows(*_a, **_k):
        raise AssertionError("reference rows made before the entry check")

    monkeypatch.setattr(data, "make_refs", no_rows)
    config = run.load_cell(CELL)["config"]
    with pytest.raises(AssertionError, match="before the entry check"):
        fam.System(config, 7)             # this program has the entry
    monkeypatch.delattr(fam.BucketedMicrobatcher, "submit_block")
    with pytest.raises(SystemExit) as exit_:
        fam.System(config, 7)
    assert exit_.value.code == 3
    err = capsys.readouterr().err.strip().splitlines()
    assert len(err) == 1 and "submit_block" in err[0]


# -- the whole cell, rehearsed on the CPU -------------------------------------------------

def _rehearsal_root(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    traffic = run.load_cell(CELL)["traffic"]
    traffic.update(clients=16, pool_rows=4096, check_rows=128)
    traffic["backfill"].update(block_rows=1024, period_ms=100.0,
                               pool_rows=8192, check_rows=64)
    json.dump(traffic,
              open(tmp_path / "perfbench/traffic/serve_c16_backfill.json",
                   "w"))
    bench["workloads"].append({
        "name": "backfill_small", "config": "elearn_knn_serve_backfill",
        "traffic": "serve_c16_backfill", "chips": 1, "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("backfill_small")
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    return str(tmp_path)


@pytest.mark.parametrize("fault,broken", [
    ("none", set()),
    # a dropped block is lost, and stays behind for good
    ("drop", {"backfill_lost_rows", "backfill_behind_blocks"}),
    ("delay", {"backfill_behind_blocks"})])
def test_rehearsal_of_the_cell(tmp_path, fault, broken):
    p = subprocess.run(
        [sys.executable,
         os.path.join(PERFBENCH, "tests", "backfill_fault_run.py"),
         "--fault", fault, "--rehearse-refs", str(1 << 13),
         "--root", _rehearsal_root(tmp_path), "--workload", "backfill_small",
         "--seed", str(2 ** 31 + 34), "--seconds", "2", "--trace", "1"],
        env=dict(os.environ,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache")),
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    compared, checked = line["compared"], line["checked"]
    assert line["correct"] is (not broken), compared
    assert line["failed"] == 0 and line["attempted"] >= 16
    for name, c in compared.items():
        assert (c["value"] > c["limit"]) is (name in broken), (name, c)
    assert checked["backfill_checked_rows"] == 64
    assert checked["backfill_due_blocks"] >= 15
    metrics = line["metrics"]
    # every metric of the cell but the four that read the device's planes
    # and the three that read the fused search (here: the XLA scan)
    device = {"search_device_ms", "knn_roofline", "device_idle_pct",
              "idle_unexplained_pct", "cert_fallback_pct", "parse_encode_ms",
              "vote_format_ms"}
    listed = {m["name"] for m in run.metrics_for(
        run.load_cell(CELL)["bench"], "per_layer", CELL)}
    assert set(metrics) == listed - device, sorted(listed - set(metrics))
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["backfill_block_ms"]["value"] > 0
    if fault == "none":
        assert metrics["backfill_rows_per_dispatch"]["value"] > 16
        assert metrics["tile_fill_pct"]["value"] > 12.5
        assert checked["backfill_rows_per_s"] >= 0.8 * 10240
        assert compared["backfill_behind_blocks"]["value"] <= 2
