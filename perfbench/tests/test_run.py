"""(d) A rehearsal of the whole run on the CPU at 2^12 references: the last
line has the contract's keys; the command itself refuses to run off a TPU;
a cell made only of new files and a new entry runs; and with the timed path
broken underneath ``correct`` comes out false."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import PERFBENCH, ROOT

REHEARSE = {"refs": 1 << 12}
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _argv(workload, trace=0, seed=2 ** 31 + 19):
    return ["--workload", workload, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]


@pytest.mark.parametrize("workload,metrics", [
    ("knn_bulk_b4096", {"queries_per_s", "setup_s"}),
    ("classcond_serve_c128", {"queries_per_s", "p95_ms", "setup_s"})])
def test_last_line_has_the_contracts_keys(workload, metrics):
    result = run.run(_argv(workload), rehearse=REHEARSE)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


def test_traced_rehearsal_reports_counts_but_no_device_metric():
    """Off a TPU the trace has no device plane: every reader of the device
    trace returns nothing, and the line carries no busy time."""
    line = run.run(_argv("classcond_serve_c128", trace=1), rehearse=REHEARSE)
    assert {"rows_per_dispatch", "queue_wait_ms",
            "window_compiles"} <= set(line["metrics"])
    for name in ("search_device_ms", "knn_roofline", "device_idle_pct",
                 "host_ms_per_call"):
        assert name not in line["metrics"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["metrics"]["window_compiles"]["value"] == 0


def test_command_refuses_to_run_off_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    p = subprocess.run([sys.executable, os.path.join(PERFBENCH, "run.py")]
                       + _argv("knn_bulk_b4096"), env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_a_cell_made_only_of_new_files(tmp_path):
    """A later PR's cell: a traffic file and a configuration file of its own,
    plus entries in BENCHMARK.json; no file that was there is edited."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(PERFBENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    config = json.load(open(tmp_path / "perfbench/configs/elearn_knn.json"))
    config["settings"]["top.match.count"] = 5
    json.dump(config, open(tmp_path / "perfbench/configs/elearn_k5.json", "w"))
    json.dump({"loop": "closed", "clients": 3, "rows_per_request": 256,
               "entry": "score_lines", "pool_rows": 4096, "check_rows": 200},
              open(tmp_path / "perfbench/traffic/bulk_b256_c3.json", "w"))
    bench["configs"].append({"name": "elearn_k5", "source": "test",
                             "file": "perfbench/configs/elearn_k5.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "k5_bulk", "config": "elearn_k5",
                               "traffic": "bulk_b256_c3", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] == "window_compiles":
            m["workloads"].append("k5_bulk")
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    line = run.run(_argv("k5_bulk", trace=1),
                   rehearse=dict(REHEARSE, root=str(tmp_path)))
    assert line["correct"] is True and line["attempted"] >= 3
    assert "window_compiles" in line["metrics"]
    assert line["checked"]["rows"] == 200


# -- the timed path broken underneath: ``correct`` has to come out false -----

def _flip_last_reply(monkeypatch):
    from avenir_tpu.serving.registry import KNNServable

    sound = KNNServable.score_lines

    def altered(self, lines, pad_to):
        out = sound(self, lines, pad_to)
        head, cls = out[-1].rsplit(",", 1)
        out[-1] = f"{head},{'F' if cls == 'P' else 'P'}"
        return out

    monkeypatch.setattr(KNNServable, "score_lines", altered)


def _half_the_index(monkeypatch):
    """The search leaves out every second reference row."""
    import numpy as np

    from avenir_tpu.models import knn as mknn

    sound = mknn.nearest_neighbors

    def halved(model, test, k, *a, **kw):
        half = mknn.KNNModel(
            codes=model.codes[::2], cont=model.cont[::2],
            labels=model.labels[::2], values=None, class_probs=None,
            n_bins=model.n_bins, class_values=model.class_values,
            cont_lo=model.cont_lo, cont_hi=model.cont_hi)
        d, i = sound(half, test, k, *a, **kw)
        return d, (i * 2).astype(np.int32)

    monkeypatch.setattr(mknn, "nearest_neighbors", halved)


def _bf16_search(monkeypatch):
    """The program's search on operands rounded to bfloat16: the step a later
    PR would be tempted by."""
    import ml_dtypes
    import numpy as np

    from avenir_tpu.models import knn as mknn

    def low(x):
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)

    sound = mknn._normalize_cont
    monkeypatch.setattr(mknn, "_normalize_cont",
                        lambda c, lo, hi: low_dev(sound(c, lo, hi)))

    def low_dev(x):
        import jax
        return jax.lax.reduce_precision(x, 8, 7)

    mknn._topk_over_tiles.clear_cache()


@pytest.mark.parametrize("workload", ["knn_bulk_b4096", "classcond_serve_c128"])
@pytest.mark.parametrize("fault,number", [
    (_flip_last_reply, "class_mismatch"),
    (_half_the_index, "dist_gap"),
    (_bf16_search, "dist_gap")])
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault,
                                          number):
    fault(monkeypatch)
    try:
        line = run.run(_argv(workload), rehearse=REHEARSE)
    finally:
        from avenir_tpu.models import knn as mknn
        monkeypatch.undo()
        mknn._topk_over_tiles.clear_cache()
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"], line["compared"]
