#!/usr/bin/env python
"""chip_smoke.py — the CSV -> job -> model -> serving path on a TPU, proved.

    python chip_smoke.py             one chip: every phase below
    python chip_smoke.py --chips 4   four chips: the sharded fused pipeline
                                     against the unsharded one and the
                                     row-sharded kNN index, nothing else

Drives the system the way a user does — ``python -m avenir_tpu <Job>``,
``python -m avenir_tpu.pipeline run`` and ``python -m avenir_tpu.serving`` as
separate programs over CSV files — at a size users call real (4 Mi
hospital-readmission rows streamed in 1 Mi-row chunks, a 128 Ki-row kNN
reference set), and CHECKS what comes out: Naive-Bayes counts against an
independent numpy count of the same file (exact integers), MI statistics
against numpy from exact pair counts, kNN neighbours against a numpy brute
force, ``/score`` answers against the batch predictors' part files.

One process per chip: this parent imports no JAX (and nothing of
``avenir_tpu``); every phase is a child process, strictly one after another.
The children's environment pins ``JAX_PLATFORMS=tpu``, so where there is no
chip JAX itself fails and the script exits non-zero — nothing here lets a
CPU run end in ``ok``.  Any failed phase raises; no phase is wrapped in a
``try`` that lets the run end in 0.  Times printed are set-up facts of a
smoke, not performance numbers.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

import argparse
import concurrent.futures
import functools
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".scratch", "chip_smoke")     # git-ignored
LOG = os.path.join(ROOT, "chiprun_out", "chip_smoke.log")  # brought back

SEED = 23
CHUNK_ROWS = 1 << 20
HOSP_ROWS = 4 * CHUNK_ROWS          # 4 whole chunks: no ragged-tail program
HOLDOUT_ROWS = 20_000
TREE_ROWS = 1 << 18
KNN_REFS = 1 << 17                  # pallas_knn.fused_serves: the pool fills
KNN_QUERIES = 4096
KNN_K = 10
SERVE_BUCKETS = "1,16"
DEADLINE_S = 1150.0

_T0 = time.monotonic()


def say(msg):
    line = f"[{time.monotonic() - _T0:7.1f}s] {msg}"
    print(line, flush=True)
    with open(LOG, "a") as fh:
        fh.write(line + "\n")


def child_env():
    """The environment every child runs in: the platform pinned to the TPU
    (JAX then FAILS where there is no chip instead of carrying on on the
    CPU) and the checkout importable."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def run_child(name, argv, timeout=600.0):
    """One phase = one child process, waited for before the next starts.
    Returns (stdout, wall seconds); a non-zero exit raises."""
    left = DEADLINE_S - (time.monotonic() - _T0)
    if left <= 5:
        raise RuntimeError(f"no time left to start phase {name!r}")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable] + argv, cwd=WORK, env=child_env(),
                          capture_output=True, text=True,
                          timeout=min(timeout, left))
    wall = time.monotonic() - t0
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-4000:] + "\n" + proc.stderr[-8000:])
        raise RuntimeError(f"phase {name!r} exited {proc.returncode} "
                           f"after {wall:.1f}s")
    # JAX only WARNS when it cannot read or write a cache entry, and the
    # job then silently compiles every time: say so
    for line in proc.stderr.splitlines():
        if "persistent compilation cache" in line:
            say(f"{name}: {line.strip()[:300]}")
    return proc.stdout, wall


def run_job(name, job, props, inp, out, timeout=600.0):
    """``python -m avenir_tpu <Job> -Dk=v ... <in> <out>`` -> (counters as
    the CLI printed them {group: {name: int}}, wall seconds)."""
    shutil.rmtree(os.path.join(WORK, out), ignore_errors=True)
    argv = ["-m", "avenir_tpu", job] + [f"-D{k}={v}" for k, v in
                                        props.items()] + [inp, out]
    stdout, wall = run_child(name, argv, timeout)
    return parse_counters(stdout), wall


def parse_counters(stdout, indent=""):
    counters, group = {}, None
    for line in stdout.splitlines():
        if line.startswith("\t") and group is not None and "=" in line:
            k, v = line.strip().rsplit("=", 1)
            counters[group][k] = int(v)
        elif line.startswith(indent) and line.strip() and \
                not line.startswith("\t"):
            group = line.strip()
            counters.setdefault(group, {})
    return counters


def part(out):
    with open(os.path.join(WORK, out, "part-00000")) as fh:
        return fh.read().splitlines()


def cache_entries(cache_dir):
    """Names of the persistent compile cache's executables (jit_<fn>-<key>),
    one per compiled program kept."""
    if not os.path.isdir(cache_dir):
        return set()
    return {n for n in os.listdir(cache_dir) if n.endswith("-cache")}


def journal_events(tel_dir):
    events = []
    for path in sorted(glob.glob(os.path.join(WORK, tel_dir, "*.jsonl"))):
        with open(path) as fh:
            events += [json.loads(ln) for ln in fh if ln.strip()]
    return events


# ---------------------------------------------------------------------------
# phase 0: the device, the native ingest library, block_until_ready
# ---------------------------------------------------------------------------

PROBE = r"""
import json, os, time
import jax, jax.numpy as jnp, numpy as np
devs = jax.devices()
from avenir_tpu.runtime import native
from avenir_tpu.utils import compile_cache
from avenir_tpu.utils.roofline import chip_peaks
# does an enqueue return before the device is done, and does
# jax.block_until_ready wait for it?  Two programs, 6 and 24 chained
# 4096^3 bf16 matmuls (a few ms and four times that): where the wait
# blocks, ITS time grows with the program and the host fetch after it
# does not.
a = jnp.ones((4096, 4096), jnp.bfloat16)
def chain(n):
    @jax.jit
    def prog(x):
        for _ in range(n):
            x = jnp.dot(x, a, preferred_element_type=jnp.float32
                        ).astype(jnp.bfloat16) * jnp.bfloat16(1e-3)
        return x
    return prog
med = lambda v: float(np.median(v)) * 1e3
timing = {}
for n in (6, 24):
    prog = chain(n)
    np.asarray(prog(a)[0, 0])                    # compile + settle
    enq, blk, fetch = [], [], []
    for _ in range(20):
        t0 = time.perf_counter(); y = prog(a); t1 = time.perf_counter()
        jax.block_until_ready(y); t2 = time.perf_counter()
        np.asarray(y[0, 0]); t3 = time.perf_counter()
        enq.append(t1 - t0); blk.append(t2 - t1); fetch.append(t3 - t2)
    timing[n] = {"enqueue_ms": med(enq), "block_until_ready_ms": med(blk),
                 "fetch_after_block_ms": med(fetch)}
print(json.dumps({
    "platform": devs[0].platform, "kind": devs[0].device_kind,
    "count": len(devs), "peaks": chip_peaks(),
    "native": native.is_available(), "native_error": native.build_error(),
    "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or compile_cache.CACHE_DIR,
    "cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    "timing": timing}))
"""


def phase_probe(chips):
    if not os.path.isdir(os.path.join(ROOT, "avenir_tpu")):
        raise SystemExit("chip_smoke.py: no avenir_tpu/ beside this script "
                         "— it proves the checkout it stands in")
    try:
        stdout, wall = run_child("probe", ["-c", PROBE], timeout=300)
    except RuntimeError as e:
        raise SystemExit(
            f"chip_smoke.py: JAX found no TPU in the child ({e}); this "
            f"script runs on the chip only (README \"Benchmarks\")")
    dev = json.loads(stdout.strip().splitlines()[-1])
    say(f"probe {wall:.1f}s: {dev['platform']} {dev['kind']!r} "
        f"x{dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke.py: platform {dev['platform']!r}, "
                         f"not a TPU")
    if dev["count"] != chips:
        raise RuntimeError(f"expected {chips} chip(s), JAX reports "
                           f"{dev['count']}")
    if not dev["native"]:
        raise RuntimeError("native ingest library unavailable: "
                           f"{dev['native_error']}")
    short, long_ = dev["timing"]["6"], dev["timing"]["24"]
    blocks = (long_["block_until_ready_ms"]
              > 2.5 * short["block_until_ready_ms"]
              and long_["fetch_after_block_ms"]
              < 0.5 * long_["block_until_ready_ms"])
    for n, t in (("6", short), ("24", long_)):
        say(f"block_until_ready, {n} chained 4096^3 bf16 matmuls (medians "
            f"of 20): enqueue {t['enqueue_ms']:.3f} ms, block_until_ready "
            f"{t['block_until_ready_ms']:.3f} ms, host fetch after it "
            f"{t['fetch_after_block_ms']:.3f} ms")
    say(f"block_until_ready {'BLOCKS' if blocks else 'DOES NOT BLOCK'} on "
        f"this platform (its wait grows with the program, the fetch after "
        f"it does not)" if blocks else
        "block_until_ready DOES NOT BLOCK on this platform")
    say(f"compile cache: {dev['cache_dir']} "
        f"({'JAX_COMPILATION_CACHE_DIR' if dev['cache_from_env'] else 'in-checkout default'}), "
        f"{len(cache_entries(dev['cache_dir']))} entries at start")
    return dev


# ---------------------------------------------------------------------------
# phase 1: data, made from the seed by the repo's own generators
# ---------------------------------------------------------------------------

DATAGEN = r"""
import json, sys
from multiprocessing import get_context
from avenir_tpu.core.csv_io import write_csv
from avenir_tpu.datagen import elearn, hosp_readmit, retarget

GEN = {"hosp": (hosp_readmit.generate_hosp_readmit,
                hosp_readmit.HOSP_SCHEMA_JSON),
       "retarget": (retarget.generate_retarget,
                    retarget.RETARGET_SCHEMA_JSON),
       "elearn": (elearn.generate_elearn, elearn.ELEARN_SCHEMA_JSON)}
BLOCK = 1 << 18

def block(job):
    import io
    kind, start, n, seed, idfmt = job
    rows = GEN[kind][0](n, seed=seed)
    if idfmt:                      # unique, ordered record ids
        rows[:, 0] = [idfmt % i for i in range(start, start + n)]
    buf = io.StringIO()
    write_csv(buf, rows)
    return buf.getvalue()

if __name__ == "__main__":
    kind, seed, idfmt = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    files = [(p, int(n)) for p, n in
             (a.split(":") for a in sys.argv[4:])]
    with open(kind + ".json", "w") as fh:
        json.dump(GEN[kind][1], fh)
    total = sum(n for _, n in files)
    jobs = [(kind, s, min(BLOCK, total - s), seed + 1 + s // BLOCK, idfmt)
            for s in range(0, total, BLOCK)]
    with get_context("spawn").Pool(min(8, len(jobs))) as pool:
        text = "".join(pool.imap(block, jobs))
    lines = text.splitlines(keepends=True)
    at = 0
    for path, n in files:
        with open(path, "w") as fh:
            fh.writelines(lines[at:at + n])
        at += n
"""


def datagen(kind, seed, idfmt, files):
    """files: [(path, rows)] cut in order from one generated stream."""
    script = os.path.join(WORK, "_datagen.py")
    with open(script, "w") as fh:
        fh.write(DATAGEN)
    _out, wall = run_child(
        f"datagen.{kind}",
        [script, kind, str(seed), idfmt] + [f"{p}:{n}" for p, n in files])
    sizes = ", ".join(
        f"{p} {n} rows {os.path.getsize(os.path.join(WORK, p)) >> 20} MiB"
        for p, n in files)
    say(f"datagen {kind} {wall:.1f}s: {sizes}")


# ---------------------------------------------------------------------------
# the independent numpy reference for the hospital file
# ---------------------------------------------------------------------------

def hosp_reference():
    """(feature ordinals, per-feature bin labels, codes [N, F], labels [N],
    class values) parsed by numpy straight from hosp.csv + hosp.json — no
    code of the repo."""
    with open(os.path.join(WORK, "hosp.json")) as fh:
        fields = json.load(fh)["fields"]
    feats = [f for f in fields if f.get("feature")]
    cls = next(f for f in fields if not f.get("feature") and not f.get("id"))
    cols = [f["ordinal"] for f in feats] + [cls["ordinal"]]
    raw = np.loadtxt(os.path.join(WORK, "hosp.csv"), dtype="S16",
                     delimiter=",", usecols=cols)
    if raw.shape[0] != HOSP_ROWS:
        raise RuntimeError(f"hosp.csv holds {raw.shape[0]} rows")
    codes = np.empty((raw.shape[0], len(feats)), np.int64)
    bins = []
    for j, f in enumerate(feats):
        if f["dataType"] == "categorical":
            vocab = [v.encode() for v in f["cardinality"]]
            col = np.full(raw.shape[0], -1, np.int64)
            for i, v in enumerate(vocab):
                col[raw[:, j] == v] = i
            bins.append(list(f["cardinality"]))
        else:
            col = raw[:, j].astype(np.int64) // int(f["bucketWidth"])
            bins.append(None)              # the bin label is the number
        if (col < 0).any():
            raise RuntimeError(f"unparsed value in column {f['name']}")
        codes[:, j] = col
    class_values = list(cls["cardinality"])
    labels = np.full(raw.shape[0], -1, np.int64)
    for i, v in enumerate(class_values):
        labels[raw[:, -1] == v.encode()] = i
    if (labels < 0).any():
        raise RuntimeError("unparsed class value")
    return feats, bins, codes, labels, class_values


def check_nb_counts(model_lines, ref, what):
    """Every count row of the model file equals the numpy count of the
    file, and every non-zero numpy cell is in the model file."""
    feats, bins, codes, labels, class_values = ref
    c = len(class_values)
    want = {}
    for j, f in enumerate(feats):
        width = int(codes[:, j].max()) + 1
        table = np.bincount(codes[:, j] * c + labels,
                            minlength=width * c).reshape(width, c)
        for b in range(width):
            name = bins[j][b] if bins[j] else str(b)
            for ci, cv in enumerate(class_values):
                want[(cv, str(f["ordinal"]), name)] = int(table[b, ci])
            want[("", str(f["ordinal"]), name)] = int(table[b].sum())
    for ci, cv in enumerate(class_values):
        want[(cv, "", "")] = int((labels == ci).sum())
    got = {}
    for line in model_lines:
        cv, ordinal, name, count = line.split(",")
        got[(cv, ordinal, name)] = int(count)
    wrong = [(k, got.get(k, 0), v) for k, v in want.items()
             if got.get(k, 0) != v]
    extra = [k for k, v in got.items() if k not in want and v]
    if wrong or extra:
        raise RuntimeError(f"{what}: counts differ from numpy: "
                           f"{wrong[:5]} extra {extra[:5]}")
    say(f"{what}: {len(got)} model rows, every count equals the numpy "
        f"count of hosp.csv exactly ({len(labels)} rows)")


def _mi(table):
    """Mutual information of a 2-D count table's axes, float64, nats."""
    n = table.sum()
    p = table / n
    outer = p.sum(1, keepdims=True) * p.sum(0, keepdims=True)
    nz = p > 0
    return float((p[nz] * np.log(p[nz] / outer[nz])).sum())


def check_mi_stats(mi_lines, ref, what, tol=5e-5):
    """The job emits statistics, not counts: each must equal the float64
    numpy statistic of EXACT numpy pair counts of the file."""
    feats, _bins, codes, labels, class_values = ref
    c = len(class_values)
    names = [f["name"] for f in feats]
    width = [int(codes[:, j].max()) + 1 for j in range(len(feats))]
    got = {}
    for line in mi_lines:
        tag, *rest = line.split(",")
        if tag in ("featureClassMI", "featurePairMI", "featurePairClassMI",
                   "featurePairClassCondMI"):
            got[(tag,) + tuple(rest[:-1])] = float(rest[-1])
    worst, n_checked = 0.0, 0
    for j, name in enumerate(names):
        t = np.bincount(codes[:, j] * c + labels,
                        minlength=width[j] * c).reshape(width[j], c)
        worst = max(worst, abs(got[("featureClassMI", name)] - _mi(t)))
        n_checked += 1
    for i in range(len(names)):
        for j in range(i + 1, len(names)):
            joint = (codes[:, i] * width[j] + codes[:, j]) * c + labels
            t = np.bincount(joint, minlength=width[i] * width[j] * c
                            ).reshape(width[i], width[j], c).astype(float)
            pair = _mi(t.sum(-1))
            pair_class = _mi(t.reshape(-1, c))
            cond = sum(t[:, :, k].sum() / t.sum() * _mi(t[:, :, k])
                       for k in range(c) if t[:, :, k].sum())
            for tag, v in (("featurePairMI", pair),
                           ("featurePairClassMI", pair_class),
                           ("featurePairClassCondMI", cond)):
                worst = max(worst, abs(got[(tag, names[i], names[j])] - v))
                n_checked += 1
    if worst > tol or n_checked != len(got):
        raise RuntimeError(f"{what}: MI statistics differ from numpy "
                           f"(max abs diff {worst:.2e} over {n_checked} "
                           f"of {len(got)})")
    say(f"{what}: {n_checked} MI statistics equal float64 numpy from exact "
        f"pair counts of hosp.csv (max abs diff {worst:.1e}, 6 decimals "
        f"printed)")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def hosp_stream_props():
    return {"feature.schema.file.path": "hosp.json",
            "stream.chunk.rows": CHUNK_ROWS}


def require_all_rows(counters, what):
    rows = counters["Records"]["Processed"]
    if rows != HOSP_ROWS:
        raise RuntimeError(f"{what}: processed {rows} rows")


def phase_hosp_jobs(dev, ref):
    cache = dev["cache_dir"]
    before = cache_entries(cache)
    nb_counters, nb_wall = run_job(
        "nb", "BayesianDistribution", hosp_stream_props(), "hosp.csv",
        "nb_model")
    require_all_rows(nb_counters, "BayesianDistribution")
    after_nb = cache_entries(cache)
    say(f"BayesianDistribution {nb_wall:.1f}s: {HOSP_ROWS} rows in "
        f"{HOSP_ROWS // CHUNK_ROWS} chunks, native ingest; count path "
        f"einsum nfb,nc->fbc (the standalone NaiveBayes.fit has no other "
        f"route; its kernel route is the fused scan below); cache "
        f"+{len(after_nb - before)} entries")
    check_nb_counts(part("nb_model"), ref, "BayesianDistribution")

    mi_counters, cold = run_job(
        "mi.cold", "MutualInformation", hosp_stream_props(), "hosp.csv",
        "mi_cold")
    require_all_rows(mi_counters, "MutualInformation")
    after_cold = cache_entries(cache)
    paths = {k: v for k, v in mi_counters["Records"].items()
             if k.startswith("CountPath.")}
    was_cold = bool(after_cold - after_nb)
    say(f"MutualInformation {cold:.1f}s ("
        f"{'cold: no cached program' if was_cold else 'the cache this machine came with already held its programs'}"
        f"): count path {paths}; cache +{len(after_cold - after_nb)} "
        f"entries {sorted(n.split('-')[0] for n in after_cold - after_nb)}")
    if paths != {"CountPath.kernel": HOSP_ROWS // CHUNK_ROWS}:
        raise RuntimeError(f"MutualInformation took {paths} on the chip, "
                           f"not the MXU kernel for every chunk")
    if not any(n.startswith("jit_cooc_counts") for n in after_cold):
        raise RuntimeError(f"no jit_cooc_counts entry in the compile cache "
                           f"{cache} after the job that compiled it")
    check_mi_stats(part("mi_cold"), ref, "MutualInformation")

    _c, warm = run_job(
        "mi.warm", "MutualInformation", hosp_stream_props(), "hosp.csv",
        "mi_warm")
    after_warm = cache_entries(cache)
    say(f"MutualInformation again (same shapes, new process) {warm:.1f}s "
        f"vs {cold:.1f}s before: cache +{len(after_warm - after_cold)} "
        f"entries")
    if after_warm != after_cold:
        raise RuntimeError(f"second process of the same shapes added cache "
                           f"entries {sorted(after_warm - after_cold)}")
    if part("mi_warm") != part("mi_cold"):
        raise RuntimeError("warm MI output differs from cold")


PIPELINE_CONF = """\
feature.schema.file.path=hosp.json
stream.chunk.rows={chunk}
trace.on=true
trace.journal.dir={tel}
pipeline.workspace={ws}
pipeline.bind.data=hosp.csv
pipeline.stages=nb,mi,cramer
pipeline.stage.nb.job=BayesianDistribution
pipeline.stage.nb.input=data
pipeline.stage.nb.output=nb_out
pipeline.stage.mi.job=MutualInformation
pipeline.stage.mi.input=data
pipeline.stage.mi.output=mi_out
pipeline.stage.cramer.job=CramerCorrelation
pipeline.stage.cramer.input=data
pipeline.stage.cramer.output=cramer_out
{extra}"""


def run_pipeline(name, extra, timeout=600.0):
    """The fused NB + MI + Cramer pipeline through its CLI ->
    (per-stage counters, scan-span path tags, journal events, wall)."""
    ws, tel = f"ws_{name}", f"tel_{name}"
    for d in (ws, tel):
        shutil.rmtree(os.path.join(WORK, d), ignore_errors=True)
    conf = f"pipeline_{name}.properties"
    with open(os.path.join(WORK, conf), "w") as fh:
        fh.write(PIPELINE_CONF.format(
            chunk=CHUNK_ROWS, tel=tel, ws=ws,
            extra="".join(f"{k}={v}\n" for k, v in extra.items())))
    stdout, wall = run_child(
        f"pipeline.{name}", ["-m", "avenir_tpu.pipeline", "run", conf],
        timeout)
    stages = {}
    for block in stdout.split("stage ")[1:]:
        stage, _, body = block.partition("\n")
        stages[stage.strip()] = parse_counters(body, indent="  ")
    events = journal_events(tel)
    tags = [e["attrs"]["path"] for e in events
            if e.get("ev") == "span.open" and e.get("name") == "scan"]
    return stages, tags, events, wall


def phase_fused_pipeline(dev, ref):
    before = cache_entries(dev["cache_dir"])
    stages, tags, _events, wall = run_pipeline("fused", {})
    fused = stages["nb"].get("SharedScan", {})
    say(f"fused pipeline NB+MI+Cramer {wall:.1f}s: SharedScan {fused}, "
        f"count path {tags}; cache +"
        f"{sorted(n.split('-')[0] for n in cache_entries(dev['cache_dir']) - before)}")
    if tags != ["kernel"] or fused.get("FusedStages") != 3:
        raise RuntimeError(f"fused pipeline took {tags} / {fused}, not one "
                           f"kernel-path scan of 3 stages")
    check_nb_counts(part("ws_fused/nb_out"), ref,
                    "fused BayesianDistribution (MXU gram)")
    if part("ws_fused/nb_out") != part("nb_model"):
        raise RuntimeError("fused NB model differs from the standalone")
    if part("ws_fused/mi_out") != part("mi_cold"):
        raise RuntimeError("fused MI output differs from the standalone")
    say("fused NB model and MI output byte-identical to the standalone "
        "jobs' part files")


def phase_predictor():
    counters, wall = run_job(
        "predict", "BayesianPredictor",
        {"feature.schema.file.path": "hosp.json",
         "bayesian.model.file.path": "nb_model",
         "prediction.mode": "validation", "positive.class.value": "Y"},
        "hosp_holdout.csv", "nb_pred")
    rows = part("nb_pred")
    with open(os.path.join(WORK, "hosp_holdout.csv")) as fh:
        inputs = fh.read().splitlines()
    if len(rows) != HOLDOUT_ROWS or any(
            not r.startswith(i + ",") for r, i in zip(rows, inputs)):
        raise RuntimeError("BayesianPredictor rows are not <input>,<class>")
    # the planted structure: readmission probability is 20-71 %, so a
    # correct model beats neither-class guessing on the held-out slice
    truth = np.array([i.rsplit(",", 1)[1] for i in inputs])
    pred = np.array([r.rsplit(",", 1)[1] for r in rows])
    acc = float((truth == pred).mean())
    base = max(float((truth == v).mean()) for v in ("N", "Y"))
    val = counters["Validation"]
    say(f"BayesianPredictor {wall:.1f}s: {len(rows)} held-out rows, "
        f"accuracy {acc:.4f} (majority class {base:.4f}), "
        f"Validation {val}")
    if val["correct"] != int((truth == pred).sum()) or \
            val["correct"] + val["incorrect"] != HOLDOUT_ROWS:
        raise RuntimeError("Validation counters disagree with the rows")
    if acc < base - 0.02:
        raise RuntimeError("held-out accuracy far below the majority class")


def phase_tree():
    counters, wall = run_job(
        "tree", "DecisionTreeBuilder",
        {"feature.schema.file.path": "retarget.json",
         "tree.hist.phase.stats": "true"},
        "retarget.csv", "tree_model")
    paths = sorted(k for k in counters["TreePhase"] if ".path." in k)
    model = json.loads(part("tree_model")[0])
    say(f"DecisionTreeBuilder {wall:.1f}s: {TREE_ROWS} rows, "
        f"{counters['Tree']['Nodes']} nodes, level paths {paths}")
    if not paths or any(not p.endswith(".path.cross") for p in paths):
        raise RuntimeError(f"tree levels took {paths}, not the cross-gram "
                           f"kernel")
    # the planted structure: campaignType (ordinal 1) decides, amount is
    # noise — the root must split on it
    if model["nodes"][0]["split"]["attr"] != 0:
        raise RuntimeError(f"tree root splits on "
                           f"{model['nodes'][0]['split']}, not campaignType")
    say("tree root splits on campaignType, as the generator plants it")


@functools.lru_cache(maxsize=None)
def elearn_reference():
    """Plain numpy brute force for EVERY query: the KNN_K + 6 nearest
    references (min-max normalised Euclidean, as the schema has only
    numeric features), rows ordered by (distance, reference row)."""
    def load(path):
        return np.loadtxt(os.path.join(WORK, path), dtype="S16",
                          delimiter=",")
    refs, queries = load("elearn_refs.csv"), load("elearn_queries.csv")
    x = refs[:, 1:10].astype(np.float64)
    q = queries[:, 1:10].astype(np.float64)
    lo, hi = x.min(0), x.max(0)
    span = np.maximum(hi - lo, 1e-9)
    x01 = np.clip((x - lo) / span, 0, 1)
    q01 = np.clip((q - lo) / span, 0, 1)
    xn = (x01 ** 2).sum(1)
    keep = KNN_K + 6
    top = np.empty((len(q01), keep), np.int64)
    for s in range(0, len(q01), 256):
        qq = q01[s:s + 256]
        d2 = (qq ** 2).sum(1)[:, None] + xn[None, :] - 2.0 * qq @ x01.T
        cand = np.sort(np.argpartition(d2, keep + 8, axis=1)[:, :keep + 8],
                       axis=1)
        order = np.argsort(np.take_along_axis(d2, cand, axis=1), axis=1,
                           kind="stable")[:, :keep]
        top[s:s + 256] = np.take_along_axis(cand, order, axis=1)

    def dist(rows):                       # [Q, n] ref rows -> distances
        d2 = ((q01[:, None, :] - x01[rows]) ** 2).sum(-1)
        return np.sqrt(d2 / x.shape[1])
    return {"ref_ids": [r.decode() for r in refs[:, 0]],
            "query_ids": [r.decode() for r in queries[:, 0]],
            "ref_class": np.array([r.decode() for r in refs[:, 10]]),
            "top": top, "top_dist": dist(top), "dist": dist}


KNN_PROPS = {"feature.schema.file.path": "elearn.json",
             "training.data.path": "elearn_refs.csv",
             "top.match.count": KNN_K}


def phase_knn(dev):
    cache = dev["cache_dir"]
    before = cache_entries(cache)
    counters, wall = run_job("knn", "NearestNeighbor", KNN_PROPS,
                             "elearn_queries.csv", "knn_pred")
    new = sorted(n.split("-")[0] for n in cache_entries(cache) - before)
    fused = counters["Records"].get("Search.fused", 0)
    tourney = counters["Records"].get("Search.tournament", 0)
    fallback = counters["Records"].get("Search.certFallback", 0)
    say(f"NearestNeighbor {wall:.1f}s: {KNN_REFS} refs x {KNN_QUERIES} "
        f"queries, k={KNN_K}; {fused} rows answered by the fused Pallas "
        f"search ({tourney} with the tournament candidate kernel), "
        f"{fallback} of them failed its exactness certificate and were "
        f"recomputed by the exact XLA scan; cache +{new}")
    if fused != KNN_QUERIES or tourney != KNN_QUERIES or \
            fallback > KNN_QUERIES // 2:
        raise RuntimeError(f"kNN: fused search answered {fused} of "
                           f"{KNN_QUERIES} rows ({tourney} through the "
                           f"tournament) and certified only "
                           f"{fused - fallback}")
    check_knn_votes()


def check_knn_votes():
    """NearestNeighbor's part file against the brute force: the majority
    class of the KNN_K nearest (first class of the schema on a tied vote),
    for every query whose K-th and (K+1)-th distances differ."""
    t0 = time.monotonic()
    ref = elearn_reference()
    with open(os.path.join(WORK, "elearn.json")) as fh:
        classes = [f for f in json.load(fh)["fields"]
                   if "cardinality" in f][-1]["cardinality"]
    votes = np.stack([(ref["ref_class"][ref["top"][:, :KNN_K]] == c).sum(1)
                      for c in classes], axis=1)
    want = np.array(classes)[np.argmax(votes, axis=1)]
    decided = ref["top_dist"][:, KNN_K - 1] < ref["top_dist"][:, KNN_K] - 1e-9
    rows = part("knn_pred")
    if len(rows) != KNN_QUERIES:
        raise RuntimeError("NearestNeighbor row count")
    got = np.array([r.rsplit(",", 1)[1] for r in rows])
    ids = [r.split(",", 1)[0] for r in rows]
    if ids != ref["query_ids"]:
        raise RuntimeError("NearestNeighbor part file: query ids/order")
    bad = np.flatnonzero(decided & (got != want))
    if len(bad):
        raise RuntimeError(
            f"NearestNeighbor: {len(bad)} of {KNN_QUERIES} predictions "
            f"differ from the brute-force vote, first {ids[bad[0]]}: "
            f"got {got[bad[0]]}, votes {dict(zip(classes, votes[bad[0]]))}")
    say(f"NearestNeighbor part file: all {int(decided.sum())} predictions "
        f"whose {KNN_K}-th neighbour is not tied equal the numpy brute-"
        f"force vote over {KNN_REFS} refs ({KNN_QUERIES - int(decided.sum())}"
        f" tied rows not compared; reference {time.monotonic() - t0:.1f}s)")


def phase_knn_neighbours():
    """EVERY query's neighbour set against the brute force: a row the
    fused search certified exact and got wrong fails the run here."""
    _c, wall = run_job("knn.pairs", "SameTypeSimilarity",
                       dict(KNN_PROPS, **{"distance.scale": 1000000}),
                       "elearn_queries.csv", "knn_pairs")
    ref = elearn_reference()
    row_of = {r: i for i, r in enumerate(ref["ref_ids"])}
    at = {q: i for i, q in enumerate(ref["query_ids"])}
    got = np.full((KNN_QUERIES, KNN_K), -1, np.int64)
    scaled = np.zeros((KNN_QUERIES, KNN_K))
    fill = np.zeros(KNN_QUERIES, np.int64)
    for line in part("knn_pairs"):
        qid, rid, sc = line.split(",")
        qi = at[qid]
        got[qi, fill[qi]], scaled[qi, fill[qi]] = row_of[rid], int(sc)
        fill[qi] += 1
    if (fill != KNN_K).any():
        raise RuntimeError(f"SameTypeSimilarity: {int((fill != KNN_K).sum())}"
                           f" queries without exactly {KNN_K} neighbours")
    mine = ref["dist"](got)
    want = np.sort(ref["top_dist"][:, :KNN_K], axis=1)
    wrong = np.flatnonzero(
        np.abs(np.sort(mine, axis=1) - want).max(1) > 1e-6)
    if len(wrong):
        qi = wrong[0]
        raise RuntimeError(
            f"{len(wrong)} of {KNN_QUERIES} queries: neighbours are not the "
            f"brute-force nearest; first {ref['query_ids'][qi]}: got "
            f"{sorted(zip(mine[qi], got[qi]))} want "
            f"{list(zip(want[qi], ref['top'][qi, :KNN_K]))}")
    if (np.abs(scaled - mine * 1e6) > 2).any():
        raise RuntimeError("SameTypeSimilarity: reported distances differ")
    same_ids = int((np.sort(got, axis=1)
                    == np.sort(ref["top"][:, :KNN_K], axis=1)).all(1).sum())
    say(f"SameTypeSimilarity {wall:.1f}s: all {KNN_QUERIES} queries, every "
        f"neighbour set equals the numpy brute force over {KNN_REFS} refs "
        f"by distance ({same_ids} also id for id; the rest differ only "
        f"among exact ties)")


def phase_elearn_nb():
    """The reference's own knn.sh shape: Naive Bayes over the SAME schema
    as the kNN reference set — one serving conf holds one schema, so this
    is the NB model the server loads beside kNN."""
    props = {"feature.schema.file.path": "elearn.json"}
    _c, w1 = run_job("nb.elearn", "BayesianDistribution", props,
                     "elearn_refs.csv", "elearn_nb_model")
    _c, w2 = run_job("predict.elearn", "BayesianPredictor",
                     dict(props, **{"bayesian.model.file.path":
                                    "elearn_nb_model"}),
                     "elearn_queries.csv", "elearn_nb_pred")
    say(f"elearn BayesianDistribution {w1:.1f}s + BayesianPredictor "
        f"{w2:.1f}s (Gaussian NB over the kNN reference schema)")


def http(port, path, body=None, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def phase_serving(dev):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with open(os.path.join(WORK, "serve.properties"), "w") as fh:
        fh.write(f"serve.models=naiveBayes,knn\n"
                 f"serve.http.port={port}\n"
                 f"serve.bucket.sizes={SERVE_BUCKETS}\n"
                 f"feature.schema.file.path=elearn.json\n"
                 f"bayesian.model.file.path=elearn_nb_model\n"
                 f"training.data.path=elearn_refs.csv\n"
                 f"top.match.count={KNN_K}\n")
    with open(os.path.join(WORK, "elearn_queries.csv")) as fh:
        queries = fh.read().splitlines()
    expect = {"naiveBayes": part("elearn_nb_pred"),
              "knn": part("knn_pred")}
    before = cache_entries(dev["cache_dir"])
    log = open(os.path.join(WORK, "serve.log"), "w")
    t0 = time.monotonic()
    server = subprocess.Popen(
        [sys.executable, "-m", "avenir_tpu.serving", "--conf",
         "serve.properties"], cwd=WORK, env=child_env(), stdout=log,
        stderr=subprocess.STDOUT, start_new_session=True)
    try:
        health = None
        while time.monotonic() - t0 < 420:
            if server.poll() is not None:
                raise RuntimeError(f"server exited {server.returncode} "
                                   f"during start-up")
            try:
                health = http(port, "/healthz", timeout=5)
                if health.get("ready"):
                    break
            except OSError:
                pass
            time.sleep(0.5)
        if not health or not health.get("ready"):
            raise RuntimeError(f"server not ready: {health}")
        ready_s = time.monotonic() - t0

        def caller(i):
            """3 requests per family from this caller, 1-4 rows each."""
            bad = []
            for model in ("naiveBayes", "knn"):
                for r in range(3):
                    at = (i * 37 + r * 11) % (KNN_QUERIES - 4)
                    rows = queries[at:at + 1 + (i + r) % 4]
                    got = http(port, "/score",
                               {"model": model, "rows": rows})["results"]
                    if got != expect[model][at:at + len(rows)]:
                        bad.append((model, at, got))
            return bad

        with concurrent.futures.ThreadPoolExecutor(6) as pool:
            bad = [b for res in pool.map(caller, range(6)) for b in res]
        if bad:
            raise RuntimeError(f"/score answers differ from the batch "
                               f"part files: {bad[:3]}")
        stats = http(port, "/stats")
        # a Serving.<model> counter exists once incremented: absent = 0
        recompiles = {m: stats[m].get("recompiles", 0) for m in expect}
        if any(recompiles.values()) or \
                any(stats[m]["requests"] < 18 for m in expect):
            raise RuntimeError(f"recompiles after warm-up: {recompiles} "
                               f"in {stats}")
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=60)
    finally:
        if server.poll() is None:
            os.killpg(server.pid, signal.SIGKILL)
            server.wait()
        log.close()
    with open(os.path.join(WORK, "serve.log")) as fh:
        tail = fh.read().strip().splitlines()
    final = json.loads(tail[-1])          # the shutdown stats line
    if rc != 0:
        raise RuntimeError(f"server exited {rc} on SIGTERM")
    new = len(cache_entries(dev["cache_dir"]) - before)
    say(f"serving: ready after {ready_s:.1f}s (models "
        f"{health.get('models')}, buckets {SERVE_BUCKETS}, cache +{new} "
        f"entries), 36 concurrent /score requests over 6 callers all equal "
        f"to the BayesianPredictor / NearestNeighbor part-file rows; "
        f"recompiles {recompiles}; SIGTERM -> exit 0, final stats line "
        f"requests { {m: final[m]['requests'] for m in expect} }")


# ---------------------------------------------------------------------------
# four chips: the sharded fused pipeline against the unsharded one
# ---------------------------------------------------------------------------

SHARD_STEP = r"""
import json
from avenir_tpu.core.config import JobConfig
from avenir_tpu.jobs.base import Job
from avenir_tpu.parallel.shard import ShardSpec
from avenir_tpu.pipeline import scan
conf = JobConfig({"feature.schema.file.path": "hosp.json",
                  "shard.devices": "4"})
spec = ShardSpec.from_conf(conf)
_enc, ds, _rows = Job.encode_input(conf, "hosp_holdout.csv", need_rows=False)
ds = ds.slice(0, 16384)
folder = scan.ChunkFolder([scan.NaiveBayesConsumer(name="nb"),
                           scan.MutualInfoConsumer(name="mi")], ds,
                          shard=spec)
text = folder._shard_step.lower(
    *spec.shard_batch(ds.codes, ds.labels, ds.cont)).compile().as_text()
print(json.dumps({"path": folder.step, "mesh": dict(spec.mesh.shape),
                  "tpu_custom_call": text.count("tpu_custom_call"),
                  "all_reduce": text.count("all-reduce")}))
"""

# the kNN index row-sharded over the four chips: the fused certified search on
# every shard + one all-gather merge, against a numpy brute force in float64
SHARD_KNN = r"""
import json, time
import numpy as np
import jax.numpy as jnp
from avenir_tpu.core.encoding import EncodedDataset
from avenir_tpu.models import knn as mknn
from avenir_tpu.ops import pallas_knn
from avenir_tpu.parallel import collectives
from avenir_tpu.parallel.mesh import make_mesh

REFS, QUERIES, K, ATTRS = %(refs)d, %(queries)d, %(k)d, 9
rng = np.random.default_rng(%(seed)d)


def signals(n):
    return EncodedDataset(
        codes=np.zeros((n, 0), np.int32),
        cont=rng.integers(0, 400, size=(n, ATTRS)).astype(np.float32),
        labels=rng.integers(0, 2, size=n).astype(np.int32), ids=None,
        n_bins=np.zeros(0, np.int32), class_values=["P", "F"],
        binned_ordinals=[], cont_ordinals=list(range(1, ATTRS + 1)))


refs, queries = signals(REFS), signals(QUERIES)
mesh = make_mesh(("data",))
route = mknn.sharded_route(mesh, "euclidean", K, REFS)
model = mknn.fit_knn(refs)
t0 = time.monotonic()
dist, idx = mknn.nearest_neighbors(model, queries, K, mesh=mesh)
wall = time.monotonic() - t0
q01 = mknn._normalize01(queries.cont, model.cont_lo, model.cont_hi)
r = model.cont01().astype(np.float64)
rr = (r * r).sum(1)
checked = np.arange(0, QUERIES, 8)       # the brute force is the slow part
q = q01[checked].astype(np.float64)
d2 = np.maximum((q * q).sum(1)[:, None] + rr[None, :] - 2.0 * q @ r.T, 0)
want = np.sqrt(np.sort(np.partition(d2, K, axis=1)[:, :K], axis=1) / ATTRS)
gap = float(np.abs(dist[checked] - want).max())
r_mat, codes_s, cont01_s, shard = model.sharded_index(mesh)
step = collectives.sharded_knn_fused(
    mesh, shard, num_bins=1, total_attrs=ATTRS,
    **pallas_knn.fused_statics(QUERIES, 0, ATTRS, K))
text = step.lower(jnp.asarray(queries.codes), jnp.asarray(q01), r_mat,
                  codes_s, cont01_s, jnp.int32(REFS)).compile().as_text()
print(json.dumps({
    "route": route, "mesh": dict(mesh.shape), "shard_rows": shard,
    "wall_s": wall, "gap": gap, "checked": len(checked), "in_range": bool(
        ((idx >= 0) & (idx < REFS)).all()),
    "fused": model.fused_rows, "tournament": model.tourney_rows,
    "shard_fused": model.shard_fused_rows,
    "refused": model.cert_fallback_rows,
    "tpu_custom_call": text.count("tpu_custom_call"),
    "all_gather": text.count("all-gather")}))
"""


def phase_four_chips(dev, ref):
    # unsharded = ONE chip of the four takes the kernel path (the auto
    # data-parallel mesh is off, or it would shard this run too)
    _s, tags1, _e, wall1 = run_pipeline(
        "one", {"data.parallel.auto": "false"})
    say(f"unsharded pipeline {wall1:.1f}s: count path {tags1}")
    if tags1 != ["kernel"]:
        raise RuntimeError(f"unsharded run took {tags1}")
    stages, tags4, events, wall4 = run_pipeline(
        "four", {"shard.devices": "4", "profile.on": "true"})
    shard = stages["nb"].get("Shard", {})
    say(f"shard.devices=4 pipeline {wall4:.1f}s: count path {tags4}, "
        f"Shard counters {shard}")
    if tags4 != ["shard"]:
        raise RuntimeError(f"sharded run took {tags4}, not the fused "
                           f"shard_map dispatch")
    for out in ("nb_out", "mi_out", "cramer_out"):
        a, b = (open(os.path.join(WORK, ws, out, "part-00000"), "rb").read()
                for ws in ("ws_one", "ws_four"))
        if a != b:
            raise RuntimeError(f"{out}: sharded part file differs from "
                               f"the unsharded one")
    say("nb_out, mi_out, cramer_out part files byte-identical sharded vs "
        "unsharded")
    check_nb_counts(part("ws_four/nb_out"), ref,
                    "sharded BayesianDistribution")
    topo = [e for e in events if e.get("ev") == "shard.topology"]
    if len(topo) != 1 or topo[0]["devices"] != 4 or \
            topo[0]["device_kind"] != dev["kind"]:
        raise RuntimeError(f"shard.topology events: {topo}")
    say(f"shard.topology: {topo[0]['devices']} x {topo[0]['device_kind']} "
        f"mesh {topo[0]['mesh']}")
    # interpret mode gives the same bytes, so only the compiled text can
    # tell: the step pipeline/scan.py builds for THIS mesh must hold the
    # Mosaic kernel and the all-reduce
    out, wall = run_child("shard.step", ["-c", SHARD_STEP])
    step = json.loads(out.strip().splitlines()[-1])
    say(f"sharded step as ChunkFolder builds it for {step['mesh']} "
        f"({wall:.1f}s): compiled text holds {step['tpu_custom_call']} "
        f"tpu_custom_call and {step['all_reduce']} all-reduce")
    if step["path"] != "shard" or step["tpu_custom_call"] < 1 or \
            step["all_reduce"] < 1:
        raise RuntimeError(f"sharded step is not the compiled kernel + "
                           f"collective: {step}")
    if shard.get("chunks") != HOSP_ROWS // CHUNK_ROWS or \
            not shard.get("collective.bytes", 0) > 0:
        raise RuntimeError(f"Shard counters {shard}")
    skew = [e for e in events if e.get("ev") == "shard.skew"]
    per_device = {}
    for e in skew:
        for d, ms in enumerate(e["device_ms"]):
            per_device.setdefault(d, []).append(ms)
    if sorted(per_device) != [0, 1, 2, 3] or \
            any(min(v) <= 0 for v in per_device.values()):
        raise RuntimeError(f"skew probe did not time 4 devices: "
                           f"{skew[:2]}")
    say("per-device probe walls (ms, median over "
        f"{len(skew)} probed chunks): " + ", ".join(
            f"dev{d} {float(np.median(v)):.2f}"
            for d, v in sorted(per_device.items()))
        + " -> every device held and worked its shard")


def phase_four_chips_knn():
    """The sharded fused kNN search over 4 x KNN_REFS references: every
    query's distances against the brute force, the route counted, and the
    merged program's compiled text holding the kernel AND the collective."""
    out, wall = run_child("shard.knn", ["-c", SHARD_KNN % {
        "refs": 4 * KNN_REFS, "queries": KNN_QUERIES, "k": KNN_K,
        "seed": SEED + 300}])
    got = json.loads(out.strip().splitlines()[-1])
    say(f"sharded kNN ({wall:.1f}s): {4 * KNN_REFS} refs over mesh "
        f"{got['mesh']} ({got['shard_rows']} rows a shard) x {KNN_QUERIES} "
        f"queries, k={KNN_K}: route {got['route']}, {got['shard_fused']} "
        f"rows through the sharded fused search ({got['tournament']} with "
        f"the tournament kernel), {got['refused']} refused by a shard and "
        f"rescanned; widest distance gap to the float64 brute force over "
        f"{got['checked']} of the queries {got['gap']:.2e}; first search {got['wall_s']:.1f}s (placement "
        f"and compile included); compiled text holds "
        f"{got['tpu_custom_call']} tpu_custom_call and "
        f"{got['all_gather']} all-gather")
    if got["route"] != "sharded_fused" or not got["in_range"] or \
            got["fused"] != KNN_QUERIES or \
            got["shard_fused"] != KNN_QUERIES or \
            got["tournament"] != KNN_QUERIES or \
            got["refused"] > KNN_QUERIES // 2:
        raise RuntimeError(f"sharded kNN did not take the sharded fused "
                           f"route for every row: {got}")
    if got["gap"] > 5e-6:     # the exact scan of refused rows reads ~2e-6
        raise RuntimeError(f"sharded kNN distances differ from the brute "
                           f"force by {got['gap']}")
    if got["tpu_custom_call"] < 1 or got["all_gather"] < 1:
        raise RuntimeError(f"merged program is not the compiled kernel + "
                           f"all-gather: {got}")


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(os.path.dirname(LOG), exist_ok=True)
    open(LOG, "w").close()
    dev = phase_probe(chips)
    datagen("hosp", SEED, "P%010d", [("hosp.csv", HOSP_ROWS),
                                     ("hosp_holdout.csv", HOLDOUT_ROWS)])
    t0 = time.monotonic()
    ref = hosp_reference()
    say(f"numpy parse of hosp.csv {time.monotonic() - t0:.1f}s")
    if chips == 4:
        phase_four_chips(dev, ref)
        phase_four_chips_knn()
    else:
        datagen("retarget", SEED + 100, "", [("retarget.csv", TREE_ROWS)])
        datagen("elearn", SEED + 200, "U%08d",
                [("elearn_refs.csv", KNN_REFS),
                 ("elearn_queries.csv", KNN_QUERIES)])
        phase_hosp_jobs(dev, ref)
        phase_fused_pipeline(dev, ref)
        phase_predictor()
        phase_tree()
        phase_knn(dev)
        phase_knn_neighbours()
        phase_elearn_nb()
        phase_serving(dev)
    say(f"compile cache at end: "
        f"{len(cache_entries(dev['cache_dir']))} entries; total "
        f"{time.monotonic() - _T0:.1f}s")
    shutil.rmtree(WORK)                  # 0.3 GiB of CSV: not kept
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
