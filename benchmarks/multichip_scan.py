#!/usr/bin/env python
"""ShardGraft multichip benchmark: the mesh-sharded SharedScan fold
measured per device count — per-chip + aggregate rows/sec and scaling
efficiency — with byte-identity to the single-chip fold ASSERTED before
any rate is recorded (the acceptance oracle rides the artifact).

Runs the nb_mi-shaped fold (NaiveBayes + MutualInfo consumers) over a
fixed synthetic chunk stream:

- ``single_chip``: today's unsharded path, the byte-identity oracle and
  the band anchor;
- one section per device count in {1, 2, 4, …, all attached}: the fused
  ``shard_map`` dispatch (per-device Pallas gram + class counts + moments,
  psum'd in-kernel), chunks ballast-padded to their pow-2 shard target and
  placed round-robin over the data axis by the same staging the jobs use;
- ``scaling_efficiency`` = aggregate(d) / (aggregate(1 shard) · d) — the
  near-linear-scaling figure ROADMAP item 1 asks for on 8 real chips;
- a quantized row (``shard.allreduce.quantized``) for the largest device
  count, exactness MEASURED and reported (bit-exact when per-device
  partial cells fit int8 — true for the host-mesh chunk slices, not for
  the TPU-size chunks; max bin-count deviation is published either way).

The harness measures the chips: where JAX finds no TPU it raises (a
host-mesh fold runs the Pallas interpreter and its rate would measure the
interpreter, not the kernel).  A fresh matmul canary rides each section
per the PR-2 convention (a loaded host indicts itself, not the scan).
One JSON object on stdout.

CrossGraft (``--nprocs N``): the REAL multi-process capture — the
harness drives itself through the fleet launcher
(``avenir_tpu.launch.launch_local``): N OS processes ×
``--devices-per-proc`` devices each join one jax-distributed fleet, the
global (proc × data) SharedScan fold runs the hierarchical psum
dispatch, byte-identity to each worker's local unsharded fold is
asserted BEFORE any rate is recorded, and the artifact publishes
aggregate + per-process rates, ``scaling_efficiency`` against the
1-process local-mesh fold at the same per-process width, and the
quantized cross-host hop's measured deviation.  The launcher's workers
inherit the parent's environment, so N workers on ONE host's chips would
each claim every chip: that layout has not run (README "Multi-chip").
"""

import argparse
import json
import os
import sys
import time

import numpy as np

N_FEAT = 8
N_BINS = 8
N_CLASSES = 2
N_CONT = 2


def gen_data(n_rows, seed=29):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, N_BINS, size=(n_rows, N_FEAT)).astype(np.int32)
    # 1/16-grid continuous values: shard-partial f32 sums are exact, so
    # the sharded moments match the single-chip fold byte-for-byte
    cont = (rng.integers(0, 16, size=(n_rows, N_CONT)) / 16.0).astype(
        np.float32)
    labels = rng.integers(0, N_CLASSES, size=n_rows).astype(np.int32)
    return codes, cont, labels


def _multiproc_worker(args):
    """One fleet worker of the ``--nprocs`` capture: join via the env the
    launcher wrote, fold the SAME chunk stream through the global mesh,
    assert byte-identity to the local unsharded oracle, measure, and let
    process 0 write the artifact JSON to ``--out``."""
    from avenir_tpu.launch import join_from_env

    idx = join_from_env()
    import jax

    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.core.encoding import EncodedDataset
    from avenir_tpu.parallel.mesh import make_mesh
    from avenir_tpu.parallel.shard import ShardSpec
    from avenir_tpu.pipeline import scan
    from avenir_tpu.utils.metrics import Counters
    from avenir_tpu.utils.rig_canary import matmul_canary_ms
    from avenir_tpu.utils.roofline import require_tpu

    require_tpu("benchmarks/multichip_scan.py --nprocs")
    nprocs = jax.process_count()
    d_local = len(jax.local_devices())
    chunk, n_chunks, passes = 262_144, 8, 3
    codes, cont, labels = gen_data(chunk * n_chunks)
    ds = EncodedDataset(
        codes=codes, cont=cont, labels=labels,
        n_bins=np.full(N_FEAT, N_BINS, np.int32), class_values=["a", "b"],
        binned_ordinals=list(range(N_FEAT)),
        cont_ordinals=list(range(N_FEAT, N_FEAT + N_CONT)))
    n_rows = ds.num_rows

    def chunks():
        return iter([ds.slice(i, i + chunk) for i in range(0, n_rows, chunk)])

    def engine(shard=None, counters=None):
        eng = scan.SharedScan(shard=shard, counters=counters)
        eng.register(scan.NaiveBayesConsumer(name="nb"))
        eng.register(scan.MutualInfoConsumer(name="mi"))
        return eng

    def timed(shard=None):
        counters = Counters()
        eng = engine(shard, counters)
        eng.run(chunks())                        # warm (compile + upload)
        canary = matmul_canary_ms()
        rates = []
        for _ in range(passes):
            t0 = time.perf_counter()
            eng.run(chunks())
            rates.append(n_rows / (time.perf_counter() - t0))
        return float(np.median(rates)), canary, counters

    base_results = engine().run(chunks())        # local 1-chip oracle

    def identical(got):
        np.testing.assert_array_equal(got["nb"].bin_counts,
                                      base_results["nb"].bin_counts)
        np.testing.assert_array_equal(got["mi"].pair_class_counts,
                                      base_results["mi"].pair_class_counts)
        if got["mi"].to_lines() != base_results["mi"].to_lines():
            raise RuntimeError("global fold diverged from 1-chip oracle")

    # 1-process local-mesh baseline at the same per-process width: the
    # scaling-efficiency denominator (explicit spec — from_conf resolves
    # globally in a multi-process runtime)
    local_spec = ShardSpec(
        mesh=make_mesh(("data",), shape=(d_local,),
                       devices=jax.local_devices()))
    identical(engine(local_spec).run(chunks()))
    local_rate, local_canary, _ = timed(local_spec)

    spec = ShardSpec.from_conf(JobConfig({"shard.devices": "all"}))
    assert spec.is_global and spec.num_procs == nprocs
    identical(engine(spec).run(chunks()))        # oracle gate before rates
    rate, canary, counters = timed(spec)

    qspec = ShardSpec.from_conf(JobConfig({
        "shard.devices": "all", "shard.allreduce.quantized": "true"}))
    q_res = engine(qspec).run(chunks())
    try:
        identical(q_res)
        q_exact, q_dev = True, 0
    except (AssertionError, RuntimeError):
        q_exact = False
        q_dev = int(np.abs(
            np.asarray(q_res["nb"].bin_counts, np.int64)
            - np.asarray(base_results["nb"].bin_counts, np.int64)).max())
    q_rate, q_canary, _ = timed(qspec)

    if idx == 0:
        artifact = {
            "benchmark": "multichip_scan",
            "metric": "nb_mi_global_mesh_scan_throughput",
            "mode": "multiprocess",
            "topology": spec.announce(),
            "rows_total": n_rows,
            "chunk_rows": chunk,
            "passes": passes,
            "local_mesh_1proc": {
                "devices": d_local,
                "rows_per_sec_aggregate": round(local_rate, 1),
                "canary_ms": round(local_canary, 2),
            },
            "global_mesh": {
                "procs": nprocs,
                "devices_total": spec.total_devices,
                "rows_per_sec_aggregate": round(rate, 1),
                "rows_per_sec_per_process": round(rate / nprocs, 1),
                "scaling_efficiency": round(rate / (local_rate * nprocs), 3),
                "collective_bytes_per_chunk": int(
                    (counters.get("Shard", "collective.bytes") or 0)
                    // max(1, counters.get("Shard", "chunks") or 1)),
                "canary_ms": round(canary, 2),
            },
            "quantized_crosshost_hop": {
                "rows_per_sec_aggregate": round(q_rate, 1),
                "byte_identical_at_this_chunk_size": q_exact,
                "max_bin_count_deviation": q_dev,
                "canary_ms": round(q_canary, 2),
            },
            "canary_healthy_threshold_ms": 7.0,
        }
        # --out unset (launched by hand through the launcher CLI rather
        # than the self-launching parent): keep the one-object-on-stdout
        # contract — the launcher echoes rank 0's line
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(artifact, fh)
        else:
            print(json.dumps(artifact), flush=True)
    print(f"proc {idx} multichip multiproc ok", flush=True)


def _launch_multiproc(args):
    """Parent side of ``--nprocs``: respawn this script as a fleet via
    the launcher, then print process 0's artifact JSON on stdout (the
    same one-object-on-stdout contract as the single-process mode)."""
    import tempfile

    from avenir_tpu.launch import LaunchError, launch_local

    out = args.out or os.path.join(tempfile.mkdtemp(prefix="multichip_"),
                                   "multichip_mp.json")
    child = [os.path.abspath(__file__), "--nprocs", str(args.nprocs),
             "--out", out]
    result = launch_local(
        child, args.nprocs, devices_per_proc=args.devices_per_proc,
        join_timeout_s=120, timeout_s=3600, echo=False)
    for w in result.workers:
        sys.stderr.write(f"[p{w.rank}] exit={w.returncode}\n")
    if result.exit_code:
        failed = next(w for w in result.workers if w.returncode)
        sys.stderr.write(failed.output[-3000:] + "\n")
        raise LaunchError(
            f"multichip worker p{failed.rank} exited "
            f"{failed.returncode}")
    with open(out) as fh:
        print(fh.read())


def main():
    # resolve avenir_tpu from the repo root no matter how the script was
    # invoked (direct --nprocs runs need it here, and the launcher's
    # workers inherit it)
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if _root not in sys.path:
        sys.path.insert(0, _root)
    os.environ["PYTHONPATH"] = (
        _root + os.pathsep + os.environ.get("PYTHONPATH", "")).rstrip(
        os.pathsep)
    ap = argparse.ArgumentParser()
    ap.add_argument("--nprocs", type=int, default=0,
                    help="CrossGraft capture: N launcher-driven worker "
                         "processes (0 = single-process sections)")
    ap.add_argument("--devices-per-proc", type=int, default=4)
    ap.add_argument("--out", default=None,
                    help="worker artifact path (parent default: tempfile)")
    args = ap.parse_args()
    if args.nprocs and os.environ.get("AVENIR_PROCESS_ID") is None:
        _launch_multiproc(args)
        return
    if os.environ.get("AVENIR_PROCESS_ID") is not None:
        _multiproc_worker(args)
        return
    _single_process_main()


def _single_process_main():
    import jax

    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.core.encoding import EncodedDataset
    from avenir_tpu.parallel.shard import ShardSpec
    from avenir_tpu.pipeline import scan
    from avenir_tpu.utils.metrics import Counters
    from avenir_tpu.utils.rig_canary import matmul_canary_ms
    from avenir_tpu.utils.roofline import require_tpu

    require_tpu("benchmarks/multichip_scan.py")
    devices = jax.devices()
    chunk, n_chunks, passes = 262_144, 8, 3
    codes, cont, labels = gen_data(chunk * n_chunks)
    ds = EncodedDataset(
        codes=codes, cont=cont, labels=labels,
        n_bins=np.full(N_FEAT, N_BINS, np.int32), class_values=["a", "b"],
        binned_ordinals=list(range(N_FEAT)),
        cont_ordinals=list(range(N_FEAT, N_FEAT + N_CONT)))
    n_rows = ds.num_rows

    def chunks():
        return iter([ds.slice(i, i + chunk) for i in range(0, n_rows, chunk)])

    def engine(shard=None, counters=None):
        eng = scan.SharedScan(shard=shard, counters=counters)
        eng.register(scan.NaiveBayesConsumer(name="nb"))
        eng.register(scan.MutualInfoConsumer(name="mi"))
        return eng

    def identical(got, want):
        np.testing.assert_array_equal(got["nb"].bin_counts,
                                      want["nb"].bin_counts)
        np.testing.assert_array_equal(got["nb"].class_counts,
                                      want["nb"].class_counts)
        np.testing.assert_array_equal(got["mi"].pair_class_counts,
                                      want["mi"].pair_class_counts)
        if got["mi"].to_lines() != want["mi"].to_lines():
            raise RuntimeError("sharded MI lines diverged from single-chip")

    def timed(shard=None):
        """(median aggregate rows/sec, canary ms, Shard counters) — one
        untimed warm pass (compile + upload), then ``passes`` timed folds;
        Accumulator.add fetches to host, so each fold is host-synced."""
        counters = Counters()
        eng = engine(shard, counters)
        eng.run(chunks())
        canary = matmul_canary_ms()
        rates = []
        for _ in range(passes):
            t0 = time.perf_counter()
            eng.run(chunks())
            rates.append(n_rows / (time.perf_counter() - t0))
        return float(np.median(rates)), canary, counters

    base_results = engine().run(chunks())
    base_rate, base_canary, _ = timed()

    counts, d = [], 1
    while d < len(devices):
        counts.append(d)
        d *= 2
    counts.append(len(devices))

    sections = []
    agg1 = None
    for d in counts:
        spec = ShardSpec.from_conf(JobConfig({"shard.devices": str(d)}))
        identical(engine(spec).run(chunks()), base_results)
        rate, canary, counters = timed(spec)
        if d == 1:
            agg1 = rate
        sections.append({
            "devices": d,
            "rows_per_sec_aggregate": round(rate, 1),
            "rows_per_sec_per_chip": round(rate / d, 1),
            "scaling_efficiency": (round(rate / (agg1 * d), 3)
                                   if agg1 else None),
            "collective_bytes_per_chunk": int(
                (counters.get("Shard", "collective.bytes") or 0)
                // max(1, counters.get("Shard", "chunks") or 1)),
            "canary_ms": round(canary, 2),
        })

    # EQuARX-style quantized all-reduce on the widest mesh: exact ONLY
    # while per-device gram partial cells fit int8 (small per-chip chunk
    # slices — the host-mesh shape); at the TPU chunk size the cells
    # overflow that bound, so identity is MEASURED and reported, never
    # asserted — the exact psum path above stays the byte-identity oracle
    qspec = ShardSpec.from_conf(JobConfig({
        "shard.devices": str(len(devices)),
        "shard.allreduce.quantized": "true"}))
    q_res = engine(qspec).run(chunks())
    try:
        identical(q_res, base_results)
        q_exact, q_dev = True, 0
    except (AssertionError, RuntimeError):
        q_exact = False
        q_dev = int(np.abs(
            np.asarray(q_res["nb"].bin_counts, np.int64)
            - np.asarray(base_results["nb"].bin_counts, np.int64)).max())
    q_rate, q_canary, _ = timed(qspec)

    print(json.dumps({
        "benchmark": "multichip_scan",
        "metric": "nb_mi_sharded_scan_throughput",
        "topology": qspec.announce(),
        "rows_total": n_rows,
        "chunk_rows": chunk,
        "passes": passes,
        "single_chip": {
            "rows_per_sec": round(base_rate, 1),
            "canary_ms": round(base_canary, 2),
        },
        "sharded": sections,
        "quantized_allreduce": {
            "devices": len(devices),
            "rows_per_sec_aggregate": round(q_rate, 1),
            "byte_identical_at_this_chunk_size": q_exact,
            "max_bin_count_deviation": q_dev,
            "canary_ms": round(q_canary, 2),
        },
        "canary_healthy_threshold_ms": 7.0,
    }))


if __name__ == "__main__":
    main()
