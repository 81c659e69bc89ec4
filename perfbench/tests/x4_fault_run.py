"""``run.py`` on a cell of the ``knn_x4`` family with a fault put under the
timed path: the run has to read ``correct`` false.

    python3 perfbench/tests/x4_fault_run.py --fault three_shards \
        --workload knn_shard4_b4096 --seed 7 --seconds 20 --trace 0

``--fault three_shards``: the merge drops the last shard's candidates, so every
row is answered from three shards of four (the exact scan of refused rows
still reads all four).  ``--fault none`` is the sound run.  On the chip the
builder runs it as above; ``--rehearse-refs N`` is the CPU rehearsal of
``tests/test_knn_x4.py`` (a copy of the benchmark with a 512-row traffic file
under ``--root``, ``N`` references, four forced host devices, the kernels in
interpret mode, the routing gate answering as on a TPU).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
for _p in (PERFBENCH, os.path.dirname(PERFBENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def drop_last_shard():
    import jax
    import jax.numpy as jnp

    from avenir_tpu.parallel import collectives

    sound = collectives.merge_shard_topk

    def three_of_four(d, i, cert, k, data_axis="data"):
        last = jax.lax.axis_index(data_axis) == jax.lax.axis_size(data_axis) - 1
        return sound(d, jnp.where(last, -1, i), cert, k, data_axis)

    collectives.merge_shard_topk = three_of_four


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=("none", "three_shards"),
                    required=True)
    ap.add_argument("--rehearse-refs", type=int)
    ap.add_argument("--root")
    args, rest = ap.parse_known_args(argv)
    rehearse = None
    if args.rehearse_refs:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        from jax.experimental.pallas import tpu as pltpu

        from avenir_tpu.models import knn as mknn
        from avenir_tpu.ops import pallas_knn

        mknn._pallas_available = lambda metric, k: (
            mknn.USE_PALLAS and metric == "euclidean"
            and k + 1 <= pallas_knn.SLOTS)
        rehearse = {"refs": args.rehearse_refs, "root": args.root}
        # for every thread: the traffic's callers are threads of their own
        pltpu.set_tpu_interpret_mode()
    if args.fault == "three_shards":
        drop_last_shard()
    import run

    result = run.run(rest, rehearse=rehearse)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
