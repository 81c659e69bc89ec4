"""The control of a cell's ``correct``: the plain reference in a lower
precision, put in the program's place, has to come out as NOT correct.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 [--refs N]

For each seed: the cell's references and as many query rows as a run compares
are made from the seed, the control answers them, and its answers go through
the run's own comparison against the cell's limits.  Needs no part of the
program.  Prints one JSON line per seed; exits 0 when every seed's control
failed at least one limit, 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control_numbers(workload: str, seed: int, refs=None, precision="bf16",
                    root=None):
    import run
    from lib import data, knn_reference

    loaded = run.load_cell(workload, root)
    config, traffic = loaded["config"], loaded["traffic"]
    if config["family"] != "knn":
        raise SystemExit(f"no control for family {config['family']!r}")
    cont, labels = data.make_refs(int(refs or config["refs"]), seed)
    lines = data.make_query_lines(int(traffic["check_rows"]), seed)
    ordinals = [f["ordinal"] for f in config["schema"]["fields"]
                if f.get("feature")]
    klass = next(f for f in config["schema"]["fields"]
                 if f.get("cardinality"))
    numbers = knn_reference.control(
        knn_reference.Reference(cont, labels), config["settings"], lines,
        klass["cardinality"], ordinals, float(config["limits"]["share_gap"]),
        precision=precision)
    limits = config["limits"]
    failed = [k for k, lim in limits.items() if not numbers[k] <= lim]
    return numbers, failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--refs", type=int)
    ap.add_argument("--precision", default="bf16")
    args = ap.parse_args(argv)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers, failed = control_numbers(args.workload, seed, args.refs,
                                          args.precision)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": args.precision, "numbers": numbers,
                          "fails": failed}), flush=True)
        all_failed &= bool(failed)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
