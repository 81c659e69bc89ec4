"""``run.py`` on a cell of the ``knn_backfill`` family with a fault put under
the bulk entry: the run has to read ``correct`` false by the guarantee it
broke.

    python3 perfbench/tests/backfill_fault_run.py --fault drop \
        --workload classcond_serve_c128_backfill --seed 7 --seconds 20 --trace 0

``--fault drop``: every third block is shed at the door (rows lost).
``--fault delay``: no block row is taken until its block has waited
(0.6 s: it falls blocks behind its schedule, but stays under the class's bound
of 16 blocks waiting, so every row is still answered).
``--fault none`` is the sound run.  ``--rehearse-refs N`` is the CPU rehearsal
of ``tests/test_knn_backfill.py`` (a copy of the benchmark with a small
traffic file under ``--root``, ``N`` references, the search on its XLA scan).
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
for _p in (PERFBENCH, os.path.dirname(PERFBENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DELAY_S = 0.6


def drop_every_third_block():
    from avenir_tpu.serving.batcher import BucketedMicrobatcher
    from avenir_tpu.serving.errors import TenantShedError

    sound, seen = BucketedMicrobatcher.submit_block, [0]

    def lossy(self, model, lines, klass=None, rid=None):
        seen[0] += 1
        if seen[0] % 3 == 0:
            raise TenantShedError("dropped by the test", tenant=klass or "",
                                  quota="queue.depth", retry_after_s=1.0)
        return sound(self, model, lines, klass=klass, rid=rid)

    BucketedMicrobatcher.submit_block = lossy


def hold_blocks_back():
    from avenir_tpu.serving.batcher import BucketedMicrobatcher

    sound = BucketedMicrobatcher._take_fill

    def late(self, model, online):
        blocks = self._blocks[model]
        if blocks and not self._stop and \
                time.perf_counter() - blocks[0].queued < DELAY_S:
            return []
        return sound(self, model, online)

    BucketedMicrobatcher._take_fill = late


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=("none", "drop", "delay"),
                    required=True)
    ap.add_argument("--rehearse-refs", type=int)
    ap.add_argument("--root")
    args, rest = ap.parse_known_args(argv)
    rehearse = None
    if args.rehearse_refs:
        os.environ["JAX_PLATFORMS"] = "cpu"
        rehearse = {"refs": args.rehearse_refs, "root": args.root}
    import run

    # after the family's warm block (a held-back warm block would only be
    # slow): the fault goes in when the window opens
    from families import knn_backfill

    start = knn_backfill.System.start_window

    def start_with_fault(self):
        {"none": lambda: None, "drop": drop_every_third_block,
         "delay": hold_blocks_back}[args.fault]()
        start(self)

    knn_backfill.System.start_window = start_with_fault
    result = run.run(rest, rehearse=rehearse)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
