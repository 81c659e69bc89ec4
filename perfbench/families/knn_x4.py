"""The kNN deployment at a history one chip cannot hold: the reference rows in
``shards`` contiguous row shards over one host's chips, one process, queries
replicated (``configs/elearn_knn_x4.json``).

Everything but the mesh is ``families/knn.py``'s: the same set-up from the
seed, the same entries, wrappers, counters and comparison.  The estimator gets
a ``("data",)`` mesh of the first ``shards`` devices, as ``KNNServable.from_conf``
gets one from ``Job.auto_mesh``, and the program does the rest.

**The route check.**  The cell measures the certified fused search on every
shard and one all-gather merge — not whatever a sharded index happens to be
answered by.  So before a single reference row is made, ``System`` asks the
program's own routing predicate (``avenir_tpu.models.knn.sharded_route``)
which search this configuration takes on this mesh, and exits with status 3
and one line on standard error where the program has no such predicate or it
answers anything but ``sharded_fused``: a commit whose sharded index takes the
XLA scan does not run this cell at all (it would run it 19 times slower,
PERF.md, PR 29's refusal), it fails in seconds.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, Optional

import jax

from avenir_tpu.models import knn as mknn
from avenir_tpu.parallel.mesh import make_mesh

from families import knn
from families.knn import SPAN_NAMES  # noqa: F401 — run.py reads it

ROUTE = "sharded_fused"
NO_ROUTE_STATUS = 3


def check_route(mesh, settings: Dict, refs: int) -> None:
    """Exit with status 3 unless the program routes this configuration to the
    sharded fused search."""
    predicate = getattr(mknn, "sharded_route", None)
    if predicate is None:
        took = "the program has no avenir_tpu.models.knn.sharded_route"
    else:
        route = predicate(mesh, settings.get("distance.metric", "euclidean"),
                          int(settings["top.match.count"]), refs)
        if route == ROUTE:
            return
        took = f"sharded_route answers {route!r}"
    print(f"perfbench knn_x4: {refs} references over a data mesh of "
          f"{mesh.shape['data']} have to take the {ROUTE} route; {took}",
          file=sys.stderr)
    raise SystemExit(NO_ROUTE_STATUS)


class System(knn.System):
    def __init__(self, config: Dict, seed: int, refs: Optional[int] = None):
        n = int(refs if refs is not None else config["refs"])
        mesh = make_mesh(("data",),
                         devices=jax.devices()[:int(config["shards"])])
        check_route(mesh, config["settings"], n)
        super().__init__(config, seed, refs)
        self.est.mesh = mesh

    def _warm_fallback(self) -> None:
        """The exact scan over the SHARDED index that refused rows fall back
        to, through the program's documented switch for it, at the row counts
        a window meets (the base class warms the one-device scan)."""
        at = time.perf_counter()
        mknn.USE_PALLAS = False
        try:
            for rows in knn.FALLBACK_ROWS:
                mknn.nearest_neighbors(self.model, self._ds.slice(0, rows),
                                       self.est.k, self.est.metric,
                                       mesh=self.est.mesh)
        finally:
            mknn.USE_PALLAS = True
        knn._phase("exact-scan fallback shapes over the sharded index", at)

    def _counters(self) -> Dict[str, float]:
        out = super()._counters()
        out["shard_fused_rows"] = float(self.model.shard_fused_rows)
        return out
