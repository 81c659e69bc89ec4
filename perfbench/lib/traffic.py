"""The one traffic generator: a closed loop of callers, driven by a traffic
file's parameters (``clients``, ``rows_per_request``, ``pool_rows``).

Each client thread sends its next request only when the reply to the last has
come.  Requests are cut from a pool of query lines made from the seed; client
``c`` walks its own slice of the pool, round and round, so every seed gives
the same number of clients and the same request sizes.  The window is closed
to new requests at ``seconds``; requests in flight then are awaited and
counted, and the window ends with the last reply.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Sequence


def run_closed_loop(entry: Callable[[Sequence[str]], List[str]],
                    pool: Sequence[str], traffic: Dict, seconds: float
                    ) -> Dict:
    clients = int(traffic["clients"])
    rows = int(traffic["rows_per_request"])
    share = len(pool) // clients
    if share < rows:
        raise ValueError(f"pool of {len(pool)} lines is too small for "
                         f"{clients} clients x {rows} rows")
    per_client: List[List[Dict]] = [[] for _ in range(clients)]
    go = threading.Event()
    deadline = float("inf")          # set when the window opens

    def client(c: int) -> None:
        mine = pool[c * share:(c + 1) * share]
        out, at = per_client[c], 0
        go.wait()
        while True:
            t0 = time.perf_counter()
            if t0 >= deadline:
                return
            if at + rows > len(mine):
                at = 0
            lines = mine[at:at + rows]
            at += rows
            try:
                replies, error = entry(lines), None
            except Exception as exc:  # noqa: BLE001 — a failed request is
                # counted, typed and reported; the loop goes on
                replies, error = None, f"{type(exc).__name__}: {exc}"
            out.append({"t0": t0, "t1": time.perf_counter(), "lines": lines,
                        "replies": replies, "error": error})

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"client-{c}") for c in range(clients)]
    for t in threads:
        t.start()
    start = time.perf_counter()
    deadline = start + seconds
    go.set()
    for t in threads:
        t.join()
    requests = sorted((r for rs in per_client for r in rs),
                      key=lambda r: r["t1"])
    end = max((r["t1"] for r in requests), default=start)
    return {"start": start, "end": end, "requests": requests}


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by the nearest-rank rule."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    rank = max(int(-(-q * len(ordered) // 100)), 1)
    return ordered[rank - 1]
