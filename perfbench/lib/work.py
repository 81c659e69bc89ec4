"""Operations and bytes a kernel's ALGORITHM needs, from its shapes alone.

These are the yardstick's counts: the same whatever kernel, limb count,
operand packing or padding implements the search, so a roofline share read
against them cannot be raised by doing more work per answer.
"""

from __future__ import annotations

from typing import Dict, Tuple


def knn_search_work(attrs: int, queries: int, refs: int, k: int
                    ) -> Tuple[float, float]:
    """(operations, bytes) of one exact brute-force k-nearest search.

    Per (query, reference, attribute): one subtract, one multiply, one add
    = 3 operations.  Bytes: every reference and query coordinate read once
    as f32 (4·A·(N + M)), and k (distance, index) pairs written per query
    (8·M·k).  ``queries`` counts REAL rows, not rows padded to a tile.
    """
    ops = 3.0 * attrs * queries * refs
    nbytes = 4.0 * attrs * (refs + queries) + 8.0 * queries * k
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: Dict[str, float]
                 ) -> Tuple[float, str]:
    """(seconds, bounding term) — the least time the chip could take: the
    larger of operations over the bf16 peak and bytes over HBM bandwidth."""
    t_ops = ops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
