"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``.  A copy of the v5e row of ``avenir_tpu/utils/roofline.py``:
later PRs may change the program's table, never this yardstick.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peak_for(device_kind: str) -> Dict[str, float]:
    """The row for ``device_kind``; a chip that is not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add a row with its source") from None
