"""Roofline accounting for benchmarks: detected-chip peaks + achieved rates.

Every benchmark JSON line carries achieved FLOP/s (compute-bound kernels)
and/or bytes/s (bandwidth-bound kernels) against the detected chip's peak, so
a throughput number can be judged against the hardware ceiling instead of in
a vacuum (the reference publishes no perf numbers at all).

Peaks are the published per-chip specs keyed by ``device_kind``.  A TPU
whose kind is not in the table is an error, never a default or a probe: a
roofline share against a guessed peak is a wrong number with a real name.
"""

from __future__ import annotations

from typing import Dict, Optional

# Published per-chip peaks: bf16 FLOP/s, int8 OP/s, and HBM bytes/s.
# v5e (reports device_kind "TPU v5 lite"): 197 TFLOP/s bf16 / 394 TOPS
# int8, 819 GB/s HBM (Google Cloud documentation, "TPU v5e").
_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 394e12,
                "hbm_bytes": 819e9},
    "TPU v5p": {"bf16_flops": 459e12, "int8_ops": 918e12,
                "hbm_bytes": 2765e9},
    "TPU v5": {"bf16_flops": 459e12, "int8_ops": 918e12,
               "hbm_bytes": 2765e9},                             # v5p
    "TPU v4": {"bf16_flops": 275e12, "int8_ops": 275e12,
               "hbm_bytes": 1228e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "int8_ops": 1836e12,
                    "hbm_bytes": 1640e9},                        # v6e
    "TPU v6e": {"bf16_flops": 918e12, "int8_ops": 1836e12,
                "hbm_bytes": 1640e9},
}


def require_tpu(what: str) -> None:
    """Measurement entry points call this first: a rate printed from the
    CPU backend or the Pallas interpreter under a device metric's name is
    worse than no number, so where JAX finds no TPU the run fails."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"{what} measures the TPU and JAX found platform "
            f"{platform!r}: run it on the chip (README \"Benchmarks\")")


def chip_peaks() -> Dict[str, float]:
    """{"device_kind", "bf16_flops", "int8_ops", "hbm_bytes"} for the
    attached chip.

    CPU backends (tests) report measured-nothing peaks of 0 → callers skip
    MFU fields rather than print garbage.  A TPU whose ``device_kind`` is
    not in the table raises."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    peaks = _PEAKS.get(kind)
    if peaks is None:
        if dev.platform == "tpu":
            raise ValueError(
                f"unknown TPU device_kind {kind!r}: add its published "
                f"peaks to avenir_tpu/utils/roofline.py::_PEAKS "
                f"(known: {sorted(_PEAKS)})")
        peaks = {"bf16_flops": 0.0, "int8_ops": 0.0, "hbm_bytes": 0.0}
    return {"device_kind": kind, **peaks}


def mfu_fields(flops: Optional[float] = None, dt: Optional[float] = None,
               bytes_moved: Optional[float] = None,
               peaks: Optional[Dict[str, float]] = None,
               int8_ops: Optional[float] = None) -> Dict[str, float]:
    """Fields to merge into a benchmark JSON line: achieved FLOP/s + MFU,
    achieved int8 OP/s + fraction of int8-MXU peak, and/or achieved
    bytes/s + fraction of HBM peak, for work done in ``dt`` seconds."""
    out: Dict[str, float] = {}
    p = peaks or chip_peaks()
    out["device_kind"] = p["device_kind"]
    if flops and dt:
        out["achieved_tflops"] = round(flops / dt / 1e12, 2)
        if p["bf16_flops"]:
            out["mfu_pct"] = round(100.0 * flops / dt / p["bf16_flops"], 2)
    if int8_ops and dt:
        out["achieved_int8_tops"] = round(int8_ops / dt / 1e12, 2)
        if p.get("int8_ops"):
            out["int8_mxu_pct"] = round(
                100.0 * int8_ops / dt / p["int8_ops"], 2)
    if bytes_moved and dt:
        out["achieved_gbps"] = round(bytes_moved / dt / 1e9, 2)
        if p["hbm_bytes"]:
            out["hbm_pct"] = round(
                100.0 * bytes_moved / dt / p["hbm_bytes"], 2)
    return out
