#!/usr/bin/env python
"""Isolate the gram-matmul orientation cost in Pallas on TPU.

G = X·Xᵀ with X [W, N] (contract dim 1 of both operands) requires the MXU's
RHS in [N, W]; if Mosaic materializes per-tile int8 transposes for that, the
gram runs far below the int8 peak.  The alternative orientation streams
A = Xᵀ [N, W] and contracts dim 0 of both (AᵀA), which is the systolic
array's native reduce-over-rows mode.  This probe times both on identical
random int8 data (no expand, no compare — dot + streaming only).

One variant per process:  python benchmarks/dot_orient_probe.py --orient a
"""

import argparse
import functools
import json
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel_a(x_ref, out_ref):          # x block [W, BN]; G += x·xᵀ
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = x_ref[:]
    out_ref[:] += jax.lax.dot_general(x, x, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)


def _kernel_b(x_ref, out_ref):          # x block [BN, W]; G += xᵀ·x
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = x_ref[:]
    out_ref[:] += jax.lax.dot_general(x, x, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bn", "orient"))
def gram(x, bn, orient):
    if orient == "a":
        w, n = x.shape
        return pl.pallas_call(
            _kernel_a, grid=(n // bn,),
            in_specs=[pl.BlockSpec((w, bn), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((w, w), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((w, w), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=110 * 1024 * 1024),
        )(x)
    n, w = x.shape
    return pl.pallas_call(
        _kernel_b, grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, w), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((w, w), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((w, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=110 * 1024 * 1024),
    )(x)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--orient", choices=["a", "b"], default="a")
    ap.add_argument("--bn", type=int, default=98304)
    ap.add_argument("--w", type=int, default=384)
    ap.add_argument("--n", type=int, default=4_194_304)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    shape = (args.w, args.n) if args.orient == "a" else (args.n, args.w)
    x = jnp.asarray(rng.integers(0, 2, size=shape, dtype=np.int8))

    def timed():
        t0 = time.perf_counter()
        g = gram(x, args.bn, args.orient)
        for _ in range(3):                 # chain: result feeds nothing; use
            g = gram(x + (g[0, 0] * 0).astype(jnp.int8), args.bn, args.orient)
        float(g[0, 0])
        return 4 * args.n / (time.perf_counter() - t0)

    timed()
    timed()
    passes = [timed() for _ in range(4)]
    med = float(np.median(passes))
    tops = 2.0 * args.w * args.w * med / 1e12
    print(json.dumps({
        "orient": args.orient, "bn": args.bn, "w": args.w,
        "rows_per_sec": round(med, 1),
        "eff_int8_tops": round(tops, 1),
        "passes": [round(p, 1) for p in passes],
    }))


if __name__ == "__main__":
    main()
