#!/usr/bin/env python
"""Decompose the kNN tournament kernel's per-call cost on real shapes.

Variants of `ops/pallas_knn._knn_tourney_kernel` at the production shapes
(4096 queries × 1M refs, bf16 packed width): ``dotonly`` (MXU pass +
trivial output), ``dotkey`` (adds bitcast key formation, no tournament),
``full`` (the shipped kernel itself, `pallas_knn._tourney_keys`).  One
variant per process run, chained dispatches, host-fetch sync — quantifies
how much of the ~22 ms call the tournament extraction actually costs TODAY
(the docs/architecture.md ceiling note cites this probe).

  python -m benchmarks.knn_decomp_probe --variant full

Round-4 result: INCONCLUSIVE on the dev rig — pass spread 29–110 ms on
identical calls (dotonly even measured slower than dotkey, which is
physically impossible), i.e. the rig's ±20%+ drift exceeds any
extraction-pass delta this probe could resolve.  The probe is kept as
the measurement method for a quieter rig; the shipped kernel's floor
analysis stands on the round-3 bisection (docs/architecture.md
"ceilings").
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


from avenir_tpu.ops import pallas_knn as pk


def _kernel(a_ref, b_ref, k1_out, k2_out, k3_out, *, variant):
    """The shipped kernel's dot (``dotonly``) and key formation (``dotkey``)
    with a trivial reduction in the tournament's place, on the shipped
    kernel's own block shapes."""
    d2v = jax.lax.dot_general(
        a_ref[:], b_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if variant == "dotonly":
        r = jnp.min(d2v, axis=1, keepdims=True).astype(jnp.int32)
    else:
        lane = jax.lax.broadcasted_iota(jnp.int32, (pk.TM, pk.TB), 1)
        col = lane & jnp.int32(pk.SEG - 1)
        di = jax.lax.bitcast_convert_type(jnp.maximum(d2v, 0.0), jnp.int32)
        r = jnp.min((di & jnp.int32(~(pk.SEG - 1))) | col, axis=1,
                    keepdims=True)
    for out in (k1_out, k2_out, k3_out):
        out[:] = jnp.broadcast_to(r, out.shape)


@functools.partial(jax.jit, static_argnames=("variant",))
def run(a_mat, b_mat, variant):
    if variant == "full":
        return pk._tourney_keys(a_mat, b_mat)
    m, n = a_mat.shape[0], b_mat.shape[0]
    spec = pl.BlockSpec((pk.TM, 128), lambda i, j: (i, j // pk._COL_STEPS),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, variant=variant),
        grid=(m // pk.TM, n // pk.TB),
        in_specs=[
            pl.BlockSpec((pk.TM, a_mat.shape[1]), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((pk.TB, b_mat.shape[1]), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct(
            (m, pk._round_up(n // pk.SEG, 128)), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(a_mat, b_mat)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=["dotonly", "dotkey", "full"],
                    required=True)
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--n", type=int, default=1_048_576)
    ap.add_argument("--width", type=int, default=128)
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.random((args.m, args.width), np.float32)
                    .astype(np.float16)).astype(jnp.bfloat16)
    b = jnp.asarray(rng.random((args.n, args.width), np.float32)
                    .astype(np.float16)).astype(jnp.bfloat16)
    o = run(a, b, args.variant)
    np.asarray(o[0][0, 0])
    vals = []
    for _ in range(4):
        t0 = time.perf_counter()
        bias = jnp.bfloat16(0)
        for _ in range(4):
            o = run(a + bias, b, args.variant)
            bias = (o[0][0, 0] * 0).astype(jnp.bfloat16)
        np.asarray(o[0][0, 0])
        vals.append((time.perf_counter() - t0) / 4 * 1e3)
    print(json.dumps({"variant": args.variant,
                      "ms_per_call_median": round(float(np.median(vals)), 2),
                      "passes_ms": [round(v, 2) for v in vals]}))


if __name__ == "__main__":
    main()
