#!/usr/bin/env python
"""Decompose the kNN tournament kernel's per-call cost on real shapes.

Variants of `ops/pallas_knn._knn_tourney_kernel` at the production shapes
(4096 queries × 1M refs, bf16 packed width): ``dotonly`` (MXU pass +
trivial output), ``dotkey`` (adds bitcast key formation, no tournament),
``full`` (the shipped kernel).  One variant per process run, chained
dispatches, host-fetch sync — quantifies how much of the ~22 ms call the
tournament extraction actually costs TODAY (the docs/architecture.md
ceiling note cites this probe).

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


def _kernel(a_ref, b_ref, k1_out, k2_out, k3_out, *, nbp, variant):
    j = pl.program_id(1)
    d2v = jax.lax.dot_general(
        a_ref[:], b_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if variant == "dotonly":
        r = jnp.min(d2v, axis=1, keepdims=True).astype(jnp.int32)
        k1_out[:] = jnp.broadcast_to(r, k1_out.shape)
        k2_out[:] = k1_out[:]
        k3_out[:] = k1_out[:]
        return
    lane = jax.lax.broadcasted_iota(jnp.int32, (pk.TM, pk.TB), 1)
    col = lane & jnp.int32(pk.SEG - 1)
    di = jax.lax.bitcast_convert_type(jnp.maximum(d2v, 0.0), jnp.int32)
    key = (di & jnp.int32(~(pk.SEG - 1))) | col
    if variant == "dotkey":
        r = jnp.min(key, axis=1, keepdims=True)
        k1_out[:] = jnp.broadcast_to(r, k1_out.shape)
        k2_out[:] = k1_out[:]
        k3_out[:] = k1_out[:]
        return
    # full: replicate the shipped tournament
    nseg = pk.TB // pk.SEG
    outlane = jax.lax.broadcasted_iota(jnp.int32, (pk.TM, nbp), 1)
    for s in range(nseg):
        seg = key[:, s * pk.SEG:(s + 1) * pk.SEG]
        w = pk.SEG // 2
        a, b = seg[:, :w], seg[:, w:]
        m1 = jnp.minimum(a, b)
        m2 = jnp.maximum(a, b)
        w //= 2
        a1, b1 = m1[:, :w], m1[:, w:]
        a2, b2 = m2[:, :w], m2[:, w:]
        hi1 = jnp.maximum(a1, b1)
        lo2 = jnp.minimum(a2, b2)
        m1 = jnp.minimum(a1, b1)
        m2 = jnp.minimum(hi1, lo2)
        m3 = jnp.maximum(lo2, hi1)
        while w > 128:
            w //= 2
            a1, b1 = m1[:, :w], m1[:, w:]
            a2, b2 = m2[:, :w], m2[:, w:]
            a3, b3 = m3[:, :w], m3[:, w:]
            hi1 = jnp.maximum(a1, b1)
            lo2 = jnp.minimum(a2, b2)
            hi2 = jnp.maximum(a2, b2)
            m1 = jnp.minimum(a1, b1)
            m2 = jnp.minimum(hi1, lo2)
            m3 = jnp.minimum(jnp.minimum(jnp.maximum(hi1, lo2), hi2),
                             jnp.minimum(a3, b3))
        t1 = jnp.min(m1, axis=1)
        em = jnp.where(m1 == t1[:, None], m2, m1)
        t2 = jnp.min(em, axis=1)
        em2 = jnp.where(em == t2[:, None],
                        jnp.where(m1 == t1[:, None], m3, m2), em)
        t3 = jnp.min(em2, axis=1)
        sel = outlane == (j * nseg + s)
        k1_out[:] = jnp.where(sel, t1[:, None], k1_out[:])
        k2_out[:] = jnp.where(sel, t2[:, None], k2_out[:])
        k3_out[:] = jnp.where(sel, t3[:, None], k3_out[:])


@functools.partial(jax.jit, static_argnames=("variant",))
def run(a_mat, b_mat, variant):
    m, n = a_mat.shape[0], b_mat.shape[0]
    nb = n // pk.TB
    nseg = n // pk.SEG
    nbp = pk._round_up(nseg, 128)
    spec = pl.BlockSpec((pk.TM, nbp), lambda i, j: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, nbp=nbp, variant=variant),
        grid=(m // pk.TM, nb),
        in_specs=[
            pl.BlockSpec((pk.TM, a_mat.shape[1]), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((pk.TB, b_mat.shape[1]), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((m, nbp), jnp.int32)] * 3,
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
