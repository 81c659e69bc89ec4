#!/usr/bin/env python
"""Decompose the kNN tournament kernel's grid step into its two halves.

Variants of `ops/pallas_knn._knn_tourney_kernel` on the benchmark's shapes
(4096 queries x 13 x 2^20 references, packed width 128, random bf16
operands), one harness, chained dispatches, host-fetch sync:

- ``dotonly``  the MXU half: the kernel's per-segment dot with a trivial
  reduction in the tournament's place;
- ``dotkey``   the dot and the key formation, a running min in the
  tournament's place;
- ``tourney``  the vector half: the kernel's key formation and tournament
  over a resident f32 block of distances, no dot.  The block is read in
  64-row pieces folded into a running triple — the same merges in another
  order — because the compiler, handed a whole segment with nothing to
  pace it, hoists the loads and spills (29.8 us a step measured that way,
  three times the operations' own time);
- ``full``     the shipped wrapper itself, `pallas_knn._tourney_keys`: its
  kernel, grid and block shapes, at the tile `pallas_knn.query_tile` gives
  ``--m`` rows.

  python -m benchmarks.knn_decomp_probe --variant dotonly tourney full
  python -m benchmarks.knn_decomp_probe --variant full --tile 128 --m 128

``--tile`` is the height of the half-kernels' query tile; the shipped search
(``full``) takes `pallas_knn.query_tile` of its block — 128, 256 or 512 rows —
and ``--tile`` has to name that.  ``--m 128 --tile 128`` is a serve
dispatch's sweep, 832 steps.
A step is ``ms / (m / tile * n / 16384)``: 6 656 steps at the defaults.
Readings on one TPU v5e (PERF.md section 6 has the table with its origins):
PR 28's kernel 148.5 ms = 22.3 us a step around a bare dot of 75.5 ms =
11.3 us; PR 31's 77.5 ms = 11.65 us a step, ``dotonly`` 74.9 ms = 11.25 us
(97 % of the MXU's peak: the contraction is one 128-deep pass whatever the
packed width holds, so 72.6 ms is the floor of this formulation),
``tourney`` 65.9 ms = 9.9 us.  The compiler's own count of a step's bundles
(a compile for a described v5e with ``--xla_jf_dump_to`` /
``--xla_jf_dump_llo_text`` in ``LIBTPU_INIT_ARGS``) predicts ``full`` and
``tourney`` at 1.5 GHz within 3 % and costs no chip time; it sees no MXU
stall, so it under-reads ``dotonly``.  Raises where JAX finds no TPU: a
time is a device metric.
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

NSEG = pk.TB // pk.SEG


def _kernel(a_ref, b_ref, k1_out, k2_out, k3_out, *, variant):
    """The shipped kernel's segment loop with one half taken out, on the
    shipped kernel's own block shapes (``b_ref`` is a resident [TB, tile]
    f32 block of distances for ``tourney``, the reference block otherwise)."""
    tile = a_ref.shape[0]
    r = jnp.zeros((NSEG, tile), jnp.float32)
    for s in range(NSEG):
        if variant == "tourney":
            tri = None
            for c in range(s * pk.SEG, (s + 1) * pk.SEG, 64):
                t = pk._rows_top3(pk._segment_keys(b_ref[c:c + 64, :]))
                tri = t if tri is None else pk._merge_triples(tri, t)
            tri = pk._sublanes_top3(tri)
            r = r + tri[0] + tri[1] + tri[2]
            continue
        d2t = jax.lax.dot_general(
            b_ref[s * pk.SEG:(s + 1) * pk.SEG, :], a_ref[:],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if variant == "dotkey":
            d2t = pk._segment_keys(d2t)
        r = r + jnp.min(d2t.reshape(pk.SEG // NSEG, NSEG, tile), axis=0)
    for out in (k1_out, k2_out, k3_out):
        out[:] = r.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("variant", "tile"))
def run(a_mat, b_mat, variant, tile):
    m, n = a_mat.shape[0], b_mat.shape[0]
    if variant == "full":
        return pk._tourney_keys(a_mat, b_mat)
    if variant == "tourney":
        b_mat = (b_mat[:pk.TB, :1].astype(jnp.float32)
                 * a_mat[:tile, :1].astype(jnp.float32).T)
        b_spec = pl.BlockSpec((pk.TB, tile), lambda i, j: (0, 0),
                              memory_space=pltpu.VMEM)
    else:
        b_spec = pl.BlockSpec((pk.TB, b_mat.shape[1]), lambda i, j: (j, 0),
                              memory_space=pltpu.VMEM)
    spec = pl.BlockSpec((NSEG, tile), lambda i, j: (j, i),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, variant=variant),
        grid=(m // tile, n // pk.TB),
        in_specs=[
            pl.BlockSpec((tile, a_mat.shape[1]), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            b_spec,
        ],
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n // pk.SEG, m), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
    )(a_mat, b_mat)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", nargs="+", required=True,
                    choices=["dotonly", "dotkey", "tourney", "full"])
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--tile", type=int, default=pk.TM,
                    choices=[128, 256, 512])
    ap.add_argument("--n", type=int, default=13 << 20)
    ap.add_argument("--width", type=int, default=128)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("knn_decomp_probe times a TPU kernel: no TPU here")
    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.uniform(ka, (args.m, args.width), jnp.bfloat16)
    b = jax.random.uniform(kb, (args.n, args.width), jnp.bfloat16)
    if args.m % args.tile:
        raise SystemExit("--m must be whole tiles of --tile rows")
    if "full" in args.variant and args.tile != pk.query_tile(args.m):
        raise SystemExit(f"full runs the shipped wrapper, whose tile for "
                         f"--m {args.m} is {pk.query_tile(args.m)}")
    steps = (args.m // args.tile) * (args.n // pk.TB)
    for variant in args.variant:
        o = run(a, b, variant, args.tile)
        np.asarray(o[0][0, 0])
        vals = []
        for _ in range(5):
            t0 = time.perf_counter()
            bias = jnp.bfloat16(0)
            for _ in range(4):
                o = run(a + bias, b, variant, args.tile)
                bias = (o[0][0, 0] * 0).astype(jnp.bfloat16)
            np.asarray(o[0][0, 0])
            vals.append((time.perf_counter() - t0) / 4 * 1e3)
        ms = float(np.median(vals))
        print(json.dumps({"variant": variant, "m": args.m, "n": args.n,
                          "width": args.width, "tile": args.tile,
                          "ms_per_call_median": round(ms, 2),
                          "us_per_step": round(ms * 1e3 / steps, 2),
                          "passes_ms": [round(v, 2) for v in vals]}),
              flush=True)


if __name__ == "__main__":
    main()
