#!/usr/bin/env python
"""What does bare XLA achieve on the co-occurrence gram shapes?

Times (a) the raw int8 matmul [W, N]·[N, W] at several W, (b) the full
XLA-only NB+MI count step: joint codes → one-hot X [N, W] int8 in HBM →
G = XᵀX, no Pallas anywhere.  If XLA's int8 gram runs near peak, the
HBM-one-hot form (round 2 dismissed it when the SCATTER was the wall) may
now beat the in-VMEM expand kernel whose dot orientation runs at <10% of
the MXU int8 peak (benchmarks/dot_orient_probe.py).

Sync: sequential launches on the single TPU compute stream execute FIFO;
one host fetch of the last result is the barrier.  Sanity: per-call time must dwarf the ~1 ms chained
dispatch cost.
"""

import argparse
import functools
import json
import time

import numpy as np
import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("nc", "nb", "w"))
def onehot_gram(codes_t, labels, nc, nb, w):
    """codes_t [F, N] int32, labels [N] → G [W, W] int32 via HBM one-hot."""
    f = codes_t.shape[0]
    y = labels[None, :]
    valid = (y >= 0) & (y < nc)
    joint = jnp.where(valid, codes_t * nc + y, -1)       # [F, N]
    wcode = joint * f + jnp.arange(f, dtype=jnp.int32)[:, None]  # j-major
    wcode = jnp.where(joint >= 0, wcode, -1)
    x = jax.nn.one_hot(wcode.T, w, dtype=jnp.int8, axis=-1)      # [N, F, W]
    x = x.sum(axis=1, dtype=jnp.int8)                             # [N, W]
    return jax.lax.dot_general(x, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("acc",))
def gram_only(x, acc=jnp.int32):
    return jax.lax.dot_general(x, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=acc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["dot", "full"], default="dot")
    ap.add_argument("--w", type=int, default=384)
    ap.add_argument("--n", type=int, default=8_388_608)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--dtype", choices=["int8", "int4", "bf16"],
                    default="int8")
    args = ap.parse_args()
    rng = np.random.default_rng(0)

    if args.mode == "dot":
        x = jnp.asarray(rng.integers(0, 2, size=(args.n, args.w),
                                     dtype=np.int8))
        acc = jnp.int32
        if args.dtype == "int4":
            x = x.astype(jnp.int4)
        elif args.dtype == "bf16":
            x = x.astype(jnp.bfloat16)
            acc = jnp.float32
        g = gram_only(x, acc)
        float(g[0, 0])                                   # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.reps):
            g = gram_only(x, acc)
        float(g[0, 0])
        dt = (time.perf_counter() - t0) / args.reps
        print(json.dumps({
            "mode": "dot", "w": args.w, "n": args.n, "dtype": args.dtype,
            "ms_per_dot": round(dt * 1e3, 2),
            "eff_int8_tops": round(2.0 * args.w ** 2 * args.n / dt / 1e12, 1),
            "rows_per_sec": round(args.n / dt, 1),
        }))
        return

    nc, nb, f = 2, 12, 11
    n = args.n
    codes_t = jnp.asarray(
        rng.integers(0, nb, size=(f, n), dtype=np.int32))
    labels = jnp.asarray(rng.integers(0, nc, size=n, dtype=np.int32))
    w = -(-f * nb * nc // 128) * 128
    g = onehot_gram(codes_t, labels, nc, nb, w)
    float(g[0, 0])
    t0 = time.perf_counter()
    for _ in range(args.reps):
        g = onehot_gram(codes_t, labels + (g[0, 0] * 0).astype(jnp.int32),
                        nc, nb, w)
    float(g[0, 0])
    dt = (time.perf_counter() - t0) / args.reps
    print(json.dumps({
        "mode": "full", "w": w, "n": n,
        "ms_per_step": round(dt * 1e3, 2),
        "rows_per_sec": round(n / dt, 1),
    }))


if __name__ == "__main__":
    main()
