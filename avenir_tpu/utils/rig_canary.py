"""Rig-state canaries — tiny bare-XLA probes that separate "the host or
chip is slow right now" from "a kernel regressed".

Motivation (round 5): a kNN median captured 45% below the published band
with the kernel code unchanged — absolute rates swing with what else the
host is doing, so every benchmark artifact carries two bare-XLA reference
timings measured in the same process, moments before the headline
measurement:

- ``matmul_4096_bf16_ms`` — a chained 4096x4096x4096 bf16 matmul
  (137 GFLOP/call).  Pure MXU + HBM; no custom kernels, no framework
  code — if this is slow, the rig is slow.
- ``knn_dot_ms`` (kNN artifacts only) — the bare distance dot at the kNN
  serving shape ([batch, 128] x [1M, 128]^T bf16 with a running row max),
  the measured lower bound the fused search kernel is judged against
  (docs/architecture.md "ceilings").  If headline QPS drops while this
  stays put, the kernel (or its memory layout) regressed; if both drop by
  the same factor, the rig did.

Timing methodology:

1. The barrier is a host fetch of the chain's last scalar.
2. The probe chains N dispatches and fetches once, so the fetch's round
   trip is paid once per chain.
3. Each probe step is ONE jitted call returning a 0-d carry (the scalar
   chains into the next call's operand): per-op eager dispatch overhead
   is large and variable.
4. The constant overhead (final fetch + warmup jitter) is removed by a
   two-point slope: time chains of ``reps_lo`` and ``reps_hi`` calls and
   report ``(t_hi - t_lo) / (reps_hi - reps_lo)``.

The thresholds below were read on a machine that no longer exists
(records deleted in PR 23, see git history); on today's chip they are not
measured.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def _slope_ms(step_scalar, operand, reps_lo: int = 2, reps_hi: int = 10) -> float:
    """Per-call ms of ``step_scalar(operand, carry) -> 0-d carry`` via the
    two-point chained-dispatch slope (see module doc)."""
    def run(n: int) -> float:
        carry = jnp.zeros((), jnp.float32)
        t0 = time.perf_counter()
        for _ in range(n):
            carry = step_scalar(operand, carry)
        np.asarray(jax.device_get(carry))
        return time.perf_counter() - t0

    run(2)                                   # compile
    run(reps_hi)                             # full-length warm: the first
    # post-startup chain runs with lazy transport/allocator init still in
    # flight (a process's first canary measured a 0.0 slope once)
    # min-of-2 per point: a single transient stall in either chain can
    # collapse (or explode) the slope — the embedded round-5 bench run
    # recorded a 1.14 ms knn-dot "bound" (physically impossible for the
    # ~30 ms of MXU work) from exactly that; minima resist one-off stalls
    t_lo = min(run(reps_lo) for _ in range(2))
    t_hi = min(run(reps_hi) for _ in range(2))
    return max((t_hi - t_lo) * 1e3 / (reps_hi - reps_lo), 0.0)


def matmul_canary_ms(dim: int = 4096, reps: int = 32) -> float:
    """Chained ``dim³`` bf16 matmul, per-call ms (2·dim³ FLOPs/call).

    ``reps`` sized so the chain differential (~reps · 5 ms) clearly
    exceeds the per-fetch round-trip variance — at 8 reps the ~40 ms
    signal drowned in that noise inside long-lived processes.

    INTERPRETATION: healthy readings are themselves noisy — fresh
    processes measured ~4–6 ms, long-lived ones lower (a deep dispatch
    pipeline hides parts of a short chain behind the fetch) — so treat
    any reading ≲ 7 ms as "healthy".  The
    signal this canary exists for is the CONTENDED regime, which reads
    10–100× higher (measured 167–192 ms under host-CPU load) and is
    unmistakable.  The kNN dot canary (~250 ms of work per chain) sits
    well above the noise and is the steadier of the two."""
    a = jnp.asarray(np.random.default_rng(0).normal(
        size=(dim, dim)).astype(np.float32)).astype(jnp.bfloat16)

    @jax.jit
    def step(x, carry):
        out = jnp.dot(x + carry.astype(jnp.bfloat16), a,
                      preferred_element_type=jnp.float32)
        # data-dependent 0-d carry, scaled so the chained perturbation is
        # far below bf16 resolution (never constant-foldable, never drifts)
        return out[0, 0] * jnp.float32(1e-30)

    return _slope_ms(step, a, reps_lo=2, reps_hi=2 + reps)


def knn_dot_canary_ms(batch: int = 16384, n_refs: int = 1_000_000,
                      width: int = 128, reps: int = 8,
                      refs=None) -> float:
    """Chained bare distance dot at the kNN serving shape, per-call ms.

    ``refs`` may pass an existing device-resident [n_refs, width] bf16
    operand (e.g. the actual packed reference matrix) so the canary times
    the dot against the very buffer the kernel reads; by default it
    uploads a fresh one.  The dot streams reference tiles under a
    ``lax.scan`` with a running row max — the monolithic [batch, n_refs]
    f32 output would be ~65 GB at the serving shape (XLA:TPU does not
    fuse a reduce into a matmul).
    """
    rng = np.random.default_rng(0)
    if refs is None:
        refs = jnp.asarray(rng.normal(size=(n_refs, width))
                           .astype(np.float32)).astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(batch, width))
                    .astype(np.float32)).astype(jnp.bfloat16)
    tile = 16384
    n = refs.shape[0] - refs.shape[0] % tile
    r_tiles = refs[:n].reshape(-1, tile, refs.shape[1])

    @jax.jit
    def step(x, carry):
        xq = x + carry.astype(x.dtype)

        def body(best, r):
            d = jnp.dot(xq, r.T, preferred_element_type=jnp.float32)
            return jnp.maximum(best, d.max(axis=1)), None

        init = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
        best, _ = jax.lax.scan(body, init, r_tiles)
        return best[0] * jnp.float32(1e-30)

    return _slope_ms(step, q, reps_lo=1, reps_hi=1 + reps)
