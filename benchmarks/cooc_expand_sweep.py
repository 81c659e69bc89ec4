#!/usr/bin/env python
"""Bisection harness for the co-occurrence kernel's VPU expand wall.

Round 3 established (ops/pallas_hist.py notes): the int8-MXU XᵀX pass is
~12.6 ms of the ~34 ms 16M-row chunk — i.e. the one-hot expand/compare at
W·N cells governs, not the matmul.  This sweep times EXPAND VARIANTS of the
same G = XᵀX kernel, one configuration per process run (fresh-process
discipline — in-process A/B drifted 30-50%), chained-dispatch host-fetch
sync.

Variants:
- ``base``     round-3 shipped kernel: tile-concatenate [W, BN] int32 +
               compare against iota//F, incl. compares on the Wp-W padding
               rows (j-major G layout).
- ``dotonly``  xt = zeros: the dot + grid overhead floor (no expand at all;
               counts are garbage — timing only).
- ``nocmp``    expand copy without compare: jrept.astype(int8) (garbage
               counts — isolates the concatenate+pack cost).
- ``fmaj32``   f-major broadcast expand: (joint[:,None,:] == iota_jc32)
               .astype(int8) — 3-D compare with jc padded to 32 so the int8
               (32,128) tiling is clean, reshape [F·jc32, BN] is a no-op
               tile collapse, zero-pad to Wp is tile-aligned.  No int32
               [W, BN] materialization at all → VMEM drops ~5×, so BN can
               grow past the base variant's budget.
- ``fmaj8``    same broadcast but compare→int32 3-D (jc padded to 8),
               reshape, int32 zero-pad, then one 2-D astype(int8) pack —
               for the case where the 3-D int8 select doesn't lower.

Usage:  python benchmarks/cooc_expand_sweep.py --variant fmaj32 --bn 98304
Each run prints one JSON line; run variants sequentially (a chip belongs
to ONE process at a time).
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_INVALID = -(1 << 20)
_PAD_SEL = -(1 << 20) - 1


def _ru(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------------------
# expand variants: joint [F, BN] int32 -> Xᵀ [Wp, BN] int8
# --------------------------------------------------------------------------

def _expand_base(joint, *, f, jc, wp):
    w = f * jc
    bn = joint.shape[1]
    jrept = jnp.concatenate([joint] * jc, axis=0)
    if wp > w:
        jrept = jnp.concatenate(
            [jrept, jnp.full((wp - w, bn), _INVALID, jnp.int32)], axis=0)
    jw = jax.lax.broadcasted_iota(jnp.int32, (wp, 1), 0)
    jsel = jnp.where(jw < w, jw // f, _PAD_SEL)
    return (jrept == jsel).astype(jnp.int8)


def _expand_nocmp(joint, *, f, jc, wp):
    w = f * jc
    bn = joint.shape[1]
    jrept = jnp.concatenate([joint] * jc, axis=0)
    if wp > w:
        jrept = jnp.concatenate(
            [jrept, jnp.full((wp - w, bn), _INVALID, jnp.int32)], axis=0)
    return jrept.astype(jnp.int8)          # garbage values; timing only


def _expand_fmaj32(joint, *, f, jc, wp):
    bn = joint.shape[1]
    jcp = _ru(jc, 32)
    jv = jax.lax.broadcasted_iota(jnp.int32, (1, jcp, 1), 1)
    xt = (joint[:, None, :] == jv).astype(jnp.int8)       # [F, jc32, BN]
    xt = xt.reshape(f * jcp, bn)
    if wp > f * jcp:
        xt = jnp.concatenate(
            [xt, jnp.zeros((wp - f * jcp, bn), jnp.int8)], axis=0)
    return xt


def _expand_fmaj8(joint, *, f, jc, wp):
    bn = joint.shape[1]
    jcp = _ru(jc, 8)
    jv = jax.lax.broadcasted_iota(jnp.int32, (1, jcp, 1), 1)
    x32 = (joint[:, None, :] == jv).astype(jnp.int32)     # [F, jc8, BN]
    x32 = x32.reshape(f * jcp, bn)
    if wp > f * jcp:
        x32 = jnp.concatenate(
            [x32, jnp.zeros((wp - f * jcp, bn), jnp.int32)], axis=0)
    return x32.astype(jnp.int8)


_EXPANDS = {
    "base": (_expand_base, "jmaj"),
    "nocmp": (_expand_nocmp, "none"),
    "fmaj32": (_expand_fmaj32, "fmaj32"),
    "fmaj8": (_expand_fmaj8, "fmaj8"),
}

# variants fed codes ALREADY in [F, N] layout (no XLA transpose in the
# prologue — the dotonly-vs-base result showed the expand itself is nearly
# free, making the 704 MB/chunk HBM transpose the prime suspect)
_T_VARIANTS = {"base_t": "base", "dotonly_t": "dotonly", "fmaj32_t": "fmaj32"}
# "fused32": joint computed inside the kernel from streamed codes_t+labels
# blocks (saves the separate [F, N] joint materialization round trip too)


def _wp_for(variant: str, f: int, jc: int) -> int:
    if variant == "fmaj32":
        return _ru(f * _ru(jc, 32), 128)
    if variant == "fmaj8":
        return _ru(f * _ru(jc, 8), 128)
    return _ru(f * jc, 128)


def _kernel(joint_ref, out_ref, *, f, jc, wp, n, variant):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    joint = joint_ref[:]
    bn = joint.shape[1]
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        joint = jnp.where(lane < n - i * bn, joint, _INVALID)
    if variant == "dotonly":
        xt = jnp.zeros((wp, bn), jnp.int8)
    else:
        xt = _EXPANDS[variant][0](joint, f=f, jc=jc, wp=wp)
    acc = jax.lax.dot_general(xt, xt, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out_ref[:] += acc


def _fused_kernel(codes_ref, labels_ref, out_ref, *, f, jc, wp, n, nclass,
                  expand):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    ct = codes_ref[:]                                  # [F, BN] int32
    y = labels_ref[:]                                  # [1, BN] int32
    bn = ct.shape[1]
    valid = (y >= 0) & (y < nclass)
    if n % bn or n == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        valid &= lane < n - i * bn
    joint = jnp.where(valid, ct * nclass + y, _INVALID)
    xt = _EXPANDS[expand][0](joint, f=f, jc=jc, wp=wp)
    acc = jax.lax.dot_general(xt, xt, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out_ref[:] += acc


@functools.partial(jax.jit, static_argnames=(
    "num_bins", "num_classes", "bn", "variant", "interpret"))
def cooc_variant(codes, labels, num_bins, num_classes, bn, variant,
                 interpret=False):
    jc = num_bins * num_classes
    npad_of = lambda n: _ru(max(n, bn), bn)
    if variant == "fused32":
        f, n = codes.shape[0], codes.shape[1]          # codes given [F, N]
        wp = _wp_for("fmaj32", f, jc)
        return pl.pallas_call(
            functools.partial(_fused_kernel, f=f, jc=jc, wp=wp, n=n,
                              nclass=num_classes, expand="fmaj32"),
            grid=(npad_of(n) // bn,),
            in_specs=[pl.BlockSpec((f, bn), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((1, bn), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((wp, wp), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((wp, wp), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=110 * 1024 * 1024),
            interpret=interpret,
        )(codes, labels[None, :] if labels.ndim == 1 else labels)
    if variant in _T_VARIANTS:                         # codes given [F, N]
        variant = _T_VARIANTS[variant]
        f, n = codes.shape[0], codes.shape[1]
        codes_t = codes.astype(jnp.int32)
    else:
        n, f = codes.shape
        codes_t = codes.T.astype(jnp.int32)
    wp = _wp_for(variant, f, jc)
    y = labels[None, :]
    valid = (y >= 0) & (y < num_classes)
    joint = jnp.where(valid, codes_t * num_classes + y, _INVALID)
    return pl.pallas_call(
        functools.partial(_kernel, f=f, jc=jc, wp=wp, n=n, variant=variant),
        grid=(npad_of(n) // bn,),
        in_specs=[pl.BlockSpec((f, bn), lambda i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((wp, wp), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((wp, wp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=110 * 1024 * 1024),
        interpret=interpret,
    )(joint)


# --------------------------------------------------------------------------
# correctness: interpret-mode check vs a numpy one-hot gram, per layout
# --------------------------------------------------------------------------

def _numpy_g(codes, labels, b, c, variant, f):
    jc = b * c
    n = codes.shape[0]
    joint = codes.astype(np.int64) * c + labels[:, None]
    joint[(labels < 0) | (labels >= c)] = -1
    if variant == "fmaj32":
        jcp, fmaj = _ru(jc, 32), True
    elif variant == "fmaj8":
        jcp, fmaj = _ru(jc, 8), True
    else:
        jcp, fmaj = jc, False
    wp = _wp_for(variant, f, jc)
    x = np.zeros((n, wp), np.int64)
    for fi in range(f):
        for row in range(n):
            j = joint[row, fi]
            if 0 <= j < jc:
                w = fi * jcp + j if fmaj else j * f + fi
                x[row, w] = 1
    return x.T @ x


def self_check(variant: str) -> None:
    if "dotonly" in variant or variant == "nocmp":
        return
    rng = np.random.default_rng(7)
    f, b, c, n = 5, 4, 3, 1000
    codes = rng.integers(0, b, size=(n, f)).astype(np.int32)
    labels = rng.integers(-1, c, size=n).astype(np.int32)   # incl. invalid
    dcodes = jnp.asarray(np.ascontiguousarray(codes.T)) \
        if (variant in _T_VARIANTS or variant == "fused32") \
        else jnp.asarray(codes)
    g = np.asarray(cooc_variant(dcodes, jnp.asarray(labels),
                                b, c, 256, variant, interpret=True))
    base_name = _T_VARIANTS.get(variant,
                                "fmaj32" if variant == "fused32" else variant)
    ref = _numpy_g(codes, labels, b, c, base_name, f)
    np.testing.assert_array_equal(g, ref)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="base",
                    choices=["base", "dotonly", "nocmp", "fmaj32", "fmaj8",
                             "base_t", "dotonly_t", "fmaj32_t", "fused32"])
    ap.add_argument("--bn", type=int, default=49152)
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--no-check", action="store_true")
    args = ap.parse_args()

    if not args.no_check:
        self_check(args.variant)

    n_classes, n_bins, n_feat = 2, 12, 11     # hosp_readmit shape
    chunk = 16_000_000
    rng = np.random.default_rng(0)
    codes = rng.integers(0, n_bins, size=(chunk, n_feat), dtype=np.int32)
    labels = rng.integers(0, n_classes, size=chunk, dtype=np.int32)
    if args.variant in _T_VARIANTS or args.variant == "fused32":
        dcodes = jnp.asarray(np.ascontiguousarray(codes.T))
    else:
        dcodes = jnp.asarray(codes)
    dlabels = jnp.asarray(labels)

    def timed_pass():
        bias = jnp.int32(0)
        t0 = time.perf_counter()
        for _ in range(args.chunks):
            out = cooc_variant(dcodes, dlabels + bias, n_bins, n_classes,
                               args.bn, args.variant)
            bias = (out[0, 0] * 0).astype(jnp.int32)
        float(out[0, 0])                       # host fetch = the only barrier
        return args.chunks * chunk / (time.perf_counter() - t0)

    timed_pass()                               # compile + warm
    timed_pass()
    passes = [timed_pass() for _ in range(args.passes)]
    med = float(np.median(passes))
    print(json.dumps({
        "variant": args.variant, "bn": args.bn,
        "rows_per_sec": round(med, 1),
        "ms_per_chunk": round(chunk / med * 1e3, 2),
        "passes_rows_per_sec": [round(p, 1) for p in passes],
        "wp": _wp_for(args.variant, n_feat, n_bins * n_classes),
    }))


if __name__ == "__main__":
    main()
