"""Explicit-collective training steps (shard_map + psum).

The auto-sharding path (sharded inputs under ``jax.jit``) already lets XLA
insert the all-reduce; this module is the explicit SPMD spelling of the same
programs — per-device partial aggregation (the reference's combiner) followed
by ``lax.psum`` over the ``data`` mesh axis (the reference's shuffle), with
the large count tensors optionally sharded over a ``model`` axis (the
reference's key-space partitioners, explore/ClassPartitionGenerator.java:600-606).

Used by sharded fit paths and by ``__graft_entry__.dryrun_multichip`` to
validate multi-chip compilation on a virtual device mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from avenir_tpu.ops.agg import (_check_chunk, one_hot as _onehot,
                                pair_class_counts)


def _shard_map_norep(step, mesh, in_specs, out_specs):
    """shard_map with the replicated-output check disabled."""
    return _shard_map(step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)


def sharded_nb_fit_step(mesh: Mesh, num_classes: int, num_bins: int, num_cont: int):
    """Build a jitted SPMD Naive-Bayes sufficient-statistics step.

    Inputs: codes [N, F] int32, labels [N] int32, cont [N, Fc] float32, all
    sharded over ``data`` on axis 0. Outputs (replicated): [F, B, C] bin
    counts, [C] class counts, ([C], [C,Fc], [C,Fc]) moments.
    """

    def step(codes, labels, cont):
        oh_b = _onehot(codes, num_bins)                      # [n, F, B] local
        oh_c = _onehot(labels, num_classes)                  # [n, C]
        fbc = jnp.einsum("nfb,nc->fbc", oh_b, oh_c, precision="highest")
        cc = jnp.sum(oh_c, axis=0)
        s1 = jnp.einsum("nc,nf->cf", oh_c, cont, precision="highest")
        s2 = jnp.einsum("nc,nf->cf", oh_c, cont * cont, precision="highest")
        # the 'shuffle': one all-reduce over ICI per tensor
        fbc = jax.lax.psum(fbc, "data")
        cc = jax.lax.psum(cc, "data")
        s1 = jax.lax.psum(s1, "data")
        s2 = jax.lax.psum(s2, "data")
        return fbc, cc, cc, s1, s2

    wrapped = _shard_map(
        step, mesh=mesh,
        in_specs=(P("data", None), P("data"), P("data", None)),
        out_specs=(P(), P(), P(), P(), P()),
    )
    return jax.jit(wrapped)


def sharded_nb_fit_step_2d(mesh: Mesh, num_classes: int, num_bins: int):
    """2-D (data × model) variant: batch sharded over ``data``; the [F, B, C]
    count tensor computed and *kept sharded* over ``model`` on the feature
    axis — the layout for high-cardinality tensors that must not be
    replicated per device (SURVEY.md §7 'hard parts').

    F must be divisible by the ``model`` axis size.
    """

    def step(codes, labels):
        # codes arrive [n_local, F_local]: data-sharded rows, model-sharded features
        oh_b = _onehot(codes, num_bins)
        oh_c = _onehot(labels, num_classes)
        fbc = jnp.einsum("nfb,nc->fbc", oh_b, oh_c, precision="highest")
        fbc = jax.lax.psum(fbc, "data")      # reduce over data only; stays model-sharded
        # labels are replicated over 'model', so reducing over 'data' alone
        # already yields the global class counts on every model rank
        cc = jax.lax.psum(jnp.sum(oh_c, axis=0), "data")
        return fbc, cc

    wrapped = _shard_map(
        step, mesh=mesh,
        in_specs=(P("data", "model"), P("data")),
        out_specs=(P("model", None, None), P()),
    )
    return jax.jit(wrapped)


@functools.lru_cache(maxsize=32)
def sharded_knn_topk(mesh: Mesh, k: int, num_bins: int,
                     metric: str = "euclidean", data_axis: str = "data",
                     ref_tile: int = 65536):
    """Exact global k-NN with the reference set sharded over the mesh.

    The reference outsources its O(M·N) all-pairs distances to a Hadoop job
    (resource/knn.sh:47-60); the multi-chip spelling here shards the
    reference rows over ``data`` (queries replicated), and each device scans
    its local shard in ``ref_tile``-row tiles with a running exact top-k —
    the same bounded-memory discipline as the single-device scan, so
    per-device memory is O(M·ref_tile), never O(M·N/D) — then merges with
    one ``lax.all_gather`` of the [M, k] candidates: k·D values per query
    cross ICI instead of the N-row distance matrix.

    Returns a jitted fn(test_codes, test_cont, ref_codes, ref_cont, lo, hi,
    n_real) → ([M, k] distances, [M, k] global reference indices). The
    reference arrays must be padded so each device's shard is a whole
    number of ``ref_tile`` tiles; pad rows (global index ≥ n_real) are
    masked to +inf so they can never win the top-k. Requires k ≤ local
    shard rows. Cached per (mesh, k, bins, metric, tile) so repeated
    queries reuse the compiled program.
    """
    from avenir_tpu.models.knn import _tile_distances

    def step(tc, tx, rc, rx, lo, hi, n_real):
        local = rc.shape[0]
        # whole shard as one tile when it isn't tile-divisible (direct
        # callers with small shards); _nearest_neighbors_sharded pads the
        # global array so production shards always divide
        tile = ref_tile if local >= ref_tile and local % ref_tile == 0 \
            else local
        t = local // tile
        rc_t = rc.reshape(t, tile, rc.shape[1])
        rx_t = rx.reshape(t, tile, rx.shape[1])
        m = tc.shape[0] if tc.size else tx.shape[0]
        base = jax.lax.axis_index(data_axis) * local

        def body(carry, xs):
            best_d, best_i, t0 = carry
            rct, rxt = xs
            d = _tile_distances(tc, tx, rct, rxt, lo, hi, num_bins, metric)
            idx = base + t0 + jnp.arange(tile, dtype=jnp.int32)
            d = jnp.where(idx[None, :] < n_real, d, jnp.inf)
            cd = jnp.concatenate([best_d, d], axis=1)
            cix = jnp.concatenate(
                [best_i, jnp.broadcast_to(idx[None, :], d.shape)], axis=1)
            neg, pos = jax.lax.top_k(-cd, k)
            return (-neg, jnp.take_along_axis(cix, pos, axis=1),
                    t0 + jnp.int32(tile)), None

        best_d = jnp.full((m, k), jnp.inf, jnp.float32)
        best_i = jnp.full((m, k), -1, jnp.int32)
        (best_d, best_i, _), _ = jax.lax.scan(
            body, (best_d, best_i, jnp.int32(0)), (rc_t, rx_t))
        # [M, D·k] candidates on every device, then the final exact top-k
        dg = jax.lax.all_gather(best_d, data_axis, axis=1, tiled=True)
        ig = jax.lax.all_gather(best_i, data_axis, axis=1, tiled=True)
        neg2, pos2 = jax.lax.top_k(-dg, k)
        return -neg2, jnp.take_along_axis(ig, pos2, axis=1)

    # the outputs are replicated (every device holds the same merged top-k
    # after the all_gather), but shard_map cannot infer that statically
    in_specs = (P(), P(), P(data_axis, None), P(data_axis, None), P(), P(), P())
    wrapped = _shard_map_norep(step, mesh, in_specs, (P(), P()))
    return jax.jit(wrapped)


def merge_shard_topk(d2: jax.Array, i: jax.Array, limit: jax.Array, k: int,
                     data_axis: str = "data"):
    """Inside a ``shard_map``: every shard's local top-k (``d2`` [M, k] exact
    squared distances ascending, ``i`` [M, k] GLOBAL reference indices, −1 =
    no reference) and its ``limit`` [M] — no reference of the shard outside
    what it returned, or outranked among it, is nearer — → the global top-k
    and its certificate, the same on every device.  ONE tiled ``all_gather``
    of an int32 payload (distances and limit bit-cast, the indices: (2k +
    1)·D words a query cross ICI) and one ``lax.top_k`` over the [M, D·k]
    candidates.  A row is exact where its merged k-th distance is within
    EVERY shard's limit (implied by each shard's own certificate, and weaker:
    the merged k-th is no farther than any shard's own k-th).  Returns (d2
    [M, k], i [M, k], certificate [M], rows each shard's limit refused [D]).
    Ties keep shard order, then the shard's own order: the order of a search
    over the concatenated rows."""
    shards = jax.lax.axis_size(data_axis)
    bits = functools.partial(jax.lax.bitcast_convert_type,
                             new_dtype=jnp.int32)
    payload = jnp.concatenate([bits(d2), i, bits(limit)[:, None]], axis=1)
    g = jax.lax.all_gather(payload, data_axis, axis=1, tiled=True)
    g = g.reshape(d2.shape[0], shards, 2 * k + 1)
    ig = g[:, :, k:2 * k].reshape(-1, shards * k)
    dg = jax.lax.bitcast_convert_type(g[:, :, :k], jnp.float32)
    # a slot with no reference behind it must lose to every real one
    dg = jnp.where(ig < 0, jnp.inf, dg.reshape(-1, shards * k))
    neg, pos = jax.lax.top_k(-dg, k)
    within = -neg[:, -1:] <= jax.lax.bitcast_convert_type(g[:, :, 2 * k],
                                                          jnp.float32)
    return (-neg, jnp.take_along_axis(ig, pos, axis=1), within.all(axis=1),
            (~within).sum(axis=0, dtype=jnp.int32))


@functools.lru_cache(maxsize=32)
def sharded_knn_fused(mesh: Mesh, shard_rows: int, k: int, kk: int,
                      num_bins: int, rows: int, extra_norm: float,
                      total_attrs: int, eps: float,
                      data_axis: str = "data"):
    """The fused search's candidates (ops/pallas_knn.py::fused_candidates:
    query pack → candidate kernel → exact f32 re-rank, and the limit below
    which the shard hides nothing) on every shard of a row-sharded index,
    merged and certified by :func:`merge_shard_topk` — one compiled program
    a (query rows, k) shape.

    Shard ``s`` holds reference rows [s·shard_rows, (s+1)·shard_rows) of the
    concatenated set: its packed operand, and the codes and normalised
    coordinates the re-rank gathers from (KNNModel.device_sharded).  Its own
    real-row count, min(n − s·shard_rows, shard_rows), masks its pad rows;
    local indices leave the shard as global ones.  Queries are replicated.

    Returns a jitted fn(codes_q, cont01_q, r_mat, codes_r, cont01_r, n) →
    ([M, k] distances, [M, k] global indices, [M] certificate, [D] rows
    each shard's limit refused), all replicated.  The caller sends the rows
    without a certificate to :func:`sharded_knn_topk`."""
    from avenir_tpu.ops import pallas_knn

    def _shard_search(codes_q, cont01_q, r_mat, codes_r, cont01_r, n):
        base = jax.lax.axis_index(data_axis) * shard_rows
        d2s, idxs, _kth, limit = pallas_knn.fused_candidates(
            codes_q, cont01_q, r_mat, codes_r, cont01_r,
            jnp.clip(n - base, 0, shard_rows), num_bins=num_bins, rows=rows,
            extra_norm=extra_norm, k=k, kk=kk, eps=eps)
        i = idxs[:, :k]
        d2, gi, cert, refused = merge_shard_topk(
            d2s[:, :k], jnp.where(i < 0, -1, i + base), limit, k, data_axis)
        return (pallas_knn.unit_distances(d2, total_attrs), gi, cert,
                refused)

    by_rows = P(data_axis, None)
    # norep: pallas_call outputs carry no varying-mesh-axis metadata, and
    # the merged outputs are replicated by construction
    return jax.jit(_shard_map_norep(
        _shard_search, mesh, (P(), P(), by_rows, by_rows, by_rows, P()),
        (P(), P(), P(), P())))


@functools.lru_cache(maxsize=32)
def sharded_knn_pack(mesh: Mesh, num_bins: int, data_axis: str = "data"):
    """The fused search's packed reference operand, built by every device
    from the rows it holds (ops/pallas_knn.py::pack_refs_dev).

    Returns a jitted fn(codes, cont01, norm, n) — row-sharded [D·R, ·]
    arrays and the real row count of the whole set — → the [D·operand_rows(R),
    W] bf16 operand, row-sharded the same way."""
    from avenir_tpu.ops import pallas_knn

    def _shard_pack(codes, cont01, norm, n):
        local = cont01.shape[0]
        base = jax.lax.axis_index(data_axis) * local
        return pallas_knn.pack_refs_dev(
            codes, cont01, norm, jnp.clip(n - base, 0, local), num_bins)

    by_rows = P(data_axis, None)
    # norep: the loop's zero-filled carry is not marked as varying over the
    # mesh, the chunks written into it are
    return jax.jit(_shard_map_norep(
        _shard_pack, mesh, (by_rows, by_rows, P(data_axis), P()), by_rows))


def sharded_lr_step(mesh: Mesh, data_axis: str = "data"):
    """Data-parallel logistic-regression step: per-device partial gradient
    (the reference's per-mapper Σ x·(y−σ(wᵀx)) accumulation,
    regress/LogisticRegressionJob.java:169-176) + ``psum`` (its single
    reducer), then the weight update — replicated weights out.

    Returns a jitted fn(w [D], x [N, D] data-sharded, y [N] data-sharded,
    n_total, lr, l2) → new w.
    """

    def step(w, x, y, n_total, lr, l2):
        p = jax.nn.sigmoid(x @ w)
        partial_g = x.T @ (y - p)                 # local combiner output
        grad = jax.lax.psum(partial_g, data_axis) / n_total - l2 * w
        return w + lr * grad

    wrapped = _shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(data_axis, None), P(data_axis), P(), P(), P()),
        out_specs=P(),
    )
    return jax.jit(wrapped)


def sharded_mi_step(mesh: Mesh, num_classes: int, num_bins: int,
                    data_axis: str = "data", model_axis: str = "model"):
    """2-D sharded mutual-information count step — the high-cardinality
    joint-distribution layout (SURVEY.md §7 "hard parts": feature-pair×class
    one-hots are O(F²·V²·C)).

    Batch shards over ``data`` (the reference's record sharding across MI
    mappers, explore/MutualInformation.java:136-214); the [P, B, B, C]
    pair-class tensor shards its *pair axis* over ``model`` (the reference's
    key-space partitioning of (distrType, ordinals…) shuffle keys), so each
    device holds only P/model_parallel of the largest tensor while the
    ``psum`` over ``data`` plays the combiner+shuffle. The [F, B, C]
    feature-class tensor and [C] class counts are cheap and come back
    replicated.

    Returns a jitted fn(codes [N, F] data-sharded, labels [N] data-sharded,
    ci [P] model-sharded, cj [P] model-sharded) →
    (pair_class [P, B, B, C] pair-axis model-sharded,
     feature_class [F, B, C] replicated, class_counts [C] replicated).
    """

    def step(codes, labels, ci, cj):
        _check_chunk(codes)            # per-shard f32 exact-accumulation cap
        oh_c = _onehot(labels, num_classes)            # [n_loc, C]
        # local slice of the pair list: gather both columns per local pair,
        # then the SAME two-operand joint (bin_j, class) kernel the
        # single-device path uses (ops/agg.py::pair_class_counts — 2.3× the
        # three-operand einsum on-chip, drop-invalid labels preserved)
        pabc = pair_class_counts(jnp.take(codes, ci, axis=1),
                                 jnp.take(codes, cj, axis=1),
                                 labels, num_classes, num_bins)
        fbc = jnp.einsum("nfb,nc->fbc", _onehot(codes, num_bins), oh_c,
                         precision="highest").astype(jnp.int32)
        cc = jnp.sum(oh_c, axis=0).astype(jnp.int32)
        return (jax.lax.psum(pabc, data_axis),
                jax.lax.psum(fbc, data_axis),
                jax.lax.psum(cc, data_axis))

    in_specs = (P(data_axis, None), P(data_axis),
                P(model_axis), P(model_axis))
    # fbc/cc are replicated across model by construction but shard_map
    # cannot infer it
    wrapped = _shard_map_norep(step, mesh, in_specs,
                               (P(model_axis, None, None, None), P(), P()))
    return jax.jit(wrapped)


def quantized_allreduce_sum(x: jax.Array, axis_name: str) -> jax.Array:
    """EQuARX-style bandwidth-reduced all-reduce-sum (arXiv 2506.17615)
    for count-tensor partials inside a ``shard_map``.

    Each device block-quantizes its partial to int8 with one f32 scale per
    trailing-axis row (``s = max(|row|, 127) / 127`` — never below 1, so
    partials whose cells all fit int8 quantize EXACTLY with scale 1), then
    ONE ``all_gather`` moves the int8 payload + scales (≈4× fewer bytes on
    the wire than an int32/f32 ring psum) and each device dequantizes and
    sums locally in f32.

    Exact whenever every per-device partial cell is ≤ 127 in magnitude —
    true for gram partials of chunks smaller than 127·D rows per cell —
    and bounded by scale/2 per device otherwise, which is why this rides
    behind ``shard.allreduce.quantized`` (default off) with the exact
    psum as the byte-identity oracle."""
    qmax = 127.0
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), qmax) / qmax
    q = jnp.round(xf / s).astype(jnp.int8)
    qg = jax.lax.all_gather(q, axis_name)            # [D, ...] int8
    sg = jax.lax.all_gather(s, axis_name)            # [D, ..., 1] f32
    return jnp.sum(qg.astype(jnp.float32) * sg, axis=0)


@functools.lru_cache(maxsize=32)
def sharded_scan_step(mesh: Mesh, num_bins: int, num_classes: int,
                      data_axis: str = "data", interpret: bool = False,
                      block_cols=None, quantized: bool = False,
                      moments: bool = True,
                      proc_axis: Optional[str] = None):
    """THE ShardGraft SharedScan dispatch (round 12): per-device Pallas
    co-occurrence gram + class counts + class moments of ONE data-sharded
    chunk, all-reduced over the mesh's data axis inside the compiled
    program — the reference's combiner (per-device partials) + shuffle
    (psum) for every table the scan's consumers collectively read, in one
    dispatch per chunk exactly like the single-chip fast path.

    Returns a jitted fn(codes [N, F] data-sharded, labels [N], cont
    [N, Fc]) → (G, cc [C] int32, cnt [C] f32, s1 [C, Fc] f32, s2 [C, Fc]
    f32), all replicated — or just (G, cc) under ``moments=False``
    (count-only consumer sets).  G's layout is the single-device kernel's
    (``pallas_hist.plan``/``w_index``), so ``counts_from_cooc`` reads it
    out unchanged and the fold is byte-identical to the 1-chip gram;
    per-device moment partials are exact f32 sums, so the psum'd moments
    match the single-chip fold bit-for-bit whenever those partials are
    exactly representable (integer-grid values — the scope the stream
    panes already document).

    ``interpret=True`` runs the kernel through the Pallas interpreter —
    how the host-mesh tier-1 byte-identity tests attest the collective
    wiring without Mosaic hardware.  ``quantized=True`` routes the gram
    all-reduce (the dominant payload) through
    :func:`quantized_allreduce_sum`; class counts and moments stay on the
    exact psum either way.

    CrossGraft (``proc_axis`` set): the GLOBAL form over a (proc × data)
    hybrid mesh — the batch axis sharded over BOTH axes, the gram
    reduced HIERARCHICALLY inside the same fused dispatch: ``psum`` over
    ``data`` first (the within-host ICI leg, always exact — the cheap
    hop carries full precision), then over ``proc`` (the cross-host DCN
    leg; under ``quantized`` THIS leg rides the EQuARX-style int8
    collective, because DCN — not ICI — is where the bytes hurt, arXiv
    2506.17615).  The DrJAX mapreduce decomposition (arXiv 2403.07128):
    per-host map + hierarchical reduce, one compiled program.  Counts
    and moments psum over both axes exactly.

    Memoized on the full signature (``Mesh`` is hashable): every
    ``ChunkFolder`` construction — one per ``SharedScan.run`` — reuses the
    SAME jitted program, so a warm pass warms all later runs in the
    process instead of each run paying a fresh trace+compile."""
    from avenir_tpu.ops import pallas_hist

    batch_axes = (data_axis if proc_axis is None
                  else (proc_axis, data_axis))

    def step(codes, labels, cont):
        _check_chunk(codes)        # per-shard f32 exact-accumulation cap
        g = pallas_hist.cooc_counts.__wrapped__(
            codes, labels, num_bins, num_classes, interpret=interpret,
            block_cols=block_cols)
        if proc_axis is None:
            if quantized:
                g = jnp.round(quantized_allreduce_sum(
                    g, data_axis)).astype(jnp.int32)
            else:
                g = jax.lax.psum(g, data_axis)
        else:
            # hierarchical: exact within-host psum, then the cross-host
            # leg — quantized only here, where the wire is DCN
            g = jax.lax.psum(g, data_axis)
            if quantized:
                g = jnp.round(quantized_allreduce_sum(
                    g, proc_axis)).astype(jnp.int32)
            else:
                g = jax.lax.psum(g, proc_axis)
        oh_c = _onehot(labels, num_classes)                    # [n_loc, C]
        cnt = jnp.sum(oh_c, axis=0)                            # exact f32
        cc = jax.lax.psum(cnt.astype(jnp.int32), batch_axes)
        if not moments:
            # count-only consumer sets skip the moment einsums + psums
            # entirely (the single-chip kernel path makes the same cut)
            return g, cc
        s1 = jnp.einsum("nc,nf->cf", oh_c, cont, precision="highest")
        s2 = jnp.einsum("nc,nf->cf", oh_c, cont * cont,
                        precision="highest")
        return (g, cc,
                jax.lax.psum(cnt, batch_axes),
                jax.lax.psum(s1, batch_axes),
                jax.lax.psum(s2, batch_axes))

    # norep: pallas_call outputs don't carry varying-mesh-axis metadata
    wrapped = _shard_map_norep(
        step, mesh,
        (P(batch_axes, None), P(batch_axes), P(batch_axes, None)),
        (P(),) * (5 if moments else 2))
    return jax.jit(wrapped)


def sharded_cooc_step(mesh: Mesh, num_bins: int, num_classes: int,
                      interpret: bool = False, block_cols=None):
    """Data-sharded MXU co-occurrence count step (the round-3 count kernel
    under explicit SPMD): each device runs the Pallas XᵀX kernel
    (ops/pallas_hist.py) over its local rows — the per-device partial is
    the reference's combiner — and ONE ``psum`` over ``data`` plays the
    shuffle. G's layout (``pallas_hist.plan``/``w_index`` — fmaj for most
    shapes, jmaj fallback) is identical to the single-device kernel, so
    ``pallas_hist.counts_from_cooc`` reads the result out unchanged.

    ``interpret=True`` runs the kernel through the Pallas interpreter —
    how the CPU-mesh dryrun/tests attest the collective wiring without
    Mosaic hardware; on a TPU mesh leave it False."""
    from avenir_tpu.ops import pallas_hist

    def step(codes, labels):
        g = pallas_hist.cooc_counts.__wrapped__(
            codes, labels, num_bins, num_classes, interpret=interpret,
            block_cols=block_cols)
        return jax.lax.psum(g, "data")

    # norep: pallas_call outputs don't carry varying-mesh-axis metadata, so
    # the replication check cannot validate them
    wrapped = _shard_map_norep(step, mesh,
                               (P("data", None), P("data")), P())
    return jax.jit(wrapped)
