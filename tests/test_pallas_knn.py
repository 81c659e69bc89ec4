"""Fused pallas kNN kernel (ops/pallas_knn.py) vs a numpy oracle.

Runs in Mosaic interpret mode on the CPU test mesh; the same code runs
compiled on the chip in the benchmark's cells (BENCHMARK.json, perfbench/)
and in chip_smoke.py's kNN phases."""

import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from avenir_tpu.ops import pallas_knn as pk

# condition-gated environment skip (CrossGraft triage of the long-standing
# tier-1 failures): these tests NEED pltpu.force_tpu_interpret_mode — the
# Mosaic-TPU interpreter entry added in jax 0.4.38 — and this container's
# jax (0.4.37) predates it; the fused kNN kernel has no other CPU
# interpreter path.  The skip self-heals: on a rig whose jax ships the
# API the whole module runs again, unchanged.
needs_tpu_interpret = pytest.mark.skipif(
    not hasattr(pltpu, "force_tpu_interpret_mode"),
    reason="jax.experimental.pallas.tpu.force_tpu_interpret_mode absent "
           "in this jax build (needs >= 0.4.38); the Mosaic kNN kernel "
           "cannot run off-TPU without it — environment-bound, "
           "auto-re-enabled on a jax that ships the API")


def _oracle(codes_q, cont_q, codes_r, cont_r, k):
    f = codes_q.shape[1] + cont_q.shape[1]
    ds, idxs = [], []
    for at in range(0, len(codes_q), 32):       # [32, N, f] at a time
        cq, xq = codes_q[at:at + 32], cont_q[at:at + 32]
        mism = (cq[:, None, :] != codes_r[None, :, :]).sum(-1).astype(np.float64)
        d2 = mism + ((xq[:, None, :] - cont_r[None, :, :]) ** 2).sum(-1)
        idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
        ds.append(np.sqrt(np.take_along_axis(d2, idx, axis=1) / f))
        idxs.append(idx)
    return np.concatenate(ds), np.concatenate(idxs)


# blocks that take each of the kernel's three query tiles: 128 rows, 256 rows,
# and two tiles of pk.TM
_TILE_WALK = [(24, 128), (200, 256), (600, 2 * pk.TM)]


def test_query_tile_follows_the_block():
    assert [pk.query_tile(m) for m in (1, 64, 128, 129, 256, 257, 512, 513,
                                       4096)] == \
        [128, 128, 128, 256, 256, pk.TM, pk.TM, pk.TM, pk.TM]
    assert [pk.query_rows(m) for m, _ in _TILE_WALK] == \
        [rows for _, rows in _TILE_WALK]
    assert pk.query_rows(4096) == 4096 and pk.query_rows(4097) == 9 * pk.TM
    # the kernel is handed whole tiles and takes its tile from them
    for m in (1, 64, 129, 257, 600, 4096):
        assert pk.query_tile(pk.query_rows(m)) == pk.query_tile(m)
        assert pk.fused_statics(m, 0, 9, 10)["rows"] == pk.query_rows(m)


def test_device_limb_split_rounds_with_reduce_precision(rng):
    """The device-side pack splits with ``lax.reduce_precision``: under jit
    the TPU compiler may drop an ``astype(bf16).astype(f32)`` round trip as
    excess precision — the low limbs then come back 0, d² is off by ~2⁻⁸
    and wrong rows were certified on the chip (PR 23).  XLA on the CPU keeps
    either spelling, so pin the op and its bit-equality with the host
    split."""
    import jax
    import jax.numpy as jnp

    v = (rng.random(size=(257, 9)) * 3).astype(np.float32)
    text = str(jax.make_jaxpr(pk._limbs_dev)(jnp.asarray(v)))
    assert text.count("reduce_precision") == 3 and "bf16" not in text
    for host, dev in zip(pk._limbs(v), jax.jit(pk._limbs_dev)(jnp.asarray(v))):
        np.testing.assert_array_equal(host, np.asarray(dev))


# every schema at the 128-row tile, the other two tiles on the mixed one
@pytest.mark.parametrize("f,fc,m,rows", [
    (f, fc, *_TILE_WALK[0]) for f, fc in [(5, 6), (6, 8), (4, 0), (0, 5)]
] + [(5, 6, *walk) for walk in _TILE_WALK[1:]])
@needs_tpu_interpret
def test_search_fused_matches_oracle(rng, f, fc, m, rows):
    # the PRODUCTION program (models/knn.py): one jitted dispatch running
    # device-side query pack -> tournament kernel -> device-side exact
    # re-rank, at a reference count the route sends here, with mixed,
    # categorical-only and continuous-only attributes, and over blocks that
    # take each of the kernel's query tiles
    import jax.numpy as jnp

    nb, k = 8, 5
    n = 70_000
    assert pk.query_rows(m) == rows
    codes_r = rng.integers(0, nb, size=(n, f)).astype(np.int32)
    cont_r = rng.random(size=(n, fc)).astype(np.float32)
    codes_q = rng.integers(0, nb, size=(m, f)).astype(np.int32)
    cont_q = rng.random(size=(m, fc)).astype(np.float32)
    assert pk.fused_serves(n, k)
    with pltpu.force_tpu_interpret_mode():
        r_mat, n_real = pk.prepare_refs(codes_r, cont_r, nb)
        assert r_mat.shape[0] == pk.operand_rows(n) == 5 * pk.TB
        d, i, cert = pk.search_fused(
            codes_q, cont_q, r_mat, jnp.asarray(codes_r),
            jnp.asarray(cont_r), n_real, nb, k, f + fc)
    d, i, cert = np.asarray(d), np.asarray(i), np.asarray(cert)
    od, oi = _oracle(codes_q, cont_q, codes_r, cont_r, k)
    assert cert.mean() > 0.9                # uniform data: failures are rare
    np.testing.assert_allclose(d[cert], od[cert], atol=2e-5)
    if fc:  # continuous features break distance ties; indices are unique
        assert (i[cert] == oi[cert]).mean() == 1.0


# every column-block count under two 512-row tiles, the short tiles at one
@pytest.mark.parametrize("nblocks,m", [
    (nblocks, _TILE_WALK[-1][1]) for nblocks in (3, 17, 33)
] + [(3, rows) for _, rows in _TILE_WALK[:-1]])
@needs_tpu_interpret
def test_tourney_keys_match_sorted_segments(rng, nblocks, m):
    # the raw kernel outputs, before the XLA assembly, against a sort of
    # every 2048-reference segment's keys: 1, 2 and 3 column blocks of 128
    # segments (the last two end in a partly filled block); one query tile
    # of 128 rows, one of 256 and two of 512. Operands are multiples of 1/8
    # below 2, so every partial sum of the dot is exact in f32 whatever the
    # order, and the key's truncation (1/16 at these magnitudes) still bites.
    import jax.numpy as jnp

    n, width = nblocks * pk.TB, 128
    a = rng.integers(0, 16, size=(m, width)).astype(np.float32) / 8
    b = rng.integers(0, 16, size=(n, width)).astype(np.float32) / 8
    with pltpu.force_tpu_interpret_mode():
        got = [np.asarray(x) for x in pk._tourney_keys(
            jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16))]
    nseg = n // pk.SEG
    assert all(g.shape == (m, pk._round_up(nseg, 128)) for g in got)
    col = np.arange(pk.SEG, dtype=np.int32)
    want = np.empty((3, m, nseg), np.int32)
    for s in range(nseg):
        d2 = a @ b[s * pk.SEG:(s + 1) * pk.SEG].T
        key = (d2.view(np.int32) & np.int32(~(pk.SEG - 1))) | col
        want[:, :, s] = np.sort(key, axis=1)[:, :3].T
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g[:, :nseg], w)
        assert (g[:, nseg:] == pk._PAD_KEY).all()


@pytest.mark.parametrize("m", [64, 200])
@needs_tpu_interpret
def test_tourney_keys_do_not_depend_on_the_tile(rng, m):
    # a row's keys do not depend on which rows share its tile, nor on the
    # tile's height: the block under its own tile (128 rows for 64, 256 for
    # 200) against the same rows padded by the caller to 512, which takes
    # the 512-row tile.  Random bf16 operands, inexact dots: bit for bit.
    import jax.numpy as jnp

    n, width = 3 * pk.TB, 128
    a = jnp.asarray(rng.random(size=(m, width)), jnp.bfloat16)
    b = jnp.asarray(rng.random(size=(n, width)) * 2, jnp.bfloat16)
    own, full = pk.query_rows(m), pk.TM
    assert pk.query_tile(own) == own < pk.query_tile(full) == full
    with pltpu.force_tpu_interpret_mode():
        small, large = ([np.asarray(x) for x in pk._tourney_keys(
            jnp.pad(a, ((0, rows - m), (0, 0))), b)] for rows in (own, full))
    assert [x.shape[0] for x in small] == [own] * 3
    for s_, l_ in zip(small, large):
        np.testing.assert_array_equal(s_[:m], l_[:m])
        assert (s_[:m, :n // pk.SEG] != pk._PAD_KEY).all()


@needs_tpu_interpret
def test_search_fused_is_bit_identical_across_the_three_tiles(rng):
    # the whole program on the same 64 rows handed over alone (128-row
    # tile), among 200 (256-row tile) and among 512 (the 512-row tile):
    # distances, indices and certificates bit for bit, refused rows included
    import jax.numpy as jnp

    f, fc, nb, k, n, m = 3, 6, 5, 5, 70_000, 64
    codes_r = rng.integers(0, nb, size=(n, f)).astype(np.int32)
    cont_r = rng.random(size=(n, fc)).astype(np.float32)
    codes_q = rng.integers(0, nb, size=(pk.TM, f)).astype(np.int32)
    cont_q = rng.random(size=(pk.TM, fc)).astype(np.float32)
    # six adjacent copies of a row share a segment, which then hides more
    # than two of the five nearest of a query equal to it: refused
    codes_r[:96], cont_r[:96] = (np.repeat(x[:16], 6, axis=0)
                                 for x in (codes_q, cont_q))
    got = []
    with pltpu.force_tpu_interpret_mode():
        r_mat, n_real = pk.prepare_refs(codes_r, cont_r, nb)
        for rows in (m, 200, pk.TM):
            assert pk.query_tile(rows) == pk.query_rows(rows)
            got.append([np.asarray(x)[:m] for x in pk.search_fused(
                codes_q[:rows], cont_q[:rows], r_mat, jnp.asarray(codes_r),
                jnp.asarray(cont_r), n_real, nb, k, f + fc)])
    cert = got[0][2]
    assert cert.any() and (~cert).any()
    for other in got[1:]:
        for x, y in zip(got[0], other):
            np.testing.assert_array_equal(x, y)


# positions within a 2048-reference segment whose distances are planted
# below everything else, and the multiples (hi, lo) of the query's scale they
# get.  The kernel merges the keys of a segment in the order the MXU delivers
# them — 8-row groups, the two halves of a 16-row push, neighbouring pushes,
# and last the 8 sublanes — so each case puts the three smallest where one
# of those stages has to keep or to order them.
_PLANTED = {
    # all three in one 128-reference column block
    "one_column_block": [(5 * 128 + 3, 1.0, 0), (5 * 128 + 64, 1.5, 0),
                         (5 * 128 + 100, 1.25, 0)],
    # first, a middle and the last column block: arrival order != key order
    "first_middle_last": [(7, 1.5, 0), (1024 + 13, 1.0, 0), (2047, 1.25, 0)],
    # one 8-row group: three sublanes of one result vreg
    "one_row_group": [(16, 1.25, 0), (19, 1.5, 0), (23, 1.0, 0)],
    # one sublane (rows = 0 mod 8) of three far-apart row groups
    "one_sublane": [(8, 1.5, 0), (8 + 16 * 5, 1.0, 0), (8 + 1024, 1.25, 0)],
    # both halves of one 16-row push
    "push_halves": [(32, 1.25, 0), (40, 1.0, 0), (33, 1.5, 0)],
    # six distances equal after the key's truncation (1 + j * 2^-20 all
    # truncate to 1.0): distinct only by column bits, smallest columns win
    "equal_truncated": [(2000, 1.0, 1 * 2.0 ** -20), (5, 1.0, 6 * 2.0 ** -20),
                        (900, 1.0, 2 * 2.0 ** -20), (64, 1.0, 5 * 2.0 ** -20),
                        (1300, 1.0, 3 * 2.0 ** -20), (129, 1.0, 4 * 2.0 ** -20)],
    # d2 <= 0: the clamp max(d2, 0) catches four of them and the exact zero
    # needs none; all five keys are the bare column (a denormal, as a float)
    "clamped": [(1999, -1.0, 0), (3, -0.5, 0), (700, 0.0, 0), (1100, -2.0, 0),
                (250, -0.25, 0)],
}


@pytest.fixture(scope="module")
def tourney_keys_jit():
    import jax
    return jax.jit(pk._tourney_keys)


@pytest.mark.parametrize("case", sorted(_PLANTED))
@needs_tpu_interpret
def test_tourney_keys_planted_segments(rng, tourney_keys_jit, case):
    # two query tiles x two reference blocks (16 segments); d2 = c_q * (hi +
    # lo) exactly: operand column 0 carries hi, column 1 lo, and a query is
    # (c_q, c_q, 0, ...) with c_q a power of two.  The two query tiles get
    # different scales and every segment a rotation of the planted values, so
    # an output map or transpose that drops the tile or the block index fails.
    import jax.numpy as jnp

    m, nseg, width = 2 * pk.TM, 2 * pk.TB // pk.SEG, 128
    n = nseg * pk.SEG
    hi = 4 + rng.integers(0, 64, size=n).astype(np.float32) / 8
    lo = np.zeros(n, np.float32)
    plant = _PLANTED[case]
    for s in range(nseg):
        for j, (pos, _, _) in enumerate(plant):
            _, h, l = plant[(j + s) % len(plant)]
            hi[s * pk.SEG + pos], lo[s * pk.SEG + pos] = h, l
    scale = np.where(np.arange(m) < pk.TM, 1.0, 2.0).astype(np.float32)
    scale[::3] *= 0.5
    a = np.zeros((m, width), np.float32)
    b = np.zeros((n, width), np.float32)
    a[:, 0] = a[:, 1] = scale
    b[:, 0], b[:, 1] = hi, lo
    with pltpu.force_tpu_interpret_mode():
        got = [np.asarray(x) for x in tourney_keys_jit(
            jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16))]
    d2 = np.maximum(scale[:, None] * (hi + lo)[None, :], np.float32(0))
    assert d2.dtype == np.float32
    key = ((d2.view(np.int32) & np.int32(~(pk.SEG - 1)))
           | np.tile(np.arange(pk.SEG, dtype=np.int32), nseg))
    want = np.sort(key.reshape(m, nseg, pk.SEG), axis=2)[:, :, :3]
    if case in ("equal_truncated", "clamped"):      # the case is what it says
        assert (want[:, 0, :] >> 11 == want[:, 0, :1] >> 11).all()
    for j, g in enumerate(got):
        np.testing.assert_array_equal(g[:, :nseg], want[:, :, j])
        assert (g[:, nseg:] == pk._PAD_KEY).all()


@needs_tpu_interpret
def test_search_fused_block2_short_last_block_not_falsely_certified(rng):
    # regression: n_real = TB+1 — the smallest index fused_serves admits at
    # k = 10 — puts one real ref in the last block, so a pad lands in the
    # candidate pool; that must NOT certify rows (a pad among the candidates
    # proves nothing — blocks still hide non-candidates). Exactness comes
    # from the fallback.
    import jax.numpy as jnp

    f, fc, nb, k = 4, 3, 6, 10
    n = pk.TB + 1
    m = 16
    codes_r = rng.integers(0, nb, size=(n, f)).astype(np.int32)
    cont_r = rng.random(size=(n, fc)).astype(np.float32)
    codes_q = rng.integers(0, nb, size=(m, f)).astype(np.int32)
    cont_q = rng.random(size=(m, fc)).astype(np.float32)
    with pltpu.force_tpu_interpret_mode():
        r_mat, n_real = pk.prepare_refs(codes_r, cont_r, nb)
        assert pk.fused_serves(n_real, k) and r_mat.shape[0] % pk.TB == 0
        d, i, cert = pk.search_fused(
            codes_q, cont_q, r_mat, jnp.asarray(codes_r),
            jnp.asarray(cont_r), n_real, nb, k, f + fc)
    cert = np.asarray(cert)
    od, oi = _oracle(codes_q, cont_q, codes_r, cont_r, k)
    # with only 18 candidates over 16k+ refs nothing should certify; any
    # certified row MUST actually be exact
    ok = cert
    if ok.any():
        np.testing.assert_allclose(np.asarray(d)[ok], od[ok], atol=2e-5)
    assert (~cert).any()


@needs_tpu_interpret
def test_search_fused_block2_heavy_ties_and_duplicates(rng):
    # adversarial for the block top-2 sweep: many duplicated reference rows
    # (ties across and within blocks) — certified rows must still be exact
    import jax.numpy as jnp

    f, fc, nb, k = 4, 2, 5, 5
    base = rng.integers(0, nb, size=(500, f)).astype(np.int32)
    codes_r = np.tile(base, (160, 1))[:70_000]          # heavy duplication
    cont_base = rng.random(size=(500, fc)).astype(np.float32)
    cont_r = np.tile(cont_base, (160, 1))[:70_000]
    m = 16
    codes_q = rng.integers(0, nb, size=(m, f)).astype(np.int32)
    cont_q = rng.random(size=(m, fc)).astype(np.float32)
    with pltpu.force_tpu_interpret_mode():
        r_mat, n_real = pk.prepare_refs(codes_r, cont_r, nb)
        assert pk.fused_serves(n_real, k) and r_mat.shape[0] % pk.TB == 0
        d, i, cert = pk.search_fused(
            codes_q, cont_q, r_mat, jnp.asarray(codes_r),
            jnp.asarray(cont_r), n_real, nb, k, f + fc)
    d, cert = np.asarray(d), np.asarray(cert)
    od, _ = _oracle(codes_q, cont_q, codes_r, cont_r, k)
    # distances (not indices — ties) must match the oracle on certified rows
    np.testing.assert_allclose(d[cert], od[cert], atol=2e-5)
    # non-vacuity: with massive duplication the k-th and (k+1)-th distances
    # tie, so the bound-based certificate must actually refuse some rows —
    # the fallback (exercised at the model level) covers them
    assert (~cert).any()
    from avenir_tpu.core.encoding import EncodedDataset
    from avenir_tpu.models import knn as mknn
    model = mknn.fit_knn(EncodedDataset(
        codes=codes_r, cont=cont_r,
        labels=np.zeros(len(codes_r), np.int32), ids=None,
        n_bins=np.full(f, nb, np.int32), class_values=["a"],
        binned_ordinals=list(range(f)),
        cont_ordinals=list(range(f, f + fc))))
    test = EncodedDataset(
        codes=codes_q, cont=cont_q, labels=None, ids=None,
        n_bins=np.full(f, nb, np.int32), class_values=["a"],
        binned_ordinals=list(range(f)),
        cont_ordinals=list(range(f, f + fc)))
    with pltpu.force_tpu_interpret_mode():
        dm, _ = mknn.nearest_neighbors(model, test, k=k)
    # model-level oracle over the TRAIN-range-normalized continuous values
    on, _ = _oracle(codes_q,
                    mknn._normalize01(cont_q, model.cont_lo, model.cont_hi),
                    codes_r,
                    mknn._normalize01(cont_r, model.cont_lo, model.cont_hi),
                    k)
    np.testing.assert_allclose(dm, on, atol=2e-5)   # every row exact
