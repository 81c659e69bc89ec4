"""The row-sharded kNN index (models/knn.py::KNNModel.device_sharded) and the
fused search over it (parallel/collectives.py::sharded_knn_fused) against a
numpy brute force over the concatenated references; the one-chip index
(KNNModel.device_packed), packed on the device as the host packs; and the
one-chip route through the same body (models/knn.py::_nearest_neighbors_fused).

Four of the eight forced host devices, the Pallas kernels in Mosaic interpret
mode; ``_pallas_available`` is patched to say what it says on a TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from avenir_tpu.core.encoding import DatasetEncoder, EncodedDataset
from avenir_tpu.core.schema import FeatureSchema
from avenir_tpu.models import knn as mknn
from avenir_tpu.ops import pallas_knn as pk
from avenir_tpu.parallel import collectives
from avenir_tpu.parallel.mesh import make_mesh
from avenir_tpu.telemetry import spans as tel

SHARDS = 4


@pytest.fixture()
def mesh():
    return make_mesh(("data",), devices=jax.devices()[:SHARDS])


@pytest.fixture()
def on_tpu(monkeypatch):
    """The routing gate as it answers on a TPU, the kernels interpreted."""
    monkeypatch.setattr(
        mknn, "_pallas_available",
        lambda metric, k: (mknn.USE_PALLAS and metric == "euclidean"
                           and k + 1 <= pk.SLOTS))
    with pltpu.force_tpu_interpret_mode():
        yield


def _ds(codes, cont, nb=5):
    f, fc = codes.shape[1], cont.shape[1]
    return EncodedDataset(
        codes=codes, cont=cont, labels=np.zeros(len(codes), np.int32),
        ids=None, n_bins=np.full(f, nb, np.int32), class_values=["a"],
        binned_ordinals=list(range(f)), cont_ordinals=list(range(f, f + fc)))


def _random(rng, n, f, fc, nb=5, lo=0.0, hi=1.0):
    return (rng.integers(0, nb, size=(n, f)).astype(np.int32),
            rng.uniform(lo, hi, size=(n, fc)).astype(np.float32))


def _brute(model, test, k):
    """Exact top-k over all references on the train-range-normalised values,
    float64: (distances, indices, distance of the (k+1)-th)."""
    q = mknn._normalize01(test.cont, model.cont_lo, model.cont_hi)
    r = model.cont01()
    d2 = (test.codes[:, None, :] != model.codes[None]).sum(-1).astype(
        np.float64)
    for j in range(q.shape[1]):      # a column at a time: [M, N] stays small
        d2 += (q[:, j, None].astype(np.float64) - r[None, :, j]) ** 2
    order = np.argsort(d2, axis=1, kind="stable")[:, :k + 1]
    d = np.sqrt(np.take_along_axis(d2, order, 1)
                / (test.codes.shape[1] + q.shape[1]))
    return d[:, :k], order[:, :k], d[:, k]


def _same_neighbours(idx, want_idx, want_d, next_d):
    """Neighbour sets equal, but for a tie at the k-th place."""
    for row in range(idx.shape[0]):
        if next_d[row] - want_d[row, -1] > 1e-7:
            assert set(idx[row]) == set(want_idx[row]), row


def _search_span(records):
    return [r for r in records if r.name == "knn.search"][-1]


@pytest.fixture()
def recorder(tmp_path):
    """The program's spans, kept: a profiler session makes every site live."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    tel.tracer().recorded(clear=True)
    try:
        yield lambda: tel.tracer().recorded()
    finally:
        jax.profiler.stop_trace()


# -- the operand --------------------------------------------------------------

@pytest.mark.parametrize("n,n_real,f,fc", [
    (3000, 3000, 3, 4),         # one TB-row chunk, mostly pad rows
    (3000, 2500, 0, 9),         # pad rows among the rows given
    (40_000, 39_999, 2, 0),     # TB-rounded operand, three chunks of 16384
])
def test_device_pack_of_references_is_the_host_packs(rng, n, n_real, f, fc):
    nb = 5
    codes, cont = _random(rng, n, f, fc, nb)
    norm = mknn._row_norms(cont)
    got = jax.jit(pk.pack_refs_dev, static_argnames="num_bins")(
        jnp.asarray(codes), jnp.asarray(cont), jnp.asarray(norm),
        jnp.int32(n_real), num_bins=nb)
    want = pk._pack(codes[:n_real], cont[:n_real], nb, pk.operand_rows(n),
                    True, pk._PADC)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_sharded_index_holds_each_shards_own_host_pack(rng, mesh):
    """70 001 rows over 4 shards of 17 501: shard s holds rows
    [s*17501, (s+1)*17501), the last 3 fewer; its operand is prepare_refs of
    exactly those rows; placed once."""
    n, nb = 70_001, 5
    codes, cont = _random(rng, n, 2, 5, nb)
    model = mknn.fit_knn(_ds(codes, cont, nb))
    r_mat, codes_s, cont01_s, shard = model.device_sharded(mesh, nb)
    assert shard == 17_501 and model.sharded_index(mesh)[0] is r_mat
    assert model.device_sharded(mesh, nb)[0] is r_mat
    rows = pk.operand_rows(shard)
    assert r_mat.shape == (SHARDS * rows, pk._width(2, nb, 5))
    assert {s.data.shape for s in r_mat.addressable_shards} == {
        (rows, r_mat.shape[1])}
    got = np.asarray(r_mat, np.float32)
    for s in range(SHARDS):
        lo, hi = s * shard, min((s + 1) * shard, n)
        want, _n = pk.prepare_refs(codes[lo:hi], model.cont01()[lo:hi], nb)
        np.testing.assert_array_equal(got[s * rows:(s + 1) * rows],
                                      np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(cont01_s)[:n], model.cont01())
    np.testing.assert_array_equal(np.asarray(codes_s)[:n], codes)


def _host_pack_refused(*_a, **_k):
    raise AssertionError("a route called the host pack")


@pytest.mark.parametrize("n,f,fc,nb", [
    (2 * pk.TB, 0, 9, 1),       # the deployment's width, whole TB blocks
    (3000, 3, 4, 5),            # categorical codes, one block mostly pad
    (40_001, 2, 3, 7),          # n a multiple of neither TB nor the chunk
])
def test_one_chip_index_is_packed_on_the_device_as_the_host_packs(
        rng, monkeypatch, n, f, fc, nb):
    codes, cont = _random(rng, n, f, fc, nb)
    model = mknn.fit_knn(_ds(codes, cont, nb))
    host_pack = pk._pack
    monkeypatch.setattr(pk, "_pack", _host_pack_refused)
    r_mat, codes_r, cont01_r, n_real = model.device_packed(nb)
    assert n_real == n and r_mat.dtype == jnp.bfloat16
    assert r_mat.shape == (pk.operand_rows(n), pk._width(f, nb, fc))
    want = host_pack(codes, model.cont01(), nb, pk.operand_rows(n), True,
                     pk._PADC)
    np.testing.assert_array_equal(np.asarray(r_mat, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(codes_r), codes)
    np.testing.assert_array_equal(np.asarray(cont01_r), model.cont01())


def test_one_chip_search_never_takes_the_host_pack(rng, on_tpu, monkeypatch,
                                                   recorder):
    n, m, k = 20_000, 8, 5
    model = mknn.fit_knn(_ds(*_random(rng, n, 0, 9)))
    test = _ds(*_random(rng, m, 0, 9))
    monkeypatch.setattr(pk, "_pack", _host_pack_refused)
    d, idx = mknn.nearest_neighbors(model, test, k)
    assert _search_span(recorder()).attrs["path"] == "fused"
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)


def test_one_chip_operand_is_packed_from_the_rerank_arrays(rng, monkeypatch):
    """One upload: the operand is built from the very device arrays the
    exact re-rank gathers from, and the host gives only the norms."""
    model = mknn.fit_knn(_ds(*_random(rng, 5000, 2, 3)))
    read, pack = [], pk.pack_refs

    def spy(*args, **kwargs):
        read.append(args)
        return pack(*args, **kwargs)

    monkeypatch.setattr(pk, "pack_refs", spy)
    placed = model.device_packed(5)
    assert model.device_packed(5) is placed and len(read) == 1
    codes, cont01, norm, _nb = read[0]
    assert codes is placed[1] and cont01 is placed[2]
    np.testing.assert_array_equal(norm, mknn._row_norms(model.cont01()))


def test_one_chip_index_is_placed_once_under_one_span(rng, recorder):
    import threading

    n = 5000
    model = mknn.fit_knn(_ds(*_random(rng, n, 2, 3)))
    threads = [threading.Thread(target=model.device_packed, args=(5,))
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    model.device_packed(5)
    places = [r for r in recorder() if r.name == "knn.place"]
    assert len(places) == 1
    assert {a: places[0].attrs[a] for a in
            ("refs", "shards", "shard_rows", "operand_rows")} == {
        "refs": n, "shards": 1, "shard_rows": n,
        "operand_rows": pk.operand_rows(n)}


# -- the route ------------------------------------------------------------------

def test_sharded_route_off_a_tpu_is_the_scan(mesh):
    assert mknn.sharded_route(None, "euclidean", 10, 10**6) is None
    one = make_mesh(("data",), devices=jax.devices()[:1])
    assert mknn.sharded_route(one, "euclidean", 10, 10**6) is None
    assert mknn.sharded_route(mesh, "euclidean", 10, 10**6) == "sharded_scan"


@pytest.mark.parametrize("metric,k,refs,route", [
    ("euclidean", 10, 4 * (13 << 20), "sharded_fused"),   # the deployment
    ("euclidean", 10, 70_001, "sharded_fused"),
    ("manhattan", 10, 70_001, "sharded_scan"),
    ("euclidean", pk.SLOTS, 70_001, "sharded_scan"),      # k + 1 > SLOTS
    ("euclidean", 10, 39, "sharded_scan"),                # last shard: 9 rows
    ("euclidean", 10, 40, "sharded_scan"),      # 10 rows: k fits, no pool
    # shards of 16 385 rows, k + MARGIN = 18 candidates: the last shard's 8
    # segments give 16, one row more opens the ninth
    ("euclidean", 10, 65_539, "sharded_scan"),
    ("euclidean", 10, 65_540, "sharded_fused"),
])
def test_sharded_route_on_a_tpu(on_tpu, mesh, metric, k, refs, route):
    assert mknn.sharded_route(mesh, metric, k, refs) == route


def test_index_shard_rows_keep_whole_scan_tiles():
    assert mknn._index_shard_rows(4 * (13 << 20), 4) == 13 << 20
    assert mknn._index_shard_rows(70_001, 4) == 17_501
    assert mknn._index_shard_rows(4 * 65_536, 4) == 65_536
    assert mknn._index_shard_rows(4 * 65_536 + 1, 4) == 65_536 + 2048


# -- the search -------------------------------------------------------------------

@pytest.mark.parametrize("n,f,fc,k", [
    (70_001, 0, 9, 3),          # the deployment's attributes, N % 4 = 1
    (70_003, 3, 4, 5),          # categorical attributes too, N % 4 = 3
])
def test_sharded_fused_search_matches_brute_force_and_one_chip(
        rng, on_tpu, mesh, recorder, n, f, fc, k):
    m = 24
    model = mknn.fit_knn(_ds(*_random(rng, n, f, fc)))
    test = _ds(*_random(rng, m, f, fc))
    d, idx = mknn.nearest_neighbors(model, test, k, mesh=mesh)
    span = _search_span(recorder())
    assert span.attrs["path"] == "sharded_fused"
    assert span.attrs["shards"] == SHARDS
    assert span.attrs["kernel_rows"] == pk.query_rows(m) == 128
    assert span.attrs["rows"] == m
    assert len(span.attrs["refused_by_shard"]) == SHARDS
    assert span.attrs["refused"] <= sum(span.attrs["refused_by_shard"])
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)
    assert (idx >= 0).all() and (idx < n).all()
    # counted once, whatever the number of shards
    assert (model.fused_rows, model.tourney_rows, model.shard_fused_rows) \
        == (m, m, m)
    assert model.cert_fallback_rows == span.attrs["refused"]

    # the program itself, before any row is rescanned, beside the one-chip
    # fused search over the same rows: bit for bit where both certify
    nb = int(model.n_bins.max()) if f else 1
    r_mat, codes_s, cont01_s, shard = model.sharded_index(mesh)
    q01 = mknn._normalize01(test.cont, model.cont_lo, model.cont_hi)
    sd, si, scert, by_shard = (np.asarray(a) for a in
                               collectives.sharded_knn_fused(
        mesh, shard, num_bins=nb, total_attrs=f + fc,
        **pk.fused_statics(m, f, fc, k))(
            jnp.asarray(test.codes), jnp.asarray(q01), r_mat, codes_s,
            cont01_s, jnp.int32(n)))
    assert by_shard.tolist() == span.attrs["refused_by_shard"]
    one_mat, codes_r, cont01_r, _n = model.device_packed(nb)
    od, oi, ocert = (np.asarray(a) for a in pk.search_fused(
        test.codes, q01, one_mat, codes_r, cont01_r, n, nb, k, f + fc))
    both = scert & ocert
    assert both.sum() >= m // 2
    np.testing.assert_array_equal(sd[both], od[both])
    _same_neighbours(si[both], oi[both], want_d[both], next_d[both])


def _planted(rng, n, shard, near):
    """Uniform references far from the query (coordinates in [0.6, 1]) and
    the query at 0.1: ``near[s]`` rows of shard ``s`` are moved next to it,
    one a 2048-row segment, so that every shard's tournament certifies."""
    codes, cont = _random(rng, n, 0, 9, lo=0.6, hi=1.0)
    query = np.full((1, 9), 0.1, np.float32)
    for s, count in near.items():
        for j in range(count):
            cont[s * shard + j * pk.SEG + 7] = query[0] + 0.01 + 0.001 * j \
                + 0.0001 * s
    return codes, cont, query


def test_row_refused_on_exactly_one_shard_is_rescanned_and_counted_once(
        rng, on_tpu, mesh, recorder):
    n, k, shard = 70_001, 5, 17_501
    codes, cont, query = _planted(rng, n, shard, dict.fromkeys(range(4), k))
    # shard 2: three more copies of its nearest row INSIDE that row's
    # segment — a segment hiding three of the top k is what the
    # certificate exists to refuse
    cont[2 * shard + 8:2 * shard + 11] = cont[2 * shard + 7]
    model = mknn.fit_knn(_ds(codes, cont))
    test = _ds(np.zeros((1, 0), np.int32), query)
    d, idx = mknn.nearest_neighbors(model, test, k, mesh=mesh)
    records = recorder()
    span = _search_span(records)
    assert span.attrs["path"] == "sharded_fused"
    assert span.attrs["refused_by_shard"] == [0, 0, 1, 0]
    assert span.attrs["refused"] == 1
    fallback = [r for r in records if r.name == "knn.fallback"]
    assert len(fallback) == 1 and fallback[0].attrs["rows"] == 1
    assert (model.fused_rows, model.shard_fused_rows,
            model.cert_fallback_rows) == (1, 1, 1)
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)
    # the scan read the placed index: no further copy of the references
    assert "_dev_sharded" not in model.__dict__
    assert "_dev_tiles" not in model.__dict__


def test_true_neighbours_all_on_one_shard(rng, on_tpu, mesh, recorder):
    """... and shard 2 could not certify its OWN top-k (one of its segments
    hides three equal rows among them), yet hides nothing nearer than the
    merged k-th: the row is certified, and exact."""
    n, k, shard = 70_001, 5, 17_501
    codes, cont, query = _planted(rng, n, shard, {1: k})
    cont[2 * shard + 7:2 * shard + 10] = query[0] + 0.2
    model = mknn.fit_knn(_ds(codes, cont))
    test = _ds(np.zeros((1, 0), np.int32), query)
    q01 = mknn._normalize01(query, model.cont_lo, model.cont_hi)
    own = model.cont01()[2 * shard:3 * shard]
    _d, _i, own_cert = pk.search_fused(
        test.codes, q01, pk.prepare_refs(codes[:shard], own, 1)[0],
        jnp.asarray(codes[:shard]), jnp.asarray(own), shard, 1, k, 9)
    assert not np.asarray(own_cert)[0]
    d, idx = mknn.nearest_neighbors(model, test, k, mesh=mesh)
    span = _search_span(recorder())
    assert span.attrs["refused"] == 0
    assert span.attrs["refused_by_shard"] == [0, 0, 0, 0]
    want_d, want_idx, _next = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=1e-7)
    np.testing.assert_array_equal(idx, want_idx)
    assert ((idx >= shard) & (idx < 2 * shard)).all()


def test_k_beyond_a_shards_rows_takes_the_sharded_scan(rng, on_tpu, mesh,
                                                       recorder):
    n, k, m = 30, 10, 6                      # 8 rows a shard, the last 6
    model = mknn.fit_knn(_ds(*_random(rng, n, 2, 3)))
    test = _ds(*_random(rng, m, 2, 3))
    d, idx = mknn.nearest_neighbors(model, test, k, mesh=mesh)
    assert _search_span(recorder()).attrs["path"] == "sharded_scan"
    assert model.fused_rows == 0
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)


@pytest.mark.parametrize("n,k", [(1003, 10), (37, 4)])
def test_the_shards_merged_top_k_is_the_unsharded_top_k(rng, mesh, n, k):
    """Ties the shards to the whole: each shard's own top-k (numpy, over its
    rows alone, global indices, −1 past its rows), merged by the program's
    merge, is the top-k of one search over all the rows; a row is certified
    where its merged k-th is within every shard's limit."""
    m = 16
    shard = -(-n // SHARDS)
    # integer-valued squared distances: ties within and across shards
    d2 = rng.integers(0, 50, size=(m, n)).astype(np.float32)
    loc_d = np.full((SHARDS, m, k), np.inf, np.float32)
    loc_i = np.full((SHARDS, m, k), -1, np.int32)
    for s in range(SHARDS):
        mine = d2[:, s * shard:(s + 1) * shard]
        order = np.argsort(mine, axis=1, kind="stable")[:, :k]
        loc_d[s, :, :order.shape[1]] = np.take_along_axis(mine, order, 1)
        loc_i[s, :, :order.shape[1]] = order + s * shard
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want_d = np.take_along_axis(d2, order, 1)
    # each shard's limit: at, just under or well over the merged k-th
    limit = (want_d[None, :, -1]
             + rng.choice([-1.0, 0.0, 7.0], size=(SHARDS, m))).astype(
                 np.float32)

    def merge(d, i, lim):
        return collectives.merge_shard_topk(d[0], i[0], lim[0], k)

    by_shard = P("data", None, None)
    got_d, got_i, got_cert, refused = shard_map(
        merge, mesh=mesh, in_specs=(by_shard, by_shard, P("data", None)),
        out_specs=(P(), P(), P(), P()), check_vma=False)(
            jnp.asarray(loc_d), jnp.asarray(loc_i), jnp.asarray(limit))
    np.testing.assert_array_equal(got_d, want_d)
    np.testing.assert_array_equal(got_i, order)
    within = want_d[None, :, -1] <= limit
    np.testing.assert_array_equal(got_cert, within.all(axis=0))
    np.testing.assert_array_equal(refused, (~within).sum(axis=1))


# -- one chip -----------------------------------------------------------------

@pytest.mark.parametrize("n,path", [(12, "xla"), (16_384, "xla"),
                                    (16_385, "fused")])
def test_one_chip_route_by_index_size(rng, on_tpu, recorder, n, path):
    """k = 10 keeps 18 candidates, two a 2048-row segment: 8 segments cannot
    fill the pool, the ninth's first row can.  Below that the exact scan
    answers, in one tile."""
    k, m = 10, 6
    model = mknn.fit_knn(_ds(*_random(rng, n, 2, 3)))
    test = _ds(*_random(rng, m, 2, 3))
    d, idx = mknn.nearest_neighbors(model, test, k)
    span = _search_span(recorder())
    assert span.attrs["path"] == path
    fused = m if path == "fused" else 0
    assert (model.fused_rows, model.tourney_rows) == (fused, fused)
    assert model.shard_fused_rows == 0
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)


def test_row_refused_on_one_chip_is_rescanned_and_counted_once(
        rng, on_tpu, recorder):
    """The real kernel and certificate on one chip: a segment hiding three
    of the top k is refused, rescanned by the exact scan, counted once."""
    n, k = 40_000, 5
    codes, cont, query = _planted(rng, n, n, {0: k})
    cont[8:11] = cont[7]
    model = mknn.fit_knn(_ds(codes, cont))
    test = _ds(np.zeros((1, 0), np.int32), query)
    d, idx = mknn.nearest_neighbors(model, test, k)
    records = recorder()
    span = _search_span(records)
    assert span.attrs["path"] == "fused" and span.attrs["refused"] == 1
    assert "shards" not in span.attrs
    fallback = [r for r in records if r.name == "knn.fallback"]
    assert len(fallback) == 1 and fallback[0].attrs["rows"] == 1
    assert (model.fused_rows, model.tourney_rows, model.cert_fallback_rows,
            model.shard_fused_rows) == (1, 1, 1, 0)
    want_d, want_idx, next_d = _brute(model, test, k)
    np.testing.assert_allclose(d, want_d, atol=3e-6)
    _same_neighbours(idx, want_idx, want_d, next_d)


# -- serving ------------------------------------------------------------------

def test_warmup_places_the_sharded_index_and_requests_do_not(
        rng, on_tpu, mesh, recorder):
    from avenir_tpu.serving.registry import KNNServable

    schema = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        *({"name": f"x{j}", "ordinal": j, "dataType": "int", "feature": True}
          for j in range(1, 4)),
        {"name": "y", "ordinal": 4, "dataType": "categorical",
         "cardinality": ["P", "F"]}]})
    enc = DatasetEncoder(schema)
    n = 41_000          # 10 250 rows a shard: 6 segments fill k + MARGIN = 11
    train = EncodedDataset(
        codes=np.zeros((n, 0), np.int32),
        cont=rng.integers(0, 200, size=(n, 3)).astype(np.float32),
        labels=rng.integers(0, 2, size=n).astype(np.int32), ids=None,
        n_bins=np.zeros(0, np.int32), class_values=list(enc.class_values),
        binned_ordinals=[], cont_ordinals=[1, 2, 3])
    est = mknn.KNN(k=3, mesh=mesh)
    servable = KNNServable(est, est.fit(train), enc)
    servable.warmup(8)
    placed = [r for r in recorder() if r.name == "knn.place"]
    assert len(placed) == 1 and placed[0].attrs["shards"] == SHARDS
    assert servable.model.sharded_index(mesh) is not None
    tel.tracer().recorded(clear=True)
    lines = [f"u{i},{i},{2 * i},{3 * i}" for i in range(5)]
    replies = servable.score_lines(lines, 8)
    records = recorder()
    assert not [r for r in records if r.name == "knn.place"]
    assert _search_span(records).attrs["path"] == "sharded_fused"
    assert [r.rsplit(",", 1)[0] for r in replies] == lines
