"""The main path's kernels, compiled for a DESCRIBED v5e at real widths.

The TPU's compiler is installed wherever JAX's TPU library is, and compiles
for a chip that is described, not attached: what Mosaic refuses on the
machine with the chip (a slice not aligned to the tiling, too much VMEM, a
kernel that cannot be partitioned) it refuses here, at no chip time.  A
compile that passes is not a chip run and proves nothing about results or
times — ``chip_smoke.py`` is the run.

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture of THIS file and nowhere else —
never at import, in a ``skipif``, in ``parametrize`` or in ``conftest.py``
(the TPU library goes to one process at a time, so a module that loads it
while it is imported gives xdist's workers different collections and the
run executes no test); not ``autouse``; every compile happens in the test's
own process; JAX's persistent compilation cache is off around them (an
entry compiled for a described chip cannot be read back without one).  All
cases live in this one file so one worker owns the library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


ROWS = 1 << 20          # one stream chunk of the smoke's 4 M-row file


@pytest.mark.parametrize("f,b,c,mode", [
    (11, 12, 2, "fmaj"),        # hospital readmission, the flagship
    (100, 20, 2, "clsb"),       # wide schema, blocked per-class tier
])
def test_cooc_gram_compiles_for_v5e(one_chip, f, b, c, mode):
    from avenir_tpu.ops import pallas_hist

    assert pallas_hist.plan(f, b, c)[0] == mode
    compiled = pallas_hist.cooc_counts_cols.lower(
        _shape((f, ROWS), jnp.int32, one_chip),
        _shape((ROWS,), jnp.int32, one_chip), b, c).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tree_level_cross_gram_compiles_for_v5e(one_chip):
    """DecisionTreeBuilder's level table on retarget data at depth 4:
    2 features x 9 bins against 8 frontier nodes x 2 classes."""
    from avenir_tpu.ops import pallas_hist

    f, b, sel = 2, 9, 8 * 2
    assert pallas_hist.cross_applicable(f, b, sel)
    compiled = pallas_hist.cross_cooc_counts_cols.lower(
        _shape((f, ROWS), jnp.int32, one_chip),
        _shape((ROWS,), jnp.int32, one_chip), b, sel).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_shared_scan_gram_moments_compiles_for_v5e(one_chip):
    """The fused SharedScan step: flagship gram + class moments of two
    continuous columns in one program."""
    from avenir_tpu.ops import pallas_hist

    compiled = pallas_hist.gram_moments.lower(
        _shape((ROWS, 11), jnp.int32, one_chip),
        _shape((ROWS,), jnp.int32, one_chip),
        _shape((ROWS, 2), jnp.float32, one_chip), 12, 2).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,m,rows,f,bins,fc", [
    (1_000_000, 4096, 4096, 0, 1, 9),
    # the benchmark's cells (perfbench/configs): a bulk block, and the serve
    # cell's largest bucket, 64 rows swept as one 128-row query tile
    (13 << 20, 64, 128, 0, 1, 9),
    (13 << 20, 200, 256, 0, 1, 9),  # the 256-row tile
    (13 << 20, 4096, 4096, 0, 1, 9),
    (1 << 24, 4096, 4096, 0, 1, 9), # refused until PR 28: 104.25M of scoped VMEM
    # a wide schema, packed width 256: two MXU passes a segment and a
    # reference block of 16384 x 256 bf16, which the kernel's
    # vmem_limit_bytes has to go on admitting
    (1 << 20, 4096, 4096, 6, 32, 8),
], ids=["tournament", "tournament-13Mi-serve", "tournament-13Mi-tile256",
        "tournament-13Mi", "tournament-16Mi", "tournament-wide256"])
def test_knn_fused_search_compiles_for_v5e(one_chip, n, m, rows, f, bins, fc):
    """elearn-shaped references (9 continuous attributes, packed width 128),
    and one schema with categorical attributes, x a block of queries through
    the whole fused search program."""
    from avenir_tpu.ops import pallas_knn as pk

    k = 10
    npad = pk.operand_rows(n)
    width = pk._width(f, bins, fc)
    assert width == (256 if f else 128) and pk.fused_serves(n, k)
    statics = pk.fused_statics(m, f, fc, k)
    assert statics["rows"] == pk.query_rows(m) == rows
    compiled = pk._search_fused.lower(
        _shape((m, f), jnp.int32, one_chip),
        _shape((m, fc), jnp.float32, one_chip),
        _shape((npad, width), jnp.bfloat16, one_chip),
        _shape((n, f), jnp.int32, one_chip),
        _shape((n, fc), jnp.float32, one_chip),
        _shape((), jnp.int32, one_chip),
        num_bins=bins, total_attrs=f + fc, **statics).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's outputs are [segments, rows]: a block of 64 is swept as
    # one 128-row tile and written as 128-row output blocks, not 512
    assert f"s32[{npad // pk.SEG},{rows}]" in text
    # the query pack's bf16 limb split must reach the chip as roundings the
    # compiler cannot drop: 3 limbs each of the coordinates and the norm
    assert text.count("reduce-precision(") >= 6


def test_sharded_scan_step_compiles_for_four_v5e_chips(topo):
    """The ShardGraft dispatch on the 2x2 host: the kernel AND the
    all-reduce must both be in the program, compiled (not interpreted)."""
    from avenir_tpu.parallel import collectives

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    assert mesh.shape["data"] == 4
    rows = NamedSharding(mesh, P("data"))
    step = collectives.sharded_scan_step(mesh, 12, 2, interpret=False,
                                         moments=False)
    compiled = step.lower(
        _shape((ROWS, 11), jnp.int32, NamedSharding(mesh, P("data", None))),
        _shape((ROWS,), jnp.int32, rows),
        _shape((ROWS, 0), jnp.float32,
               NamedSharding(mesh, P("data", None)))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def test_sharded_fused_knn_compiles_for_four_v5e_chips(topo):
    """The benchmark's four-chip cell (perfbench/configs/elearn_knn_x4.json):
    13 x 2^20 elearn-shaped references a shard x 4096 queries through the
    whole sharded program — the fused search on every shard and the
    all-gather merge — and the program that packs a shard's operand on the
    chip that holds it, inside one chip's memory."""
    from avenir_tpu.ops import pallas_knn as pk
    from avenir_tpu.parallel import collectives

    mesh = Mesh(np.asarray(topo.devices), ("data",))
    shard, m, fc, k = 13 << 20, 4096, 9, 10
    n = mesh.shape["data"] * shard
    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    index = (_shape((n, 0), jnp.int32, rows),
             _shape((n, fc), jnp.float32, rows))
    compiled = collectives.sharded_knn_fused(
        mesh, shard, num_bins=1, total_attrs=fc,
        **pk.fused_statics(m, 0, fc, k)).lower(
            _shape((m, 0), jnp.int32, whole),
            _shape((m, fc), jnp.float32, whole),
            _shape((n, pk._width(0, 1, fc)), jnp.bfloat16, rows), *index,
            _shape((), jnp.int32, whole)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    assert text.count("reduce-precision(") >= 6
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8e9
    packed = collectives.sharded_knn_pack(mesh, 1).lower(
        *index, _shape((n,), jnp.float32, NamedSharding(mesh, P("data"))),
        _shape((), jnp.int32, whole)).compile()
    assert "all-" not in packed.as_text()       # every shard packs alone
    mem = packed.memory_analysis()
    assert mem.output_size_in_bytes == shard * 128 * 2      # bf16, one shard
    assert mem.temp_size_in_bytes < 1e9


def test_one_chip_knn_pack_compiles_for_v5e(one_chip):
    """The one-chip cells' placement (perfbench/configs/elearn_knn.json):
    13 x 2^20 elearn-shaped references packed on the chip that holds them
    (models/knn.py::KNNModel.device_packed), in place.  Its limb split must
    reach the compiled program as reduce-precision: the TPU compiler drops
    an ``astype`` round trip as excess precision (PERF.md, PR 23,
    Finding 7)."""
    from avenir_tpu.ops import pallas_knn as pk

    n, fc = 13 << 20, 9
    compiled = pk.pack_refs.lower(
        _shape((n, 0), jnp.int32, one_chip),
        _shape((n, fc), jnp.float32, one_chip),
        _shape((n,), jnp.float32, one_chip), num_bins=1).compile()
    assert compiled.as_text().count("reduce-precision(") >= 6
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * pk._width(0, 1, fc) * 2
    assert mem.temp_size_in_bytes < 1e9
