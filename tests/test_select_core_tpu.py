"""The select-while-scanning kernel compiles for the chip at the benchmark's
real geometries (ISSUE 26; tier-1, no chip: the TPU compiler is installed
here and compiles for a v5e that is described, not attached). What interpret
mode cannot show — tiling, VMEM, what Mosaic lowers — at no chip time. A
compile that passes is not a chip run. The topology is described inside a
fixture, never at import (one process at a time may load libtpu)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from lazzaro_tpu.core import state as S
from lazzaro_tpu.ops import pallas_topk as PT
from lazzaro_tpu.utils.batching import REQUEST_COLS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,batch,dtype", [
    (135_168, 24, jnp.bfloat16),        # share131k, a mid batch bucket
    (5_001_216, 64, jnp.bfloat16),      # lme5m, the full batch
    (5_001_216, 8, jnp.bfloat16),       # lme5m, the lone dispatch
    (1_048_576, 128, jnp.float32),      # an f32 arena, the widest call
])
def test_kernel_compiles_for_v5e(one_chip, rows, batch, dtype):
    d, k = 768, 128
    item = jnp.dtype(dtype).itemsize
    block = PT.select_block_rows(rows, d, item)
    assert PT.block_tiles(rows, d, item)
    c = -(-batch // 16) * 16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def core(emb, qn, rm, rg, t, kc, kmax):
        return PT._scan_pallas(emb, qn, rm, rg, t, kc, kmax, kp=k,
                               block=block, sentinel=rows - 1,
                               interpret=False)

    comp = jax.jit(core).lower(
        sds((rows, d), dtype), sds((c, d), dtype), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), sds((c, 1), jnp.int32),
        sds((c, 1), jnp.int32), sds((), jnp.int32)).compile()
    text = comp.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[{c},{rows}]" not in text


def test_pod_exact_program_compiles_for_four_chips(topo, monkeypatch):
    """``make_fused_sharded``'s exact mode on a 2x2 mesh at 20M rows (each
    chip's slice is `lme5m`'s pool): the kernel sits inside the
    ``shard_map`` and the candidate merge is the one ``all_gather``. The
    program asks ``on_tpu()`` which vehicle to take; here the test says."""
    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    n, d, c, edges = 4 * 1221 * 4096, 768, 64, 4096

    def sds(shape, dt, spec=None):
        spec = spec if spec is not None else P(*([None] * len(shape)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    st = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype,
                      P("data", None) if a.ndim == 2 else P("data")),
        jax.eval_shape(lambda: S.init_arena(n - 1, d, jnp.bfloat16)))
    kern = S.make_fused_sharded(mesh, "data", k=128, cap_take=5, max_nbr=8,
                                mode="exact")
    text = kern.read.lower(
        st, (), sds((4, n // 4 + 1), jnp.int32, P("data", None)),
        sds((4, edges), jnp.int32, P("data", None)),
        sds((c, d + REQUEST_COLS), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    assert f"f32[{c},{n // 4}]" not in text


def test_pod_arena_is_constructed_in_its_shards_for_four_chips(topo):
    """PR 29: ``lme20m-mesh4``'s arena (20,004,864 rows x 768 bf16 = 30.7 GB,
    more than a chip) as ``MemoryIndex(mesh=...)`` now creates it: the compiled
    program leaves every chip its quarter, 7.68 GB + the columns, holds no
    whole column and has no collective."""
    import re

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    n, d = 4 * 1221 * 4096, 768
    comp = S.sharded_init(lambda c: S.init_arena(c, d, jnp.bfloat16), n - 1,
                          mesh, "data").lower().compile()
    text = comp.as_text()
    assert not re.search(r"all-gather|all-reduce|collective-permute|all-to-all",
                         text)
    assert f"[{n // 4},{d}]" in text and f"[{n}," not in text
    assert f"[{n}]" not in text
    mem = comp.memory_analysis()
    shard = (n // 4) * (d * 2 + 4 * 7 + 2)        # emb + seven words + two flags
    assert shard <= mem.output_size_in_bytes <= 1.01 * shard < 8e9
    assert mem.temp_size_in_bytes < 64 * 2**20


# ------------------------------------------- the int8 coarse scan (ISSUE 36)

@pytest.mark.parametrize("rows,batch", [
    (5_001_216, 64),        # lme5m-int8, the full batch
    (5_001_216, 8),         # lme5m-int8, the lone dispatch (padded to 32)
    (135_168, 24),          # share131k's arena with int8_serving on
])
def test_int8_kernel_compiles_for_v5e(one_chip, rows, batch):
    d, k_fetch, g_fetch = 768, 136, 9
    block = PT.q8_block_rows(rows, d)
    assert PT.q8_block_tiles(rows, d) and block == 12288
    c = -(-batch // PT._Q8_QUERY_TILE) * PT._Q8_QUERY_TILE

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def core(q8a, scale, qq, qs, rm, rg, t):
        return PT._scan_q8_pallas(q8a, scale, qq, qs, rm, rg, t, k_fetch,
                                  g_fetch, kp=256, gp=128, block=block,
                                  sentinel=rows - 1, interpret=False)

    comp = jax.jit(core).lower(
        sds((rows, d), jnp.int8), sds((rows,), jnp.float32),
        sds((c, d), jnp.int8), sds((c, 1), jnp.float32),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((c, 1), jnp.int32)).compile()
    text = comp.as_text()
    assert "tpu_custom_call" in text and "lz_select_scan_q8" in text
    assert f"[{c},{rows}]" not in text


def test_int8_serving_program_compiles_for_v5e_beside_the_master(
        one_chip, monkeypatch):
    """``search_fused_quant_ragged_read`` at ``lme5m-int8``'s geometry: the
    kernel, the rescore's gather and the tail as ONE program whose operands
    are the 11.5 GB a chip holds resident (master, codes, scales, columns)
    and whose temporaries are megabytes — no ``[batch, rows]`` tile, no sort
    at the arena's width."""
    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    rows, d, c = 5_001_216, 768, 64

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    st = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: S.init_arena(rows - 1, d, jnp.bfloat16)))
    comp = S.search_fused_quant_ragged_read.lower(
        st, sds((rows, d), jnp.int8), sds((rows,), jnp.float32),
        sds((rows + 1,), jnp.int32), sds((8192,), jnp.int32),
        sds((c, d + REQUEST_COLS), jnp.int32),
        k=128, slack=8, cap_take=5, max_nbr=8).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"[{c},{rows}]" not in text
    mem = comp.memory_analysis()
    assert 11.4e9 < mem.argument_size_in_bytes < 11.7e9
    assert mem.temp_size_in_bytes < 64 * 2**20


def test_pod_int8_program_compiles_for_four_chips(topo, monkeypatch):
    """``make_fused_sharded``'s quant mode on a 2x2 mesh at 10M rows (each
    chip holds 2.5M rows of master AND codes, 5.8 GB): the shard-local
    coarse scan is the same kernel inside the ``shard_map``, the rescore
    gathers local rows, and the candidate merge is the ``all_gather``."""
    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    n, d, c, edges = 4 * 612 * 4096, 768, 64, 4096

    def sds(shape, dt, spec=None):
        spec = spec if spec is not None else P(*([None] * len(shape)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    st = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype,
                      P("data", None) if a.ndim == 2 else P("data")),
        jax.eval_shape(lambda: S.init_arena(n - 1, d, jnp.bfloat16)))
    kern = S.make_fused_sharded(mesh, "data", k=128, cap_take=5, max_nbr=8,
                                mode="quant", slack=8)
    comp = kern.read.lower(
        st, (sds((n, d), jnp.int8, P("data", None)),
             sds((n,), jnp.float32, P("data"))),
        sds((4, n // 4 + 1), jnp.int32, P("data", None)),
        sds((4, edges), jnp.int32, P("data", None)),
        sds((c, d + REQUEST_COLS), jnp.int32)).compile()
    text = comp.as_text()
    assert "lz_select_scan_q8" in text and "all-gather" in text
    assert f"[{c},{n // 4}]" not in text
    assert comp.memory_analysis().temp_size_in_bytes < 64 * 2**20


# --------------------------------------- the write path's link scan (ISSUE 45)

def _described_ingest(one_chip, rows, b, edges=1_048_575):
    """``ingest_dedup_fused``'s operands at ``rows`` x 768 bf16, described."""
    d, k = 768, 3

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    st, es = (jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                     jax.eval_shape(make))
              for make in (lambda: S.init_arena(rows - 1, d, jnp.bfloat16),
                           lambda: S.init_edges(edges)))
    i32, f32 = jnp.int32, jnp.float32
    per_fact = (sds((b,), i32), sds((b, d), f32), sds((b,), f32),
                sds((b,), f32), sds((b,), i32), sds((b,), i32),
                sds((b,), i32), sds((b,), jnp.bool_), sds((b,), i32),
                sds((b,), i32), sds((2 * b * k + 1,), i32))
    scalars = (sds((), i32), sds((), f32), sds((), i32)) + (sds((), f32),) * 5
    return (st, es, None, None, None, None) + per_fact + scalars


@pytest.mark.parametrize("rows,b", [
    (5_001_216, 128),       # lme5m-live: a 100-fact conversation
    (135_168, 128),         # share131k's arena: 33 blocks
    (5_001_216, 256),       # a batch the scan takes in two pieces
])
def test_fused_ingest_compiles_for_v5e_and_holds_no_score_tile(
        one_chip, monkeypatch, rows, b):
    """The write-path twin of
    ``test_compiled_serving_program_holds_no_score_tile``, at the cells'
    real shapes: ``ingest_dedup_fused`` (modes (1, 0), k = 3) lowers for a
    described v5e with ``lz_link_scan`` as its one Mosaic kernel and no
    ``[facts, rows]`` buffer of any type; what it holds besides the donated
    arena is megabytes."""
    import re

    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    comp = S.ingest_dedup_fused.lower(
        *_described_ingest(one_chip, rows, b), k=3,
        shard_modes=(1, 0)).compile()
    text = comp.as_text()
    assert text.count("tpu_custom_call") == 1 and "lz_link_scan" in text
    for facts in {b, min(b, PT._MAX_QUERIES)}:
        assert not re.search(rf"\[{facts},{rows}\]|\[{rows},{facts}\]", text)
    assert comp.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("rows,c,want", [
    (135_168, 8, "fb68f94e94b78515"), (135_168, 64, "c7db72de159e6899"),
    (5_001_216, 8, "aa1567dae3196e6a"), (5_001_216, 64, "b776dfd99eafd386"),
])
def test_serving_kernel_is_the_one_pr_44_left(one_chip, monkeypatch, rows, c,
                                              want):
    """``lz_select_scan`` as the chip's compiler reads it, source locations
    stripped (``scripts/exact_stablehlo.py``'s last column, frozen from
    commit d42b5f3): the write path's kernel shares its step functions, and
    nothing of the serving programs' Mosaic modules may move with it."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "exact_stablehlo", os.path.join(os.path.dirname(__file__), os.pardir,
                                        "scripts", "exact_stablehlo.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    monkeypatch.setattr(PT, "on_tpu", lambda: True)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    st = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: S.init_arena(rows - 1, 768, jnp.bfloat16)))
    text = S.search_fused_ragged_read.lower(
        st, sds((rows + 1,), jnp.int32), sds((8192,), jnp.int32),
        sds((c, 768 + REQUEST_COLS), jnp.int32),
        k=128, cap_take=5, max_nbr=8).as_text()
    count, kernels = script.mosaic_hash(text)
    assert count == 1 and kernels.startswith(want)


def test_pod_ingest_program_compiles_for_four_chips(topo, monkeypatch):
    """``make_ingest_fused_sharded``'s dedup program on a 2x2 mesh at 20M
    rows (each chip's slice is ``lme5m``'s pool): the link scan's kernel
    sits inside the ``shard_map``, the candidate merge is the one
    ``all_gather``, and no chip holds a ``[facts, its rows]`` buffer."""
    monkeypatch.setattr(PT, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    n, d, b, k, edges = 4 * 1221 * 4096, 768, 128, 3, 4 * 262_144

    def sds(shape, dt, spec=None):
        spec = spec if spec is not None else P(*([None] * len(shape)))
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    def rows_of(make):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype,
                          P("data", None) if a.ndim == 2 else P("data")),
            jax.eval_shape(make))

    i32, f32 = jnp.int32, jnp.float32
    per_fact = (sds((b,), i32), sds((b, d), f32), sds((b,), f32),
                sds((b,), f32), sds((b,), i32), sds((b,), i32),
                sds((b,), i32), sds((b,), jnp.bool_), sds((b,), i32),
                sds((b,), i32), sds((2 * b * k + 1,), i32))
    scalars = (sds((), i32), sds((), f32), sds((), i32)) + (sds((), f32),) * 5
    kern = S.make_ingest_fused_sharded(mesh, "data", k=k, shard_modes=(1, 0))
    text = kern.ingest.lower(
        rows_of(lambda: S.init_arena(n - 1, d, jnp.bfloat16)),
        rows_of(lambda: S.init_edges(edges - 1)), *per_fact,
        *scalars).compile().as_text()
    assert "lz_link_scan" in text and "all-gather" in text
    assert f"[{b},{n // 4}]" not in text and f"[{n // 4},{b}]" not in text
