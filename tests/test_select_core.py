"""The blocked select-while-scanning exact core (ISSUE 26; tier-1, CPU).

``S._exact_two_tier`` — gate top-1 + main top-k of every query over its own
tenant's rows, selected while the pool streams in blocks — against a NumPy
f32 oracle written here: same scores (bf16 rows × bf16-rounded query, f32
accumulate), ties to the lowest pool row, ``(NEG_INF, capacity)`` past a
query's own k or its tenant's live rows. The Pallas vehicle runs in
interpret mode at small shapes only; the compiled serving program is shown
to hold no ``[C, rows]`` buffer."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.ops import pallas_topk as PT
from lazzaro_tpu.utils.batching import REQUEST_COLS

NEG = np.float32(S.NEG_INF)
BLK = PT.SELECT_BLOCK


def _unit(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _arena(rows, d=32, tenants=4, seed=0, dead=0.1, supers=0.0, paged=0):
    """An ArenaState of ``rows`` pool rows built column by column (bf16
    rows; the last row is the dead sentinel). ``paged`` > 0: that many
    LOGICAL rows behind a shuffled ``row_map`` with free pool slots."""
    rng = np.random.default_rng(seed)
    emb = _unit(rng, rows, d)
    emb[-1] = 0.0
    n = paged or rows
    tenant = rng.integers(0, tenants, n).astype(np.int32)
    alive = rng.random(n) >= dead
    sup = rng.random(n) < supers
    alive[-1] = False
    st = S.init_arena(n - 1, d, jnp.bfloat16).replace(
        emb=jnp.asarray(emb, jnp.bfloat16), tenant_id=jnp.asarray(tenant),
        alive=jnp.asarray(alive), is_super=jnp.asarray(sup))
    if paged:
        slots = rng.permutation(rows - 1)[:n - 1].astype(np.int32)
        row_map = np.append(slots, rows - 1).astype(np.int32)
        inv = np.full((rows,), -1, np.int32)
        inv[row_map] = np.arange(n)
        st = st.replace(row_map=jnp.asarray(row_map),
                        inv_map=jnp.asarray(inv))
    return st


def _oracle(st, q, tenant_q, k, k_q=None):
    """NumPy f32 over what the arena stores; ties by POOL row."""
    emb = np.asarray(st.emb.astype(jnp.float32))
    qn = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-9)
    qn = np.asarray(jnp.asarray(qn, jnp.bfloat16).astype(jnp.float32))
    scores = qn @ emb.T
    pool_n, cap = emb.shape[0], st.capacity
    alive, ten, sup = (np.asarray(c) for c in
                       (st.alive, st.tenant_id, st.is_super))
    if st.row_map is not None:
        inv = np.asarray(st.inv_map)
        bound = inv >= 0
        at = np.maximum(inv, 0)
        alive, ten, sup = alive[at] & bound, ten[at], sup[at]
        logical = np.where(bound, inv, cap)
    else:
        logical = np.arange(pool_n)
    c = len(q)
    gate_s = np.full((c,), NEG)
    gate_r = np.full((c,), cap)
    ann_s = np.full((c, k), NEG)
    ann_r = np.full((c, k), cap)
    for i in range(c):
        mine = alive & (ten == tenant_q[i])
        for tier, width in ((mine & sup, 1), (mine & ~sup, k)):
            rows = np.flatnonzero(tier)
            order = rows[np.lexsort((rows, -scores[i, rows]))]
            if width == 1:
                if len(order):
                    gate_s[i], gate_r[i] = scores[i, order[0]], \
                        logical[order[0]]
                continue
            take = order[:k if k_q is None else min(k, int(k_q[i]))]
            ann_s[i, :len(take)] = scores[i, take]
            ann_r[i, :len(take)] = logical[take]
    return gate_s, gate_r, ann_s, ann_r


def _check(got, want):
    g_s, g_r, a_s, a_r = (np.asarray(x) for x in got)
    np.testing.assert_allclose(g_s, want[0], atol=2e-6, rtol=0)
    np.testing.assert_array_equal(g_r, want[1])
    np.testing.assert_allclose(a_s, want[2], atol=2e-6, rtol=0)
    np.testing.assert_array_equal(a_r, want[3])


def _k_mix(rng, batch, k):
    return rng.choice(np.asarray([1, 5, k], np.int32), size=batch)


CASES = {
    # pools: one block, several blocks, a row count the block does not divide
    "one_block_b8": dict(rows=300, batch=8, k=128, ragged=True),
    "several_blocks_b24": dict(rows=3 * BLK, batch=24, k=128, ragged=True),
    "indivisible_b8": dict(rows=2 * BLK + 100, batch=8, k=128, ragged=True),
    "half_block_pool_b8": dict(rows=3 * 1024, batch=8, k=16, ragged=True),
    # batch buckets 1 and 64
    "several_blocks_b1": dict(rows=2 * BLK, batch=1, k=128, ragged=True),
    "several_blocks_b64": dict(rows=2 * BLK, batch=64, k=128, ragged=True,
                               tenants=16),
    # the static-k callers run to their k
    "static_k8_blocks": dict(rows=2 * BLK, batch=8, k=8, ragged=False),
    "static_k16_one_block": dict(rows=500, batch=24, k=16, ragged=False),
    # a tenant with fewer live rows than k
    "short_tenants": dict(rows=2 * BLK, batch=8, k=128, ragged=True,
                          tenants=400),
    # super rows and the gate tier
    "gate_tier_blocks": dict(rows=3 * BLK, batch=8, k=16, ragged=True,
                             supers=0.05),
    "gate_tier_one_block": dict(rows=700, batch=8, k=16, ragged=False,
                                supers=0.2),
    # a paged pool scans in pool space
    "paged_blocks": dict(rows=2 * BLK, batch=8, k=16, ragged=True,
                         paged=5000, supers=0.02),
    "paged_one_block": dict(rows=600, batch=8, k=16, ragged=False,
                            paged=400),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_core_matches_numpy_oracle(name):
    case = dict(CASES[name])
    rows, batch, k = case["rows"], case["batch"], case["k"]
    tenants = case.get("tenants", 4)
    st = _arena(rows, tenants=tenants, seed=len(name),
                supers=case.get("supers", 0.0), paged=case.get("paged", 0))
    rng = np.random.default_rng(11)
    q = rng.standard_normal((batch, st.dim)).astype(np.float32)
    tenant_q = rng.integers(0, tenants, batch).astype(np.int32)
    k_q = _k_mix(rng, batch, k) if case["ragged"] else None
    got = jax.jit(S._exact_two_tier, static_argnums=(3,))(
        st, jnp.asarray(q), jnp.asarray(tenant_q), k,
        None if k_q is None else jnp.asarray(k_q))
    _check(got, _oracle(st, q, tenant_q, k, k_q))


def test_pad_queries_and_zero_k_return_the_sentinel():
    """Tenant -1 with k 0 (what the scheduler pads with) reads nothing,
    though dead rows carry tenant -1 too."""
    st = _arena(2 * BLK, supers=0.05)
    rng = np.random.default_rng(5)
    q = rng.standard_normal((8, st.dim)).astype(np.float32)
    q[5:] = 0.0
    tenant_q = np.asarray([0, 1, 2, 3, 0, -1, -1, -1], np.int32)
    k_q = np.asarray([5, 5, 1, 16, 0, 0, 0, 0], np.int32)
    got = S._exact_two_tier(st, jnp.asarray(q), jnp.asarray(tenant_q), 16,
                            jnp.asarray(k_q))
    _check(got, _oracle(st, q, tenant_q, 16, k_q))
    a_s, a_r = np.asarray(got[2]), np.asarray(got[3])
    assert (a_s[4:] == NEG).all() and (a_r[4:] == st.capacity).all()
    assert (np.asarray(got[0])[5:] == NEG).all()


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_ties_across_a_block_boundary_go_to_the_lowest_row(impl):
    """The same vector at the last row of one block, the first of the
    next and deep in a third: equal scores, ascending rows — and a copy in
    another tenant never shows."""
    blk, d = 512, 32
    rng = np.random.default_rng(2)
    n = 5 * blk
    emb = _unit(rng, n, d)
    twins = [blk - 1, blk, 2 * blk + 7, 3 * blk + 1]
    emb[twins] = emb[twins[0]]
    ten = np.zeros((n,), np.int32)
    ten[twins[3]] = 1
    ten[-1] = PT.ROW_DEAD
    embb = jnp.asarray(emb, jnp.bfloat16)
    q = jnp.asarray(np.tile(emb[twins[0]], (8, 1)), jnp.bfloat16)
    g_s, g_r, a_s, a_r = PT.blocked_two_tier(
        embb, q, jnp.asarray(ten), jnp.full((n,), PT.ROW_DEAD),
        jnp.zeros((8,), jnp.int32), 8, impl=impl)
    a_s, a_r = np.asarray(a_s), np.asarray(a_r)
    assert a_r[0, :3].tolist() == twins[:3]
    assert a_s[0, 0] == a_s[0, 1] == a_s[0, 2] > a_s[0, 3]
    assert twins[3] not in a_r[0]
    assert (np.asarray(g_s) == NEG).all()
    assert (np.asarray(g_r) == n - 1).all()


@pytest.mark.parametrize("batch,k", [(1, 8), (8, 128), (24, 16)])
def test_pallas_vehicle_in_interpret_mode_matches_the_loop(batch, k):
    """Both vehicles run the same step: same rows, and scores that differ
    by the CPU gemm's rounding at most (the kernel pads the batch to 16),
    at a shape small enough for interpret mode (3 blocks of 512)."""
    st = _arena(3 * 512, tenants=3, supers=0.05, seed=batch)
    rng = np.random.default_rng(batch)
    qn = jnp.asarray(_unit(rng, batch, st.dim), jnp.bfloat16)
    tenant_q = jnp.asarray(rng.integers(-1, 3, batch), jnp.int32)
    k_q = jnp.asarray(_k_mix(rng, batch, k))
    alive, sup = np.asarray(st.alive), np.asarray(st.is_super)
    ten = np.asarray(st.tenant_id)
    rm = jnp.asarray(np.where(alive & ~sup, ten, PT.ROW_DEAD), jnp.int32)
    rg = jnp.asarray(np.where(alive & sup, ten, PT.ROW_DEAD), jnp.int32)
    outs = [PT.blocked_two_tier(st.emb, qn, rm, rg, tenant_q, k, k_q,
                                impl=impl) for impl in ("jax", "pallas")]
    for i, (a, b) in enumerate(zip(*outs)):
        if i % 2:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-6, rtol=0)


def test_pallas_vehicle_refuses_a_pool_no_block_tiles():
    with pytest.raises(ValueError, match="no block tiles"):
        PT.blocked_two_tier(jnp.zeros((700, 32), jnp.bfloat16),
                            jnp.zeros((8, 32), jnp.bfloat16),
                            jnp.zeros((700,), jnp.int32),
                            jnp.zeros((700,), jnp.int32),
                            jnp.zeros((8,), jnp.int32), 8, impl="pallas")


@pytest.mark.parametrize("rows,d,item,want", [
    (135_168, 768, 2, 4096), (5_001_216, 768, 2, 4096), (300, 32, 2, 300),
    (2 * 4096 + 100, 32, 2, 2 * 4096 + 100), (3 * 1024, 768, 2, 1024),
    (4096, 768, 2, 4096), (5 * 512, 32, 2, 512),
    (1 << 20, 1536, 4, 1024)])
def test_block_follows_the_pool(rows, d, item, want):
    from lazzaro_tpu.plan import model
    assert PT.select_block_rows(rows, d, item) == want
    assert model.select_block_rows(rows, d, item) == want   # the mirror


def test_shard_local_call_under_shard_map():
    """Each chip of a 2-way mesh runs the core on its own slice (two
    blocks each) and returns LOCAL rows; both slices match the oracle."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    parts, local = 2, 2 * 512
    st = _arena(parts * local, tenants=3, supers=0.03, seed=9)
    mesh = Mesh(np.asarray(jax.devices()[:parts]), ("data",))
    rng = np.random.default_rng(4)
    q = rng.standard_normal((8, st.dim)).astype(np.float32)
    tenant_q = rng.integers(0, 3, 8).astype(np.int32)
    k_q = _k_mix(rng, 8, 16)

    def local_core(arena, q_c, t_c, k_c):
        outs = S._exact_two_tier(arena, q_c, t_c, 16, k_c)
        return tuple(o[None] for o in outs)

    row = jax.tree_util.tree_map(
        lambda a: P("data", None) if a.ndim == 2 else P("data"), st)
    rep = (P(None, None), P(None), P(None))
    got = jax.jit(shard_map(
        local_core, mesh=mesh, in_specs=(row,) + rep,
        out_specs=(P("data", None), P("data", None), P("data", None, None),
                   P("data", None, None)), check_vma=False))(
        st, jnp.asarray(q), jnp.asarray(tenant_q), jnp.asarray(k_q))
    for p in range(parts):
        sl = slice(p * local, (p + 1) * local)
        part = jax.tree_util.tree_map(lambda a: a[sl], st)
        # a slice's last row is its own sentinel: what "no row" reads as
        _check([np.asarray(o)[p] for o in got],
               _oracle(part, q, tenant_q, 16, k_q))


# ------------------------------------------------- the compiled serving program


def _serving_index(rows_cap, d=32, tenants=6, per=40):
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.utils.telemetry import Telemetry
    idx = MemoryIndex(dim=d, capacity=rows_cap, edge_capacity=255,
                      telemetry=Telemetry())
    rng = np.random.default_rng(1)
    for t in range(tenants):
        emb = _unit(rng, per, d)
        idx.add([f"t{t}n{i}" for i in range(per)], emb, [0.5] * per,
                [0.0] * per, ["semantic"] * per, ["default"] * per, f"t{t}")
    return idx


def _compiled_read(idx, c):
    st = idx.state
    indptr, nbr = idx._csr_for(st)
    return S.search_fused_ragged_read.lower(
        st, indptr, nbr,
        jax.ShapeDtypeStruct((c, idx.dim + REQUEST_COLS), jnp.int32),
        k=128, cap_take=5, max_nbr=4).compile()


def test_compiled_serving_program_holds_no_score_tile():
    """``search_fused_ragged_read`` at a several-block geometry: no
    ``f32[C, rows]`` buffer anywhere in the compiled HLO, its temporaries
    fit under the planner's transient term, and three times the rows add
    less than a sixth of what the tile would."""
    from lazzaro_tpu.plan.model import CostModel

    c = 24
    temps = []
    for blocks in (3, 9):
        idx = _serving_index(blocks * BLK - 1)
        rows = idx.state.emb.shape[0]
        assert rows == blocks * BLK
        comp = _compiled_read(idx, c)
        text = comp.as_text()
        assert f"[{c},{BLK}]" in text                # one block's tile
        assert not re.search(rf"\[{c},{rows}\]", text)
        assert not re.search(rf"\[{rows},{c}\]", text)
        temps.append(comp.memory_analysis().temp_size_in_bytes)
        geom = idx._serve_geometry(c, "exact", 128)
        assert temps[-1] < CostModel().transient_bytes(geom)
    assert temps[1] - temps[0] < c * 6 * BLK * 4 / 6


def test_select_counter_names_the_core():
    """``serve.select{core}``: ``blocked`` when a block tiles the pool,
    ``whole_pool`` when the pool is one block; in ``prometheus()``."""
    from lazzaro_tpu.serve.scheduler import RetrievalRequest

    for cap, core in ((2 * BLK - 1, "blocked"), (255, "whole_pool")):
        idx = _serving_index(cap, tenants=2, per=10)
        reqs = [RetrievalRequest(query=np.ones((idx.dim,), np.float32),
                                 tenant="t0", k=5) for _ in range(3)]
        out = idx.search_fused_requests(reqs, cap_take=5, max_nbr=4,
                                        super_gate=0.4, acc_boost=0.0,
                                        nbr_boost=0.0)
        assert all(len(r.ids) == 5 for r in out)
        tel = idx.telemetry
        assert tel.counter_total("serve.select") >= 1
        line = f'lazzaro_serve_select_total{{core="{core}"}}'
        assert line in tel.prometheus()
        other = "whole_pool" if core == "blocked" else "blocked"
        assert f'core="{other}"' not in "".join(
            ln for ln in tel.prometheus().splitlines()
            if ln.startswith("lazzaro_serve_select_total"))
