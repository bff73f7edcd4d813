"""Pod-scale fused serving (ISSUE 5): single-chip vs mesh parity.

The full chat-turn retrieval program — masked super top-1 gate, main ANN
top-k, CSR neighbor gather, neighbor+access boost scatters — must run as
ONE distributed shard_map dispatch (``state.make_fused_sharded``) and be
BIT-IDENTICAL to the single-chip fused kernels: the shard-local cores are
the same code, the all_gather merge preserves top-k order, and boosts land
as shard-local scatters. These tests pin that parity at the state level
(exact / quant / IVF twins, gate-hit and gate-miss, boost numerics,
multi-tenant isolation) on 2- and 4-way host-device meshes, plus the
``ShardedMemoryIndex`` wiring: one dispatch per coalesced mega-batch
(jit-counter via the ``_dispatch`` hook) and the batch max-k keying that
fixes the old silent truncation when a request's ``k`` exceeded the
construction-time default.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import build_host_csr, split_csr
from lazzaro_tpu.parallel.index import ShardedMemoryIndex
from lazzaro_tpu.parallel.mesh import make_mesh, shard_stacked
from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest
from lazzaro_tpu.utils.batching import RequestCarrier, unpack_retrieval

D = 16
CAP = 127          # cap+1 = 128 divides both mesh shapes
K, CT, MN = 8, 5, 8


def _mesh(n):
    return make_mesh(("data",), (n,), devices=jax.devices()[:n])


def _arena(n_rows=90, seed=0, tenants=2, super_every=9):
    rng = np.random.default_rng(seed)
    st = S.init_arena(CAP, D, jnp.float32)
    emb = rng.standard_normal((n_rows, D)).astype(np.float32)
    rows = np.arange(n_rows, dtype=np.int32)
    tcol = (np.arange(n_rows) % tenants).astype(np.int32)
    sup = (np.arange(n_rows) % super_every == 0)
    st = S.arena_add_copy(st, jnp.asarray(rows), jnp.asarray(emb),
                          jnp.full((n_rows,), 0.5, jnp.float32),
                          jnp.zeros((n_rows,), jnp.float32),
                          jnp.zeros((n_rows,), jnp.int32),
                          jnp.zeros((n_rows,), jnp.int32),
                          jnp.asarray(tcol), jnp.asarray(sup))
    id_to_row = {f"n{i}": i for i in range(n_rows)}
    keys = ([(f"n{i}", f"n{i + 1}") for i in range(n_rows - 1)]
            + [(f"n{i}", f"n{(i * 7) % n_rows}")
               for i in range(0, n_rows, 5)])
    indptr, nbr = build_host_csr(keys, id_to_row, CAP + 1)
    return st, emb, indptr, nbr


def _queries(seed=1, q=8, tenants=2):
    rng = np.random.default_rng(seed)
    qv = rng.standard_normal((q, D)).astype(np.float32)
    q_valid = np.ones((q,), bool)
    q_valid[-1] = False
    tq = (np.arange(q) % tenants).astype(np.int32)
    gate_on = np.ones((q,), bool)
    boost_on = np.ones((q,), bool)
    return qv, q_valid, tq, gate_on, boost_on


def _shard_state(st, mesh):
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, mat if a.ndim == 2 else row), st)


def _shard_csr(indptr, nbr, mesh):
    stk = shard_stacked(mesh, "data")
    ish, nsh = split_csr(indptr, nbr, mesh.shape["data"])
    return jax.device_put(ish, stk), jax.device_put(nsh, stk)


def _requests(qv, q_valid, tq, gate_on, boost_on=None, nprobe=0):
    """The dispatch's request carrier (ISSUE 37), the same array on both
    sides of a comparison: uniform per-query columns (every query asks the
    static ceilings) and the boost scalars."""
    n = len(qv)
    return jnp.asarray(RequestCarrier.of(
        qv, valid=q_valid, tenant=tq, gate_on=gate_on,
        boost_on=np.zeros((n,), bool) if boost_on is None else boost_on,
        k=np.full((n,), K), cap=np.full((n,), CT),
        nprobe=np.full((n,), nprobe), now=1000.0, super_gate=0.4,
        acc_boost=0.05, nbr_boost=0.02).buf)

# A shard-local scan and the whole-arena scan reduce the same products in a
# different order, so cosines differ in the last bits of a UNIT-scale f32
# (the error accrues at the scale of the partial sums, not of the result):
# 2 ULP of a value in [0.5, 1).
_SCORE_ATOL = float(np.finfo(np.float32).eps)


def _assert_packed_parity(p1, p2, k=K):
    """Rows, gate verdicts and the counter tail exact; scores to
    ``_SCORE_ATOL``."""
    u1, u2 = (unpack_retrieval(np.asarray(p), k) for p in (p1, p2))
    for a, b, exact in zip(u1, u2, (False, True, False, True, True, True)):
        if exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=0, atol=_SCORE_ATOL)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_exact_mode_bit_identical_to_single_chip(n_dev):
    """Packed readback AND post-serve boost columns (salience, access
    counts, freshness) must match the single-chip ``search_fused_ragged`` —
    rows, gate verdicts, neighbor dedup, multi-tenant masks and boost columns bit
    for bit, scores to ``_SCORE_ATOL``."""
    mesh = _mesh(n_dev)
    st, emb, indptr, nbr = _arena()
    reqs = _requests(*_queries())
    st1, p1 = S.search_fused_ragged_copy(
        st, jnp.asarray(indptr), jnp.asarray(nbr), reqs, k=K, cap_take=CT,
        max_nbr=MN)
    kern = S.make_fused_sharded(mesh, "data", k=K, cap_take=CT, max_nbr=MN,
                                mode="exact")
    ish, nsh = _shard_csr(indptr, nbr, mesh)
    st2, p2 = kern.serve_copy(_shard_state(st, mesh), (), ish, nsh, reqs)
    _assert_packed_parity(p1, p2)
    for col in ("salience", "access_count", "last_accessed"):
        np.testing.assert_array_equal(np.asarray(getattr(st1, col)),
                                      np.asarray(getattr(st2, col)))


def test_read_twin_matches_and_mutates_nothing():
    mesh = _mesh(4)
    st, emb, indptr, nbr = _arena()
    reqs = _requests(*_queries()[:4])
    r1 = S.search_fused_ragged_read(
        st, jnp.asarray(indptr), jnp.asarray(nbr), reqs, k=K, cap_take=CT,
        max_nbr=MN)
    kern = S.make_fused_sharded(mesh, "data", k=K, cap_take=CT, max_nbr=MN,
                                mode="exact")
    ish, nsh = _shard_csr(indptr, nbr, mesh)
    st_sh = _shard_state(st, mesh)
    sal_before = np.asarray(st_sh.salience)
    r2 = kern.read(st_sh, (), ish, nsh, reqs)
    _assert_packed_parity(r1, r2)
    np.testing.assert_array_equal(sal_before, np.asarray(st_sh.salience))


def test_quant_mode_parity_exhaustive_slack():
    """With slack >= live rows the int8 coarse stage is exhaustive on both
    sides, so the sharded quant twin must match the single-chip quant
    kernel exactly (scores come from the same exact rescore)."""
    from lazzaro_tpu.ops.quant import quantize_rows

    mesh = _mesh(4)
    st, emb, indptr, nbr = _arena()
    reqs = _requests(*_queries())
    q8, scale = quantize_rows(st.emb)
    slack = CAP + 1
    st1, p1 = S.search_fused_quant_ragged_copy(
        st, q8, scale, jnp.asarray(indptr), jnp.asarray(nbr), reqs, k=K,
        slack=slack, cap_take=CT, max_nbr=MN)
    kern = S.make_fused_sharded(mesh, "data", k=K, cap_take=CT, max_nbr=MN,
                                mode="quant", slack=slack)
    ish, nsh = _shard_csr(indptr, nbr, mesh)
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    st2, p2 = kern.serve_copy(
        _shard_state(st, mesh),
        (jax.device_put(q8, mat), jax.device_put(scale, row)),
        ish, nsh, reqs)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    for col in ("salience", "access_count", "last_accessed"):
        np.testing.assert_array_equal(np.asarray(getattr(st1, col)),
                                      np.asarray(getattr(st2, col)))


def test_ivf_mode_parity_full_probe():
    """nprobe == n_clusters makes the candidate set exhaustive on both
    sides; scores are exact in both kernels, so live results and boost
    columns must agree (row order at equal scores may differ across
    candidate layouts, so compare sets + numerics)."""
    from lazzaro_tpu.ops import ivf as IVF

    mesh = _mesh(4)
    st, emb, indptr, nbr = _arena()
    ivf = IVF.build_ivf(st.emb, np.asarray(st.alive), n_clusters=8, iters=4)
    sup_rows = np.flatnonzero(np.asarray(st.is_super)).tolist()
    extras = IVF.pack_extras(np.asarray(ivf.residual), [], sup_rows)
    nprobe = ivf.n_clusters
    reqs = _requests(*_queries(), nprobe=nprobe)
    st1, p1 = S.search_fused_ivf_ragged_copy(
        st, None, ivf.centroids, ivf.members, jnp.asarray(extras),
        jnp.asarray(indptr), jnp.asarray(nbr), reqs,
        k=K, nprobe=nprobe, slack=8, cap_take=CT, max_nbr=MN)
    part = (CAP + 1) // 4
    mem_sh, ext_sh = IVF.shard_serve_tables(np.asarray(ivf.members), extras,
                                            4, part)
    kern = S.make_fused_sharded(mesh, "data", k=K, cap_take=CT, max_nbr=MN,
                                mode="ivf", slack=8, nprobe=nprobe)
    stk = shard_stacked(mesh, "data")
    ish, nsh = _shard_csr(indptr, nbr, mesh)
    st2, p2 = kern.serve_copy(
        _shard_state(st, mesh),
        (jax.device_put(ivf.centroids, NamedSharding(mesh, P())),
         jax.device_put(mem_sh, stk), jax.device_put(ext_sh, stk)),
        ish, nsh, reqs)
    p1, p2 = np.asarray(p1), np.asarray(p2)
    np.testing.assert_allclose(p1[:, 0], p2[:, 0], atol=1e-6)   # gate score
    np.testing.assert_array_equal(p1[:, -1], p2[:, -1])         # fast bit
    np.testing.assert_allclose(p1[:, 2:2 + K], p2[:, 2:2 + K], atol=1e-6)
    for col in ("salience", "access_count", "last_accessed"):
        np.testing.assert_array_equal(np.asarray(getattr(st1, col)),
                                      np.asarray(getattr(st2, col)))


# ---------------------------------------------------------- index wiring
def _basis(i):
    v = np.zeros(D, np.float32)
    v[i % D] = 1.0
    return v


def _filled_index(mesh, **kw):
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=CAP, dtype=np.float32,
                             **kw)
    rng = np.random.default_rng(3)
    emb_a = rng.standard_normal((12, D)).astype(np.float32)
    emb_b = rng.standard_normal((6, D)).astype(np.float32)
    idx.add([f"a{i}" for i in range(12)], emb_a, "alice")
    idx.add([f"b{i}" for i in range(6)], emb_b, "bob")
    idx.add_edges([(f"a{i}", f"a{i + 1}", 0.7) for i in range(11)])
    return idx, emb_a, emb_b


def test_serve_requests_one_distributed_dispatch_and_boosts():
    """The coalesced mixed-tenant batch costs exactly ONE distributed
    dispatch (the donated fused program — counted via the ``_dispatch``
    hook every device entry goes through), applies the access/neighbor
    boosts on device, and keeps tenants isolated."""
    mesh = _mesh(4)
    idx, emb_a, emb_b = _filled_index(mesh)
    reqs = [RetrievalRequest(query=emb_a[1], tenant="alice", k=3,
                             boost=True),
            RetrievalRequest(query=emb_b[0], tenant="bob", k=2, boost=True),
            RetrievalRequest(query=emb_a[4], tenant="alice", k=3)]
    idx.serve_requests(reqs)                   # warm/compile
    calls = {"n": 0}
    orig = idx._dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig(fn, *a, **kw)

    idx._dispatch = counting
    acc_before = np.asarray(idx.state.access_count).copy()
    res = idx.serve_requests(reqs)
    assert calls["n"] == 1
    assert res[0].ids[0] == "a1" and all(i.startswith("a") for i in res[0].ids)
    assert res[1].ids[0] == "b0" and all(i.startswith("b") for i in res[1].ids)
    assert res[0].boosted and res[1].boosted and not res[2].boosted
    acc_after = np.asarray(idx.state.access_count)
    boosted_rows = [idx.id_to_row[i] for i in res[0].ids + res[1].ids]
    for r in boosted_rows:
        assert acc_after[r] >= acc_before[r] + 1
    # each boosted query bumps its top cap_take rows exactly once (the
    # classic per-turn semantics), and the no-boost request adds nothing
    assert (acc_after.sum() - acc_before.sum()
            == 2 * idx.cap_take)


def test_pure_read_batch_takes_read_twin_single_dispatch():
    mesh = _mesh(2)
    idx, emb_a, _ = _filled_index(mesh)
    reqs = [RetrievalRequest(query=emb_a[2], tenant="alice", k=4)]
    idx.serve_requests(reqs)
    sal_before = np.asarray(idx.state.salience).copy()
    calls = {"n": 0}
    orig = idx._dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig(fn, *a, **kw)

    idx._dispatch = counting
    res = idx.serve_requests(reqs)
    assert calls["n"] == 1
    assert res[0].ids[0] == "a2"
    np.testing.assert_array_equal(sal_before, np.asarray(idx.state.salience))


def test_gate_verdict_reaches_pod_results():
    """A super row above the 0.4 gate flips ``fast`` on (and suppresses the
    device boosts for that query), below it stays off — the verdict the
    old pod path silently dropped."""
    mesh = _mesh(4)
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=CAP, dtype=np.float32)
    idx.add(["s0"], _basis(0).reshape(1, -1), "u", supers=[True])
    idx.add(["m1", "m2"], np.stack([_basis(1), _basis(2)]), "u")
    hit = idx.serve_requests([RetrievalRequest(
        query=_basis(0), tenant="u", k=2, gate_enabled=True, boost=True)])[0]
    assert hit.fast and hit.gate_id == "s0" and hit.gate_score > 0.4
    assert not hit.boosted                     # host owns the fast path
    miss = idx.serve_requests([RetrievalRequest(
        query=_basis(3), tenant="u", k=2, gate_enabled=True, boost=True)])[0]
    assert not miss.fast
    # gate disabled: verdict must stay off even on a perfect super match
    off = idx.serve_requests([RetrievalRequest(
        query=_basis(0), tenant="u", k=2, gate_enabled=False)])[0]
    assert not off.fast


def test_request_k_above_default_is_not_truncated():
    """Satellite regression: the old pod path truncated every request to
    the construction-time ``k``; the kernel is now keyed on the batch
    max-k (pow2-bucketed). Covers BOTH the fused and the classic path."""
    for fused in (True, False):
        mesh = _mesh(4)
        idx = ShardedMemoryIndex(mesh, dim=D, capacity=CAP,
                                 dtype=np.float32, k=4, serve_fused=fused)
        rng = np.random.default_rng(5)
        n = 20
        idx.add([f"x{i}" for i in range(n)],
                rng.standard_normal((n, D)).astype(np.float32), "u")
        res = idx.serve_requests([RetrievalRequest(
            query=rng.standard_normal(D).astype(np.float32), tenant="u",
            k=12)])[0]
        assert len(res.ids) == 12, (fused, len(res.ids))
        # and mixed-k batches demux each request at its own k
        res2 = idx.serve_requests([
            RetrievalRequest(query=rng.standard_normal(D).astype(np.float32),
                             tenant="u", k=2),
            RetrievalRequest(query=rng.standard_normal(D).astype(np.float32),
                             tenant="u", k=11)])
        assert len(res2[0].ids) == 2 and len(res2[1].ids) == 11


def test_index_int8_and_ivf_modes_serve_sane_results():
    """int8 and IVF pod modes: same top-1 on well-separated data, one
    dispatch, and the IVF extras keep fresh rows visible."""
    mesh = _mesh(4)
    for mode_kw in (dict(int8_serving=True), dict()):
        idx = ShardedMemoryIndex(mesh, dim=D, capacity=CAP,
                                 dtype=np.float32, **mode_kw)
        ids = [f"v{i}" for i in range(24)]
        embs = np.stack([_basis(i) + 0.05 * np.arange(D) for i in range(24)])
        idx.add(ids, embs, "u")
        if not mode_kw:
            assert idx.ivf_build(n_clusters=4, nprobe=4)
        res = idx.serve_requests([RetrievalRequest(
            query=embs[7], tenant="u", k=3)])[0]
        assert res.ids[0] == "v7"
        if not mode_kw:
            # fresh row added AFTER the build serves exactly via extras
            idx.add(["fresh"], (_basis(3) * 2).reshape(1, -1), "u")
            res = idx.serve_requests([RetrievalRequest(
                query=_basis(3) * 2, tenant="u", k=2)])[0]
            assert res.ids[0] == "fresh"


def test_scheduler_mega_batch_reaches_pod_path_once():
    """QueryScheduler coalescing composes with the fused pod path: many
    concurrent requests across tenants flush as batches, each batch ONE
    distributed dispatch."""
    mesh = _mesh(4)
    idx, emb_a, emb_b = _filled_index(mesh)
    idx.serve_requests([RetrievalRequest(query=emb_a[0], tenant="alice",
                                         k=3)])       # warm the kernel
    calls = {"n": 0}
    orig = idx._dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig(fn, *a, **kw)

    idx._dispatch = counting
    sched = QueryScheduler(idx.serve_requests, max_batch=16)
    try:
        futures = sched.submit_many(
            [RetrievalRequest(query=emb_a[i % 12], tenant="alice", k=3)
             for i in range(8)]
            + [RetrievalRequest(query=emb_b[i % 6], tenant="bob", k=2)
               for i in range(8)])
        res = [f.result(timeout=30) for f in futures]
    finally:
        sched.close()
    assert all(r.ids for r in res)
    assert all(i.startswith("a") for r in res[:8] for i in r.ids)
    assert all(i.startswith("b") for r in res[8:] for i in r.ids)
    batches = sched.stats()["batches_flushed"]
    assert calls["n"] == batches               # one dispatch per mega-batch
