"""Paged embedding arena (ISSUE 17, tier-1, CPU, tiny arenas).

The master embedding table becomes a fixed-size-page HBM pool behind an
int32 ``row_map`` indirection with a device-side free list: delete and
tier-demote PUSH slots back (reclaimed capacity the next ingest reuses),
logical growth rewrites metadata only (the pool is never copied), and the
free-list pop rides INSIDE the fused ingest dispatch. These tests pin the
three contracts the whole feature stands on:

  * parity — a paged index answers every serving mode (exact / int8 /
    IVF / IVF-PQ / tiered) identically to a dense index fed the SAME
    corpus through the same ingest → delete → re-ingest → grow churn;
  * zero added dispatches — the jit-entry counters on an ingest+serve
    round are IDENTICAL dense vs paged (the page maintenance is fused,
    not a sibling dispatch), and the host free-list mirror never
    disagrees with the device readback tail;
  * durability — ``row_map`` + free list survive a checkpoint
    round-trip and the restored free list keeps allocating.

Parity is EXACT, full-list (ISSUE 18): dense demote zero-fills rows
that stay alive, but the residency column now masks them to -inf in the
exact dense scan — the same rows the paged layout drops by freeing the
slot — so a demoted row can no longer surface as a score-0.0 top-k tail
in either layout and the comparisons assert the complete k-list.
"""

import tempfile

import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.checkpoint import load_index, save_index
from lazzaro_tpu.core.index import MemoryIndex

D = 16
CAP = 64


def _corpus(n, d=D, seed=7):
    rng = np.random.default_rng(seed)
    e = rng.standard_normal((n, d)).astype(np.float32)
    e /= np.linalg.norm(e, axis=1, keepdims=True)
    return e


def _clustered(n, d=D, seed=9, centers=8):
    rng = np.random.default_rng(seed)
    c = rng.standard_normal((centers, d)).astype(np.float32)
    e = (c[np.arange(n) % centers]
         + 0.15 * rng.standard_normal((n, d)).astype(np.float32))
    e /= np.linalg.norm(e, axis=1, keepdims=True)
    return e


def _add(idx, ids, emb, ts=0.0):
    n = len(ids)
    idx.add(ids, emb, [0.5] * n, [ts] * n, ["semantic"] * n,
            ["default"] * n, "t")


def _churn(idx, e):
    """Shared ingest → delete → dedup-ingest → grow sequence. Both the
    dense and the paged variant run EXACTLY this, on the same ``e``."""
    _add(idx, [f"m{i}" for i in range(48)], e[:48])
    idx.delete([f"m{i}" for i in range(0, 20, 2)])        # 10 holes
    pend = idx.ingest_batch_dedup(
        e[48:64], [0.6] * 16, [1.0] * 16, ["semantic"] * 16,
        ["default"] * 16, "t", dedup_gate=0.99)
    idx.commit_ingest_dedup(pend, [f"d{i}" for i in range(16)])
    _add(idx, [f"g{i}" for i in range(60)], e[64:124], ts=2.0)  # forces grow


def _pos(ids, scores):
    """(id, score) pairs over the FULL result list — the residency mask
    (ISSUE 18) closed the dense-demote score-0.0 tail, so nothing is
    filtered before comparing."""
    return [(i, round(float(s), 5)) for i, s in zip(ids, scores)]


def _parity_search(dense, paged, queries, k=10, **kw):
    """FULL-list parity: ids in order and scores to float tolerance —
    the dense-demote residency mask (ISSUE 18) closed the score-0.0
    tail divergence, so nothing is filtered before comparing."""
    for q in queries:
        di, ds = dense.search(q, "t", k=k, **kw)
        pi, ps = paged.search(q, "t", k=k, **kw)
        assert di == pi, (list(zip(di, ds)), list(zip(pi, ps)))
        np.testing.assert_allclose(ds, ps, atol=1e-5)


def test_paged_dense_parity_exact_churn():
    e = _corpus(124)
    dense = MemoryIndex(dim=D, capacity=CAP)
    paged = MemoryIndex(dim=D, capacity=CAP, paged=True, page_rows=8)
    for idx in (dense, paged):
        _churn(idx, e)
    _parity_search(dense, paged, e[:6])
    _parity_search(dense, paged, e[70:74], exact=True)
    # the churn exercised the free list both ways, and the host mirror
    # never disagreed with the device readback tail
    st = paged.stats()["paged"]
    assert st["pops_total"] > 0 and st["pushes_total"] > 0
    assert paged.telemetry.counter_total(
        "arena.page_mirror_mismatches") == 0
    # per-id vector readout goes through the same indirection
    for rid in ("m21", "g3", "d0"):
        np.testing.assert_allclose(dense.get_embedding(rid),
                                   paged.get_embedding(rid), atol=1e-6)


def test_paged_growth_is_metadata_only():
    """Copy-free growth: logical capacity doubles with block rounding
    while the pool grows only on live-set demand — after the churn the
    emb pool is strictly SMALLER than the logical table (dense would
    carry capacity+1 embedding rows), and the raw grow step reuses the
    pool buffer by reference (no copy of any embedding byte)."""
    e = _corpus(124)
    paged = MemoryIndex(dim=D, capacity=CAP, paged=True, page_rows=8)
    _churn(paged, e)
    assert paged.capacity > CAP                      # churn forced growth
    assert paged.state.emb.shape[0] - 1 < paged.capacity
    st = paged.state
    st2 = S.grow_arena_paged(st, paged.capacity * 2 + 1)
    assert st2.emb is st.emb                         # SAME buffer, no copy
    assert st2.capacity == paged.capacity * 2 + 1
    assert st2.row_map.shape[0] == st2.capacity + 1


def test_paged_dense_parity_int8():
    e = _corpus(124)
    dense = MemoryIndex(dim=D, capacity=CAP, int8_serving=True)
    paged = MemoryIndex(dim=D, capacity=CAP, int8_serving=True,
                        paged=True, page_rows=8)
    for idx in (dense, paged):
        _churn(idx, e)
    _parity_search(dense, paged, e[:6])


def test_paged_dense_parity_ivf():
    e = _clustered(320)
    dense = MemoryIndex(dim=D, capacity=256, ivf_nprobe=4)
    paged = MemoryIndex(dim=D, capacity=256, ivf_nprobe=4,
                        paged=True, page_rows=16)
    for idx in (dense, paged):
        idx._IVF_MIN_ROWS = 1
        _add(idx, [f"m{i}" for i in range(256)], e[:256])
        assert idx.ivf_maintenance()
        idx.delete([f"m{i}" for i in range(0, 64, 4)])     # member holes
        _add(idx, [f"f{i}" for i in range(32)], e[256:288], ts=1.0)
    _parity_search(dense, paged, e[::40][:6], k=5)
    assert paged.stats()["paged"]["pages_free"] >= 0


def test_paged_dense_parity_pq():
    e = _clustered(320, d=32)
    dense = MemoryIndex(dim=32, capacity=256, ivf_nprobe=4,
                        pq_serving=True)
    paged = MemoryIndex(dim=32, capacity=256, ivf_nprobe=4,
                        pq_serving=True, paged=True, page_rows=16)
    for idx in (dense, paged):
        idx._IVF_MIN_ROWS = 1
        _add(idx, [f"m{i}" for i in range(256)], e[:256])
        assert idx.ivf_maintenance()
        assert idx._pq_book is not None
        _add(idx, [f"f{i}" for i in range(16)], e[256:272], ts=1.0)
    _parity_search(dense, paged, e[::40][:6], k=5)


def test_paged_tiering_reclaims_pages_and_parity():
    """Tier demote must PUSH freed slots (reclaimed capacity), the pump's
    IVF repack hook must keep member lists hole-free, and the meaningful
    top-k must match the dense tiered index."""
    e = _corpus(124)
    dense = MemoryIndex(dim=D, capacity=CAP, int8_serving=True)
    paged = MemoryIndex(dim=D, capacity=CAP, int8_serving=True,
                        paged=True, page_rows=8)
    for idx in (dense, paged):
        _add(idx, [f"m{i}" for i in range(48)], e[:48])
        tm = idx.enable_tiering(hot_budget_rows=16)
        tm.run_once()
        assert tm.demoted_total > 0
    assert dense.tiering.demoted_total == paged.tiering.demoted_total
    st = paged.stats()["paged"]
    assert st["pages_free"] > 0
    assert st["pushes_total"] == paged.tiering.demoted_total
    assert paged.telemetry.counter_total(
        "arena.page_mirror_mismatches") == 0
    _parity_search(dense, paged, e[:6], k=4)
    # re-ingest after demote REUSES the freed pages: no pool growth
    pool = paged.state.emb.shape[0]
    _add(paged, [f"r{i}" for i in range(8)], e[64:72], ts=3.0)
    assert paged.state.emb.shape[0] == pool
    assert paged.stats()["paged"]["pages_free"] < st["pages_free"]


def test_paged_mesh_warns_and_falls_back_dense():
    import jax

    from lazzaro_tpu.parallel.mesh import make_mesh

    e = _corpus(80)
    mesh = make_mesh(("data",), (2,), jax.devices()[:2])
    with pytest.warns(UserWarning, match="paged arena is single-chip"):
        meshed = MemoryIndex(dim=D, capacity=CAP, mesh=mesh,
                             paged=True, page_rows=8)
    assert not meshed.paged and meshed.state.row_map is None
    # the fallback still answers exactly like a single-chip paged index
    single = MemoryIndex(dim=D, capacity=CAP, paged=True, page_rows=8)
    for idx in (meshed, single):
        _add(idx, [f"m{i}" for i in range(48)], e[:48])
        idx.delete([f"m{i}" for i in range(0, 12, 2)])
        _add(idx, [f"g{i}" for i in range(8)], e[48:56], ts=1.0)
    for q in e[:5]:
        mi, msc = meshed.search(q, "t", k=6)
        si, ssc = single.search(q, "t", k=6)
        mp, sp = _pos(mi, msc), _pos(si, ssc)
        assert [i for i, _ in mp] == [i for i, _ in sp]
        np.testing.assert_allclose([s for _, s in mp],
                                   [s for _, s in sp], atol=1e-5)


_COUNTED = ("ingest_fused", "ingest_fused_copy", "ingest_dedup_fused",
            "ingest_dedup_fused_copy",
            "search_fused_ragged", "search_fused_ragged_copy",
            "arena_add", "arena_add_copy", "arena_delete", "arena_delete_copy",
            "arena_add_paged", "arena_add_paged_copy",
            "arena_delete_paged", "arena_delete_paged_copy",
            "tier_demote_paged", "tier_demote_paged_copy",
            "tier_promote_paged", "tier_promote_paged_copy")


def _count_dispatches(monkeypatch):
    calls = {name: 0 for name in _COUNTED}
    for name in _COUNTED:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    return calls


def test_paging_adds_zero_dispatches(monkeypatch):
    """The jit-call counter, dense vs paged, same ops: the free-list pop
    rides INSIDE the one fused ingest program and the serve path is the
    same one fused search — paging must not add a single extra dispatch
    on the steady-state path."""
    e = _corpus(40)
    common = dict(saliences=[0.5] * 12, timestamps=[0.0] * 12,
                  types=["semantic"] * 12, shard_keys=["default"] * 12)

    def run(paged):
        idx = MemoryIndex(dim=D, capacity=CAP, paged=paged, page_rows=8)
        _add(idx, [f"s{i}" for i in range(16)], e[:16])   # warm (uncounted)
        idx.search(e[0], "t", k=5)
        calls = _count_dispatches(monkeypatch)
        before = idx.ingest_dispatch_count
        idx.ingest_batch([f"n{i}" for i in range(12)], e[16:28],
                         tenant="t", link_k=3, **common)
        assert idx.ingest_dispatch_count - before == 1
        idx.search(e[20], "t", k=5)
        return idx, dict(calls)

    dense_idx, dense_calls = run(False)
    paged_idx, paged_calls = run(True)
    assert dense_calls == paged_calls, (dense_calls, paged_calls)
    assert (paged_calls["ingest_fused"]
            + paged_calls["ingest_fused_copy"]) == 1
    # page maintenance never surfaced as a sibling dispatch
    for name in ("arena_add_paged", "arena_add_paged_copy",
                 "tier_demote_paged", "tier_demote_paged_copy",
                 "tier_promote_paged", "tier_promote_paged_copy"):
        assert paged_calls[name] == 0, (name, paged_calls)
    assert paged_idx.telemetry.counter_total(
        "arena.page_mirror_mismatches") == 0


def test_paged_checkpoint_roundtrip():
    """``row_map`` + free list survive save/load: identical answers, an
    identical page table, and a free list that KEEPS allocating (delete →
    re-add reuses a reclaimed slot, no pool growth)."""
    e = _corpus(126)
    idx = MemoryIndex(dim=D, capacity=CAP, paged=True, page_rows=8)
    _churn(idx, e)
    want = [_pos(*idx.search(q, "t", k=8)) for q in e[:5]]
    with tempfile.TemporaryDirectory() as ck:
        save_index(idx, ck)
        idx2 = load_index(ck)
    assert idx2.paged and idx2.state.row_map is not None
    np.testing.assert_array_equal(np.asarray(idx.state.row_map),
                                  np.asarray(idx2.state.row_map))
    np.testing.assert_array_equal(np.asarray(idx.state.inv_map),
                                  np.asarray(idx2.state.inv_map))
    assert int(idx2._ptable.free_top) == int(idx._ptable.free_top)
    assert idx2._pager.page_stats() == idx._pager.page_stats()
    got = [_pos(*idx2.search(q, "t", k=8)) for q in e[:5]]
    assert got == want
    # the restored free list still allocates: freed slot is reused
    pool = idx2.state.emb.shape[0]
    idx2.delete(["g0", "g1"])
    free_after_del = idx2.stats()["paged"]["pages_free"]
    _add(idx2, ["post0", "post1"], e[124:126], ts=9.0)
    assert idx2.state.emb.shape[0] == pool
    assert idx2.stats()["paged"]["pages_free"] <= free_after_del
    (ids, scores) = idx2.search(e[124], "t", k=3)
    assert _pos(ids, scores)[0][0] == "post0"
    assert idx2.telemetry.counter_total("arena.page_mirror_mismatches") == 0


def test_paged_checkpoint_rejects_mesh_load():
    e = _corpus(40)
    idx = MemoryIndex(dim=D, capacity=CAP, paged=True, page_rows=8)
    _add(idx, [f"m{i}" for i in range(16)], e[:16])
    with tempfile.TemporaryDirectory() as ck:
        save_index(idx, ck)
        import jax

        from lazzaro_tpu.parallel.mesh import make_mesh
        with pytest.raises(ValueError, match="single-chip"):
            load_index(ck, mesh=make_mesh(("data",), (2,),
                                          jax.devices()[:2]))
