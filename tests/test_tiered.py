"""Tiered memory (ISSUE 8): HBM hot set + host cold tier.

Acceptance pins:
- mixed hot/cold serving is IDENTICAL to the all-hot fused path on the
  same fixture — bit-identical scores against the quant path (the tiered
  rescore is the same gathered-row einsum), same ids/ranking/gate
  verdicts in every mode, and bit-identical boost columns (salience /
  access_count / last_accessed) — across exact, quant, and IVF modes and
  a 2-way mesh;
- hot-only turns cost exactly ONE dispatch; a turn whose candidate window
  touches cold rows costs exactly TWO (coarse scan + bounded finish);
- checkpoint round-trip carries the residency column and cold-store
  contents, and the reloaded index serves bit-identically;
- the pump: watermark-driven demotion, hysteresis after promotion,
  access-driven promotion at the hit threshold, write/delete hooks.
"""

import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.serve.scheduler import RetrievalRequest
from lazzaro_tpu.tier import ColdStore, TierManager, TierPump

D = 32
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02, now=1234.5)


def _vecs(n, seed, base_axis=None, spread=0.5):
    r = np.random.default_rng(seed)
    nz = r.standard_normal((n, D)).astype(np.float32)
    if base_axis is None:
        return nz / np.linalg.norm(nz, axis=1, keepdims=True)
    nz *= spread / np.linalg.norm(nz, axis=1, keepdims=True)
    base = np.zeros(D, np.float32)
    base[base_axis] = 1.0
    v = base[None, :] + nz
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _fill(idx, n=200, seed=0, edges=True, supers=False):
    emb = _vecs(n, seed)
    ids = [f"n{i}" for i in range(n)]
    sup = [supers and i % 29 == 0 for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
            ["default"] * n, "u0", is_super=sup)
    if edges:
        idx.add_edges([(f"n{i}", f"n{i + 1}", 0.7) for i in range(n - 1)],
                      "u0")
    return emb


def _reqs(emb, nq=8, k=10, boost=True, seed=9):
    r = np.random.default_rng(seed)
    q = emb[:nq] + 0.01 * r.standard_normal((nq, D)).astype(np.float32)
    return [RetrievalRequest(query=q[i], tenant="u0", k=k,
                             gate_enabled=True, boost=boost)
            for i in range(nq)]


def _assert_results_equal(a_list, b_list, bitwise_scores=True):
    for a, b in zip(a_list, b_list):
        assert a.ids == b.ids
        if bitwise_scores:
            assert a.scores == b.scores
        else:
            assert np.allclose(a.scores, b.scores, atol=2e-6)
        assert a.fast == b.fast
        assert a.gate_id == b.gate_id


def _assert_boost_columns_equal(ia, ib):
    for col in ("salience", "access_count", "last_accessed"):
        assert np.array_equal(np.asarray(getattr(ia.state, col)),
                              np.asarray(getattr(ib.state, col))), col


# --------------------------------------------------------------- cold store
def test_cold_store_roundtrip_and_growth():
    import ml_dtypes

    cs = ColdStore(D, dtype=ml_dtypes.bfloat16, initial_slots=4)
    v = _vecs(40, 1).astype(ml_dtypes.bfloat16)
    rows = list(range(5, 45))
    cs.put(rows, v, np.ones((40, D), np.int8),
           np.arange(40, dtype=np.float32))
    assert len(cs) == 40                   # grew past 4 initial slots
    got = cs.gather([7, 5, 44])
    assert got.dtype == np.dtype(ml_dtypes.bfloat16)
    assert got.view(np.uint16).tolist() == \
        v[[2, 0, 39]].view(np.uint16).tolist()   # bit-exact round trip
    cs.drop([7])
    assert 7 not in cs and len(cs) == 39
    r, codes, scales = cs.snapshot_codes()
    assert len(r) == 39 and codes.shape == (39, D)


def test_cold_store_memmap(tmp_path):
    cs = ColdStore(D, dtype=np.float32, path=str(tmp_path / "cold.bin"),
                   initial_slots=4)
    v = _vecs(10, 2)
    cs.put(list(range(10)), v, np.zeros((10, D), np.int8),
           np.zeros(10, np.float32))
    assert np.array_equal(cs.gather([3])[0], v[3])
    cs.put([99], v[:1], np.zeros((1, D), np.int8),
           np.zeros(1, np.float32))       # grows the mapped file
    assert np.array_equal(cs.gather([99])[0], v[0])


# --------------------------------------------------- demote / promote cycle
def test_demote_promote_restores_exact_bytes():
    idx = MemoryIndex(dim=D, capacity=255, dtype=jnp.bfloat16,
                      int8_serving=True)
    _fill(idx, edges=False)
    before = np.asarray(idx.state.emb).copy()
    tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
    cold = [idx.id_to_row[f"n{i}"] for i in range(100, 200)]
    assert tm.demote_rows(cold) == 100
    emb = np.asarray(idx.state.emb)
    assert not emb[cold].any()             # master surrendered
    assert tm.cold_count == 100
    assert tm.promote_rows(cold) == 100
    after = np.asarray(idx.state.emb)
    # every REAL row round-trips bit-exact (the sentinel scratch row is
    # fair game for the padded scatters, like every other kernel)
    cap = idx.state.capacity
    assert np.array_equal(before[:cap].view(np.uint16),
                          after[:cap].view(np.uint16))
    assert tm.cold_count == 0


def test_super_rows_are_pinned_hot():
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True)
    _fill(idx, supers=True)
    tm = idx.enable_tiering(hot_budget_rows=16, hysteresis_s=0.0)
    tm.run_once(now=1.0)
    sup_rows = np.asarray(sorted(idx._super_rows))
    assert not tm.cold_np[sup_rows].any()


# ----------------------------------------------------------- serving parity
def _pair(int8, tiering_on, ivf=0, mesh=None, slack=512, supers=True):
    # pinned epoch: the parity asserts compare boost columns BITWISE, and
    # with the fixed now=1234.5 a wall-clock epoch makes last_accessed
    # = now - epoch ≈ -1.8e9 — bit-equal only while both ctors' epochs
    # round into the same 128-second f32 bucket (a phase-of-the-suite
    # flake)
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=int8,
                      coarse_slack=slack, ivf_nprobe=ivf, mesh=mesh,
                      epoch=1000.0)
    emb = _fill(idx, supers=supers)
    if tiering_on:
        tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
        tm.demote_rows([idx.id_to_row[f"n{i}"] for i in range(100, 200)])
        assert tm.cold_count > 90          # supers among them stay hot
    return idx, emb


def test_parity_quant_mode_bitwise():
    """Mixed hot/cold vs all-hot QUANT fused: the tiered rescore is the
    same gathered-row einsum, so scores are bit-identical — and so are
    the boost columns the two serves scatter."""
    idx_t, emb = _pair(int8=True, tiering_on=True)
    idx_h, _ = _pair(int8=True, tiering_on=False)
    r_t = idx_t.search_fused_requests(_reqs(emb), **KW)
    r_h = idx_h.search_fused_requests(_reqs(emb), **KW)
    assert any(r.cold_hits > 0 for r in r_t)   # the fixture IS mixed
    _assert_results_equal(r_t, r_h, bitwise_scores=True)
    _assert_boost_columns_equal(idx_t, idx_h)


def test_parity_exact_mode():
    """Mixed hot/cold vs all-hot EXACT fused: same ids/ranking/gate and
    boost columns; scores agree to f32 round-off (the exact kernel scores
    via one whole-arena matmul, the tiered path via the gathered-row
    einsum — different contraction shapes, same math)."""
    idx_t, emb = _pair(int8=False, tiering_on=True)
    idx_h, _ = _pair(int8=False, tiering_on=False)
    r_t = idx_t.search_fused_requests(_reqs(emb), **KW)
    r_h = idx_h.search_fused_requests(_reqs(emb), **KW)
    _assert_results_equal(r_t, r_h, bitwise_scores=False)
    _assert_boost_columns_equal(idx_t, idx_h)


def test_parity_ivf_mode():
    """Mixed hot/cold vs the all-hot fused IVF path at full probe width
    (nprobe == n_clusters ⇒ the IVF candidate set is the whole arena):
    tiering bypasses the centroid prefilter — it is the one structure
    that still covers demoted rows — and must return the same results."""
    n = 4500                               # above the IVF build minimum
    idx_t = MemoryIndex(dim=D, capacity=5000, int8_serving=True,
                        coarse_slack=5001, ivf_nprobe=4096)
    idx_h = MemoryIndex(dim=D, capacity=5000, int8_serving=True,
                        coarse_slack=5001, ivf_nprobe=4096)
    emb = _vecs(n, 0)
    ids = [f"n{i}" for i in range(n)]
    for i_ in (idx_t, idx_h):
        i_.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
               ["default"] * n, "u0")
        i_.add_edges([(f"n{j}", f"n{j + 1}", 0.7) for j in range(200)],
                     "u0")
        assert i_.ivf_maintenance(iters=2)
    tm = idx_t.enable_tiering(hot_budget_rows=1024, hysteresis_s=0.0)
    tm.demote_rows([idx_t.id_to_row[f"n{i}"] for i in range(2000, 4500)])
    reqs = _reqs(emb, nq=4)
    r_t = idx_t.search_fused_requests(reqs, **KW)
    r_h = idx_h.search_fused_requests(reqs, **KW)
    _assert_results_equal(r_t, r_h, bitwise_scores=False)
    _assert_boost_columns_equal(idx_t, idx_h)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_parity_mesh_2way_bitwise():
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    idx_t, emb = _pair(int8=True, tiering_on=True, mesh=mesh)
    idx_h, _ = _pair(int8=True, tiering_on=False, mesh=mesh)
    r_t = idx_t.search_fused_requests(_reqs(emb), **KW)
    r_h = idx_h.search_fused_requests(_reqs(emb), **KW)
    assert any(r.cold_hits > 0 for r in r_t)
    _assert_results_equal(r_t, r_h, bitwise_scores=True)
    _assert_boost_columns_equal(idx_t, idx_h)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_sharded_index_per_shard_cold_stores():
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])

    def build():
        si = ShardedMemoryIndex(mesh, dim=D, capacity=255,
                                int8_serving=True, coarse_slack=256,
                                cap_take=5, max_nbr=8)
        emb = _vecs(200, 0)
        # tenant affinity packs a tenant's rows into its home partition;
        # 200 rows overflow one 128-row partition, so the corpus — and
        # the demoted slab — genuinely spans both shards
        si.add([f"n{i}" for i in range(200)], emb, "u0")
        si.add_edges([(f"n{i}", f"n{i + 1}", 0.7) for i in range(199)])
        return si, emb

    si_t, emb = build()
    si_h, _ = build()
    tm = si_t.attach_tiering(hot_budget_rows=64, hysteresis_s=0.0)
    tm.demote_rows([si_t.id_to_row[f"n{i}"] for i in range(60, 200)])
    assert sum(len(s) for s in tm.stores) == 140
    assert all(len(s) > 0 for s in tm.stores)    # BOTH shards hold rows
    reqs = _reqs(emb, nq=6, k=8)
    r_t = si_t.serve_requests(reqs)
    r_h = si_h.serve_requests(reqs)
    for a, b in zip(r_t, r_h):
        assert a.ids == b.ids and a.scores == b.scores
    assert np.array_equal(np.asarray(si_t.state.salience),
                          np.asarray(si_h.state.salience))


def test_dense_demote_never_surfaces_in_exact_search():
    """Residency parity (ISSUE 18): a DENSE-layout demote zero-fills the
    master row but leaves it alive, so the plain exact scan used to
    surface demoted rows as a score-0.0 top-k tail (the paged layout
    frees the slot, so the two layouts diverged). With the cold column
    masked to -inf, demote is indistinguishable from delete on the
    exact serve — bitwise, full k-list — on one chip and a 2-way mesh."""
    from lazzaro_tpu.parallel.mesh import make_mesh

    meshes = [None]
    if len(jax.devices()) >= 2:
        meshes.append(make_mesh(("data",), (2,), devices=jax.devices()[:2]))
    demoted = sorted(f"n{i}" for i in range(100, 200))
    for mesh in meshes:
        idx_d = MemoryIndex(dim=D, capacity=255, mesh=mesh, epoch=1000.0)
        emb = _fill(idx_d, edges=False)
        tm = idx_d.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
        assert tm.demote_rows([idx_d.id_to_row[i] for i in demoted]) == 100
        idx_x = MemoryIndex(dim=D, capacity=255, mesh=mesh, epoch=1000.0)
        _fill(idx_x, edges=False)
        idx_x.delete(demoted)
        for q in emb[100:106]:      # queries aimed AT the demoted slab
            ids_d, sc_d = idx_d.search(q, "u0", k=20)
            ids_x, sc_x = idx_x.search(q, "u0", k=20)
            assert not (set(ids_d) & set(demoted))
            assert ids_d == ids_x
            assert sc_d == sc_x     # bitwise: same masked score vector


# --------------------------------------------------------- dispatch counts
def _count_tier_dispatches(monkeypatch):
    calls = {"scan": 0, "finish": 0}
    for name in ("search_fused_tiered_ragged",
                 "search_fused_tiered_ragged_copy",
                 "search_fused_tiered_ragged_read"):
        orig = getattr(S, name)

        def w(*a, __o=orig, **k):
            calls["scan"] += 1
            return __o(*a, **k)

        monkeypatch.setattr(S, name, w)
    for name in ("tier_cold_finish", "tier_cold_finish_copy",
                 "tier_cold_rescore"):
        orig = getattr(S, name)

        def w2(*a, __o=orig, **k):
            calls["finish"] += 1
            return __o(*a, **k)

        monkeypatch.setattr(S, name, w2)
    return calls


def test_hot_only_turn_is_one_dispatch_cold_turn_two(monkeypatch):
    """The tiered serving contract: a turn whose coarse candidate window
    is all-hot stays ONE dispatch + ONE readback; a cold-hit turn pays
    exactly ONE bounded finish dispatch more."""
    idx = MemoryIndex(dim=D, capacity=511, int8_serving=True,
                      serve_k_max=16)
    n_hot, n_cold = 120, 280
    hot = _vecs(n_hot, 1, base_axis=0)
    cold = _vecs(n_cold, 2, base_axis=1)
    emb = np.concatenate([hot, cold])
    ids = [f"n{i}" for i in range(n_hot + n_cold)]
    idx.add(ids, emb, [0.5] * len(ids), [0.0] * len(ids),
            ["semantic"] * len(ids), ["default"] * len(ids), "u0")
    idx.add_edges([(f"n{i}", f"n{i + 1}", 0.7) for i in range(50)], "u0")
    tm = idx.enable_tiering(hot_budget_rows=128, hysteresis_s=0.0)
    tm.demote_rows([idx.id_to_row[f"n{i}"]
                    for i in range(n_hot, n_hot + n_cold)])

    hot_q = _vecs(4, 3, base_axis=0)
    cold_q = _vecs(4, 4, base_axis=1)
    mk = lambda q: [RetrievalRequest(query=q[i], tenant="u0", k=8,  # noqa: E731
                                     gate_enabled=True, boost=True)
                    for i in range(len(q))]
    idx.search_fused_requests(mk(hot_q), **KW)     # warm
    idx.search_fused_requests(mk(cold_q), **KW)
    calls = _count_tier_dispatches(monkeypatch)

    res = idx.search_fused_requests(mk(hot_q), **KW)
    assert calls == {"scan": 1, "finish": 0}       # ONE dispatch, all hot
    assert all(r.cold_hits == 0 for r in res)

    calls["scan"] = calls["finish"] = 0
    res = idx.search_fused_requests(mk(cold_q), **KW)
    assert calls == {"scan": 1, "finish": 1}       # exactly TWO
    assert any(r.cold_hits > 0 for r in res)
    assert tm.cold_turns >= 4
    assert 0.0 < (tm.cold_turns / tm.turns) <= 1.0


# ------------------------------------------------------------------ pump
def test_pump_watermarks_hysteresis_and_promotion():
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True)
    n = 200
    emb = _vecs(n, 0)
    ids = [f"n{i}" for i in range(n)]
    sal = [0.9 if i < 50 else 0.1 for i in range(n)]
    idx.add(ids, emb, sal, [0.0] * n, ["semantic"] * n, ["default"] * n,
            "u0")
    tm = idx.enable_tiering(hot_budget_rows=100, high_watermark=0.9,
                            low_watermark=0.75, promote_hits=2,
                            hysteresis_s=1000.0)
    # 200 hot > 0.9 * 100 → demote down to 75 hot, coldest-first
    out = tm.run_once(now=0.0)
    assert out["demoted"] == 125
    assert tm.hot_rows == 75
    hot_rows = [idx.id_to_row[f"n{i}"] for i in range(50)]
    assert not tm.cold_np[np.asarray(hot_rows)].any()   # high-sal survived

    # access-driven promotion: below the hit threshold nothing queues
    cold_row = int(np.flatnonzero(tm.cold_np)[0])
    tm.note_cold_hits([cold_row])
    assert cold_row not in tm._promote_queue
    tm.note_cold_hits([cold_row])
    assert cold_row in tm._promote_queue
    out = tm.run_once(now=1.0)
    assert out["promoted"] == 1 and not tm.cold_np[cold_row]
    # hysteresis: the promoted row is demotion-immune inside the window
    cand = tm.select_demotion_candidates(200, now=2.0)
    assert cold_row not in cand
    # ... and demotable again after it expires
    cand = tm.select_demotion_candidates(200, now=5000.0)
    assert cold_row in cand


def test_pump_thread_and_per_pass_cap():
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True)
    _fill(idx, edges=False)
    tm = idx.enable_tiering(hot_budget_rows=64, high_watermark=1.0,
                            low_watermark=1.0, hysteresis_s=0.0)
    tm.max_demote_per_pass = 50
    out = tm.run_once(now=0.0)
    assert out["demoted"] == 50            # the cap spreads the drain
    pump = TierPump(tm, interval_s=0.01).start()
    try:
        import time as _t
        deadline = _t.time() + 20.0
        while tm.hot_rows > 64 and _t.time() < deadline:
            _t.sleep(0.02)
    finally:
        pump.stop()
    assert tm.hot_rows == 64


def test_write_and_delete_hooks_clear_residency():
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True)
    emb = _fill(idx, edges=False)
    tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
    r_cold = idx.id_to_row["n150"]
    tm.demote_rows([r_cold, idx.id_to_row["n151"]])
    assert tm.cold_np[r_cold]
    # re-add writes a fresh embedding → the cold residue must drop
    idx.add(["n150"], emb[150:151], [0.5], [0.0], ["semantic"],
            ["default"], "u0")
    assert not tm.cold_np[r_cold] and r_cold not in tm.stores[0]
    # delete frees the row AND its cold-store slot
    r151 = idx.id_to_row["n151"]
    idx.delete(["n151"])
    assert not tm.cold_np[r151] and tm.cold_count == 0
    # a freed-then-reused row starts hot
    idx.add(["fresh"], emb[0:1], [0.5], [0.0], ["semantic"], ["default"],
            "u0")
    assert not tm.cold_np[idx.id_to_row["fresh"]]


def test_get_embedding_serves_cold_rows_from_store():
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True)
    emb = _fill(idx, edges=False)
    stored = np.asarray(idx.state.emb[idx.id_to_row["n7"]], np.float32)
    tm = idx.enable_tiering(hot_budget_rows=64)
    tm.demote_rows([idx.id_to_row["n7"]])
    got = idx.get_embedding("n7")
    assert np.array_equal(got, stored)


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_mixed_hot_cold_bit_identical():
    """Save/load carries the residency column + cold-store contents, and
    the reloaded index serves BIT-IDENTICAL results on a mixed fixture."""
    from lazzaro_tpu.core.checkpoint import load_index, save_index

    idx = MemoryIndex(dim=D, capacity=255, dtype=jnp.bfloat16,
                      int8_serving=True, coarse_slack=256)
    emb = _fill(idx, supers=True)
    tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0,
                            high_watermark=0.8, low_watermark=0.5)
    tm.demote_rows([idx.id_to_row[f"n{i}"] for i in range(100, 200)])
    reqs = _reqs(emb, boost=False)
    before = idx.search_fused_requests(reqs, **KW)
    with tempfile.TemporaryDirectory() as tmp:
        save_index(idx, tmp)
        back = load_index(tmp)
    assert back.tiering is not None
    assert back.tiering.cold_count == tm.cold_count
    assert np.array_equal(back.tiering.cold_np, tm.cold_np)
    assert back.tiering.high_watermark == 0.8       # policy knobs survive
    after = back.search_fused_requests(reqs, **KW)
    _assert_results_equal(before, after, bitwise_scores=True)
    # cold-store payload is byte-identical
    a = tm.stores[0].snapshot_all()
    b = back.tiering.stores[0].snapshot_all()
    oa, ob = np.argsort(a[0]), np.argsort(b[0])
    assert np.array_equal(a[0][oa], b[0][ob])
    assert np.array_equal(a[1][oa], b[1][ob])
    assert np.array_equal(a[2][oa], b[2][ob])


def test_shadow_rebuild_patches_cold_codes():
    """A full shadow rebuild quantizes from the master — which holds ZEROS
    for cold rows. The cold store's codes must be patched back, or the
    coarse scan silently stops covering the cold tier."""
    idx = MemoryIndex(dim=D, capacity=255, int8_serving=True,
                      coarse_slack=256)
    emb = _fill(idx)
    tm = idx.enable_tiering(hot_budget_rows=64)
    tm.demote_rows([idx.id_to_row[f"n{i}"] for i in range(100, 200)])
    r = idx.search_fused_requests(_reqs(emb, nq=4, boost=False), **KW)
    idx._int8_dirty = True                 # force a full rebuild
    r2 = idx.search_fused_requests(_reqs(emb, nq=4, boost=False), **KW)
    _assert_results_equal(r, r2, bitwise_scores=True)
    # and a cold row is still findable at all
    q = np.asarray(tm.gather_cold([idx.id_to_row["n150"]])[0], np.float32)
    got = idx.search_fused_requests(
        [RetrievalRequest(query=q, tenant="u0", k=3)], **KW)[0]
    assert got.ids and got.ids[0] == "n150"
