"""Fused IVF-PQ serving (ISSUE 16; tier-1 smoke, CPU, small arenas).

The last serving mode outside the one-dispatch turn: with a COMPLETE
``(codebook, codes)`` pack published by maintenance, the chat turn's whole
retrieval — ADC table build, m-byte PQ member scan over the top-nprobe
clusters, exact f32 shortlist rescore at ``coarse_fetch_slack``, super
gate / CSR gather / boost tail — runs as ONE device program
(``state.search_fused_pq[_ragged]`` + ``_copy``/``_read`` twins). These
tests pin:

- the jit counters: ONE PQ dispatch per chat turn, the read twin for pure
  reads, ZERO dispatches on cached turns;
- recall@10 against the classic multi-dispatch ``ivf_pq_search`` path on
  a clustered 10k fixture at nprobe ∈ {4, 8};
- gate-verdict parity with the classic path (the 0.4 super-gate decision
  comes from the exact rescore, never the ADC approximation) across
  gate-hit and gate-miss turns, boost columns included;
- incremental codes: the fused ingest's in-kernel ``_pq_scatter`` keeps
  the pack current at ZERO added dispatches (no offline ``encode_pq``
  pass, no dirty flag anywhere);
- PQ × tiering: demote → serve → promote round-trips through the
  ``pq_tiered`` cold-shadow scan with no dense fallback;
- 2-way mesh parity: the row-sharded PQ member scan returns the same
  rows/scores as the sharded IVF exact scan over the same tables;
- checkpoint round trip: codebook + codes + the dirty-free invariant
  survive ``checkpoint.save_index``/``load_index``;
- member-table hole re-pack reclaims delete/demote holes and bumps
  ``ivf.member_repacks`` (satellite).
"""

import tempfile

import numpy as np
import pytest

import jax

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.serve import RetrievalRequest
from tests.test_fused_ingest import ClusteredEmb, QueueLLM

D = 24
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02)


def _system(tmp, serve_fused=True, nprobe=4, per=20, super_threshold=100):
    ms = MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=QueueLLM(per), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        super_node_threshold=super_threshold,
        config=MemoryConfig(journal=False, auto_consolidate=False,
                            decay_rate=0.0, ivf_serving=nprobe,
                            pq_serving=True,
                            # tiny tier-1 arenas: the ragged k ceiling must
                            # stay below the visited-candidate count or the
                            # PQ pack falls back to the dense scan
                            serve_k_max=16))
    ms.config.serve_fused = serve_fused
    return ms


def _ingest_built(ms, convs=2):
    for c in range(convs):
        ms.start_conversation()
        ms.add_to_short_term(f"conv {c}", "episodic", 0.7)
        ms.end_conversation()
    ms.index._IVF_MIN_ROWS = 1
    assert ms.index.ivf_maintenance()      # builds IVF AND the PQ pack
    assert ms.index._pq_pack is not None and ms.index._pq_pack[1] is not None
    return ms


_COUNTED = ("search_fused_pq_ragged", "search_fused_pq_ragged_copy",
            "search_fused_pq_ragged_read", "search_fused_ivf_ragged",
            "search_fused_ivf_ragged_copy", "search_fused_ivf_ragged_read",
            "search_fused_quant_ragged", "search_fused_quant_ragged_copy",
            "search_fused_quant_ragged_read", "search_fused_ragged",
            "search_fused_ragged_copy", "search_fused_ragged_read",
            "arena_search", "arena_update_access",
            "arena_update_access_copy", "arena_boost", "arena_boost_copy",
            "arena_apply_boosts", "arena_apply_boosts_copy")


def _count_dispatches(monkeypatch, names=_COUNTED):
    calls = {name: 0 for name in names}
    for name in names:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    return calls


# ------------------------------------------------------------ jit counters
def test_one_pq_dispatch_per_chat_turn(monkeypatch):
    """A chat turn with a published PQ pack costs exactly ONE device
    dispatch — the donated ``search_fused_pq_ragged`` program — and zero
    IVF/quant/dense/classic search or boost dispatches."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 3 body")             # warm: compiles the kernel
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")
        assert calls["search_fused_pq_ragged"] == 1
        for name in calls:
            if name != "search_fused_pq_ragged":
                assert calls[name] == 0, (name, calls)
        ms.close()


def test_pq_search_memories_takes_readonly_twin(monkeypatch):
    """A pure read batch takes ``search_fused_pq_ragged_read`` — same ADC
    member scan, no donation dance, ONE dispatch per coalesced batch."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.search_memories("fact 1 body")  # warm the kernel
        calls = _count_dispatches(monkeypatch)
        hits = ms.search_memories("fact 3 body")
        assert hits
        assert calls["search_fused_pq_ragged_read"] == 1
        assert calls["search_fused_pq_ragged"] == 0
        ms.search_memories_batch([f"fact {i} body" for i in range(8)])
        assert calls["search_fused_pq_ragged_read"] == 2
        ms.close()


def test_pq_cached_hit_turn_pays_zero_dispatches(monkeypatch):
    """Zero-RTT query-cache hits survive PQ mode: a cached turn queues
    boost counts host-side and the flush stays ONE scatter."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 7 body")             # populates the query cache
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")             # cache hit
        for name in calls:
            assert calls[name] == 0, (name, calls)
        assert ms._pending_boosts
        ms.end_conversation()
        assert calls["arena_apply_boosts"] == 1
        ms.close()


# ------------------------------------------------------------------ recall
def _clustered_fixture(n=10_000, d=48, n_centers=64, seed=42, spread=0.5):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    lbl = rng.integers(0, n_centers, n)
    emb = centers[lbl] + (spread / np.sqrt(d)) * rng.standard_normal(
        (n, d)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    return rng, emb


def _recall(result_rows, truth_rows, k):
    hits = sum(len(set(r) & set(t[:k])) for r, t in
               zip(result_rows, truth_rows))
    return hits / (k * len(result_rows))


@pytest.mark.parametrize("nprobe", [4, 8])
def test_fused_pq_recall_parity_with_classic_pq_10k(nprobe):
    """recall@10 vs the exact ranking on a clustered 10k fixture: the
    fused single-dispatch PQ path must hold its own against the classic
    multi-dispatch ``ivf_pq_search`` routing (``search_batch``). Both
    scan the same m-byte codes over the same candidate set and rescore
    exactly; the classic path refines a deeper shortlist (r=128 vs
    k+slack), so the fused path gets a small allowance."""
    n, d, k, nq = 10_000, 48, 10, 64
    rng, emb = _clustered_fixture(n=n, d=d)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=nprobe,
                      pq_serving=True, coarse_slack=32)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    assert idx._pq_pack is not None and idx._pq_pack[1] is not None
    base = rng.integers(0, n, size=nq)
    queries = emb[base] + (0.3 / np.sqrt(d)) * rng.standard_normal(
        (nq, d)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    truth = np.argsort(-(queries @ emb.T), axis=1)[:, :k]

    classic = idx.search_batch(queries, "u0", k=k)   # classic ivf_pq_search
    classic_rows = [[idx.id_to_row[i] for i in ids_] for ids_, _ in classic]

    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k)
            for i in range(nq)]
    fused = idx.search_fused_requests(reqs, **KW)
    fused_rows = [[idx.id_to_row[i] for i in r.ids] for r in fused]

    r_classic = _recall(classic_rows, truth, k)
    r_fused = _recall(fused_rows, truth, k)
    assert r_fused >= 0.9, (r_fused, r_classic)
    assert r_fused >= r_classic - 0.03, (r_fused, r_classic)
    for rows in fused_rows:                # in-kernel dedup: no duplicates
        assert len(rows) == len(set(rows))
    # exact rescore: self-queries return the row itself at ~1.0
    self_reqs = [RetrievalRequest(query=emb[i], tenant="u0", k=1)
                 for i in range(8)]
    res = idx.search_fused_requests(self_reqs, **KW)
    for i, r in enumerate(res):
        assert r.ids[0] == f"m{i}"
        assert abs(r.scores[0] - 1.0) < 5e-3


# ----------------------------------------------------- gate-verdict parity
def _numeric_cols(ms):
    cols = ms.index.pull_numeric()
    n = len(ms.index.id_to_row)
    return {k: cols[k][: n + 2] for k in ("salience", "access_count")}


def test_pq_matches_classic_chat_turns():
    """Gate-miss parity: ids and boost side effects (salience + access
    counts on the arena AND host copies) match the classic multi-dispatch
    PQ serving path — including repeated (cached) turns. Both paths'
    verdicts come from the exact rescore, so ADC error never shows."""
    a = _ingest_built(_system(tempfile.mkdtemp(), serve_fused=True))
    b = _ingest_built(_system(tempfile.mkdtemp(), serve_fused=False))
    try:
        a.start_conversation()
        b.start_conversation()
        for q in ("fact 3 body", "fact 17 body", "fact 31 body",
                  "fact 3 body"):          # last one is a cache hit
            ra = a.chat(q)
            rb = b.chat(q)
            assert ra == rb
        a.end_conversation()
        b.end_conversation()
        ca, cb = _numeric_cols(a), _numeric_cols(b)
        np.testing.assert_allclose(ca["salience"], cb["salience"], atol=1e-6)
        np.testing.assert_array_equal(ca["access_count"], cb["access_count"])
    finally:
        a.close()
        b.close()


def test_pq_matches_classic_super_gate_hit():
    """Gate-hit parity: the extras array carries EVERY super row and the
    gate top-1 score is the exact rescore — the device skips boosts
    exactly when the classic exact gate search would have fired."""
    def build(serve_fused):
        ms = _ingest_built(_system(tempfile.mkdtemp(),
                                   serve_fused=serve_fused,
                                   super_threshold=5))
        assert ms.super_nodes
        return ms

    a, b = build(True), build(False)
    try:
        sid = sorted(a.super_nodes)[0]
        centroid = np.asarray(a.super_nodes[sid].embedding, np.float32)
        ids_a, mode_a = a._retrieve_for_chat(centroid.tolist(), "probe-q")
        ids_b, mode_b = b._retrieve_for_chat(centroid.tolist(), "probe-q")
        assert ids_a == ids_b
        assert mode_a == "classic"         # device skipped boosts
        assert mode_b == "classic"
        a.start_conversation()
        b.start_conversation()
        a.chat("fact 5 body")
        b.chat("fact 5 body")
        ca, cb = _numeric_cols(a), _numeric_cols(b)
        np.testing.assert_allclose(ca["salience"], cb["salience"], atol=1e-6)
        np.testing.assert_array_equal(ca["access_count"], cb["access_count"])
    finally:
        a.close()
        b.close()


# ------------------------------------------------------- incremental codes
_INGEST_COUNTED = ("ingest_dedup_fused", "ingest_dedup_fused_copy",
                   "arena_add", "arena_add_copy", "arena_merge_touch",
                   "arena_merge_touch_copy", "edges_add", "edges_add_copy",
                   "arena_search", "ivf_members_drop",
                   "ivf_members_drop_copy")


def test_incremental_codes_add_zero_ingest_dispatches(monkeypatch):
    """The in-kernel ``_pq_scatter`` keeps the pack current: one fused
    ingest mega-batch with a live PQ pack is STILL one dispatch (no
    offline ``encode_pq`` kernel beside it), and the new rows' codes land
    bit-identical to a from-scratch encode of the stored vectors."""
    from lazzaro_tpu.ops.pq import encode_pq

    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=3)
    idx = MemoryIndex(dim=d, capacity=n + 512, ivf_nprobe=4,
                      pq_serving=True)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    batch = emb[:16] + 0.1 * rng.standard_normal((16, d)).astype(np.float32)
    batch /= np.linalg.norm(batch, axis=1, keepdims=True)
    # warm the ingest kernel geometry first, then count
    pend = idx.ingest_batch_dedup(batch[:8], [0.5] * 8, [1.0] * 8,
                                  ["semantic"] * 8, ["s"] * 8, "u0",
                                  dedup_gate=1.01)
    idx.commit_ingest_dedup(pend, [f"w{i}" for i in range(8)])
    calls = _count_dispatches(monkeypatch, _INGEST_COUNTED)
    pend = idx.ingest_batch_dedup(batch[8:], [0.5] * 8, [1.0] * 8,
                                  ["semantic"] * 8, ["s"] * 8, "u0",
                                  dedup_gate=1.01)
    idx.commit_ingest_dedup(pend, [f"x{i}" for i in range(8)])
    assert calls["ingest_dedup_fused"] == 1
    for name in calls:
        if name != "ingest_dedup_fused":
            assert calls[name] == 0, (name, calls)
    pack = idx._pq_pack
    assert pack is not None and pack[1] is not None   # still complete
    rows = np.asarray([idx.id_to_row[f"x{i}"] for i in range(8)])
    want = np.asarray(encode_pq(pack[0].centroids, idx.state.emb[rows]))
    assert np.array_equal(np.asarray(pack[1])[rows], want)
    # and the fresh rows serve through the fused PQ path
    reqs = [RetrievalRequest(query=batch[8 + i], tenant="u0", k=3)
            for i in range(8)]
    res = idx.search_fused_requests(reqs, **KW)
    for i, r in enumerate(res):
        assert r.ids and r.ids[0] == f"x{i}"


# ------------------------------------------------------------- PQ × tiering
def _assert_results_equal(a_list, b_list):
    for a, b in zip(a_list, b_list):
        assert a.ids == b.ids
        assert np.allclose(a.scores, b.scores, atol=2e-6)
        assert a.fast == b.fast
        assert a.gate_id == b.gate_id


def test_pq_tiering_demote_promote_round_trip():
    """Mixed hot/cold vs all-hot fused PQ at full probe width: tiering
    swaps the cold coarse scan to the m-byte PQ slab (``pq_tiered``) —
    demoted rows keep serving with exact scores (their codes outlive the
    zeroed master), and a promote restores plain ``pq`` serving."""
    n, d = 4_500, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=17)

    def build():
        idx = MemoryIndex(dim=d, capacity=5000, ivf_nprobe=4096,
                          pq_serving=True, coarse_slack=64, epoch=1000.0)
        idx.add([f"n{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
                ["semantic"] * n, ["default"] * n, "u0")
        assert idx.ivf_maintenance(iters=2)
        return idx

    idx_t, idx_h = build(), build()
    assert idx_t._serve_route(5).mode == "pq"
    tm = idx_t.enable_tiering(hot_budget_rows=1024, hysteresis_s=0.0)
    cold = [idx_t.id_to_row[f"n{i}"] for i in range(2000, n)]
    assert tm.demote_rows(cold) == len(cold)
    assert idx_t._serve_route(5).mode == "pq_tiered"

    q = emb[list(range(8)) + list(range(2100, 2108))]
    reqs = [RetrievalRequest(query=q[i], tenant="u0", k=10,
                             gate_enabled=True, boost=False)
            for i in range(len(q))]
    r_t = idx_t.search_fused_requests(reqs, **KW)
    r_h = idx_h.search_fused_requests(reqs, **KW)
    assert any(r.cold_hits > 0 for r in r_t)   # the fixture IS mixed
    _assert_results_equal(r_t, r_h)
    # cold self-queries still land their own row with the exact score
    for i in range(8, 16):
        assert r_t[i].ids[0] == f"n{2100 + (i - 8)}"
        assert abs(r_t[i].scores[0] - 1.0) < 5e-3

    assert tm.promote_rows(cold) == len(cold)
    assert idx_t._serve_route(5).mode == "pq"
    r_t2 = idx_t.search_fused_requests(reqs, **KW)
    assert all(r.cold_hits == 0 for r in r_t2)
    _assert_results_equal(r_t2, r_h)


# ------------------------------------------------------------- mesh parity
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
def test_pq_mesh_2way_parity():
    """Pod PQ serving (row-sharded codes, replicated codebook) vs the
    sharded IVF exact scan over the SAME live tables: both rescore
    exactly, so top-1 must agree everywhere and the top-5 sets can only
    differ where the ADC coarse rank pushes a mid-rank row past the
    slack window."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh
    from lazzaro_tpu.serve.scheduler import RetrievalRequest as PodReq

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    rng, emb = _clustered_fixture(n=400, d=D, n_centers=16, seed=23)
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=1023, dtype=np.float32,
                             pq_serving=True, k=10)
    idx.add([f"m{i}" for i in range(400)], emb, "t")
    assert idx.ivf_build(n_clusters=16, nprobe=8)
    assert idx._pq_pack is not None

    reqs = [PodReq(query=emb[i], tenant="t", k=5) for i in range(16)]
    r_pq = idx.serve_requests(reqs)
    snap = idx.telemetry.snapshot()
    assert any("serve.dispatch_ms" in k_ and "pod_pq" in k_
               for k_ in snap["timers"])    # the PQ mode actually served
    idx.pq_serving = False                  # same tables, IVF exact scan
    r_ivf = idx.serve_requests(reqs)

    overlap = 0
    for a, b in zip(r_pq, r_ivf):
        assert a.ids[0] == b.ids[0]
        assert abs(a.scores[0] - b.scores[0]) < 5e-3
        overlap += len(set(a.ids) & set(b.ids))
    assert overlap >= 0.9 * 5 * len(reqs), overlap


# ------------------------------------------------------ checkpoint parity
def test_checkpoint_pq_roundtrip(tmp_path):
    """Codebook + codes + the dirty-free invariant survive
    ``checkpoint.save_index``/``load_index``: the restored pack is
    bit-identical and COMPLETE (no offline re-encode on load), the meta
    block mirrors the ``counters`` idiom, and the restored index keeps
    maintaining codes incrementally."""
    from lazzaro_tpu.core import checkpoint as C
    from lazzaro_tpu.ops.pq import encode_pq

    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=29)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=8, pq_serving=True)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    book0, codes0 = idx._pq_pack
    C.save_index(idx, str(tmp_path / "ckpt"))

    # the meta entry rides next to the counters block
    import json
    vdir = (tmp_path / "ckpt" / (tmp_path / "ckpt" / "CURRENT")
            .read_text().strip())
    meta = json.loads((vdir / "meta.json").read_text())
    assert meta["pq"] == {"m": int(book0.m), "dim": d, "complete": True}
    assert "counters" in meta

    idx2 = C.load_index(str(tmp_path / "ckpt"), ivf_nprobe=8,
                        pq_serving=True)
    pack = idx2._pq_pack
    assert pack is not None and pack[1] is not None   # complete on load
    assert np.array_equal(np.asarray(pack[0].centroids),
                          np.asarray(book0.centroids))
    assert np.array_equal(np.asarray(pack[1]), np.asarray(codes0))

    # restored index serves (after the maintenance pass republishes the
    # coarse tables) and still patches codes at write time — no dirty
    # flag resurrection
    assert idx2.ivf_maintenance()
    res = idx2.search_fused_requests(
        [RetrievalRequest(query=emb[7], tenant="u0", k=3)], **KW)
    assert res[0].ids[0] == "m7"
    fresh = np.zeros((1, d), np.float32)
    fresh[0, 5] = 1.0
    idx2.add(["fresh"], fresh, [0.5], [0.0], ["semantic"], ["default"],
             "u0")
    pack2 = idx2._pq_pack
    frow = idx2.id_to_row["fresh"]
    want = np.asarray(encode_pq(pack2[0].centroids,
                                idx2.state.emb[frow:frow + 1]))[0]
    assert np.array_equal(np.asarray(pack2[1])[frow], want)


# ------------------------------------------------------ member-table repack
def test_member_repack_reclaims_delete_holes():
    """Deleting member rows leaves dead slots behind the per-cluster
    cursors; ``ivf_member_repack`` compacts them in ONE host pass, bumps
    the counters, and the repacked tables keep serving the live rows."""
    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=31)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=8, pq_serving=True)
    ids = [f"m{i}" for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
            ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    occ0 = int(np.asarray(idx._ivf_dev[2]).sum())
    idx.delete(ids[: n // 2])              # half the pool becomes holes
    assert idx.ivf_member_repack(hole_frac=0.25)
    assert int(np.asarray(idx._ivf_dev[2]).sum()) < occ0
    snap = idx.telemetry.snapshot()
    assert any(k.startswith("ivf.member_repacks")
               for k in snap["counters"])
    assert any(k.startswith("ivf.member_holes_reclaimed")
               for k in snap["counters"])
    # no live row lost, no dead row surfaced
    live = set(ids[n // 2:])
    members = np.asarray(idx._ivf_dev[1])
    counts = np.asarray(idx._ivf_dev[2])
    for c in range(members.shape[0]):
        for s in range(int(counts[c])):
            assert idx.row_to_id[int(members[c, s])] in live
    res = idx.search_fused_requests(
        [RetrievalRequest(query=emb[n - 1], tenant="u0", k=3)], **KW)
    assert res[0].ids[0] == f"m{n - 1}"
    # below the hole threshold: a second call is a no-op
    assert not idx.ivf_member_repack(hole_frac=0.25)
