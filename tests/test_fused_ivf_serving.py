"""Fused IVF serving (ISSUE 4; tier-1 smoke, CPU, small arenas).

With a published IVF build, the per-chat-turn retrieval sequence must STILL
run as ONE device program: ``state.search_fused_ivf`` scores the query batch
against the centroids, gathers the top-``nprobe`` clusters' member rows plus
the exact-scan extras (sealed+fresh residual, super rows), scores only those
candidates (exact, or int8-gathered coarse + exact rescore with the shadow
on), and runs the super gate / CSR neighbor gather / boost scatter tail
unchanged. These tests count the actual jit entry points in IVF mode, pin
recall@10 parity against the classic multi-dispatch IVF path on a clustered
10k fixture at nprobe ∈ {4, 8}, check residual freshness (rows added
post-build are served through the fused path), pin boost-numerics parity
with the classic IVF path across gate-hit/gate-miss, and guard the
k-shortfall case where visited clusters hold fewer than k live rows.
"""

import tempfile

import numpy as np
import pytest

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.serve import RetrievalRequest
from tests.test_fused_ingest import ClusteredEmb, QueueLLM

D = 24


def _system(tmp, serve_fused=True, nprobe=4, per=20, super_threshold=100,
            int8=False):
    ms = MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=QueueLLM(per), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        super_node_threshold=super_threshold,
        config=MemoryConfig(journal=False, auto_consolidate=False,
                            decay_rate=0.0, ivf_serving=nprobe,
                            int8_serving=int8,
                            # tier-1 arenas are tiny: the ragged k ceiling
                            # must stay below the visited-candidate count
                            # or the IVF pack falls back to the dense scan
                            serve_k_max=16))
    ms.config.serve_fused = serve_fused
    return ms


def _ingest_built(ms, convs=2):
    """Ingest a couple of conversations, then force the IVF build the
    background maintenance hook would normally run once the arena passes
    ~4k rows (tier-1 arenas are tiny, so the threshold is lowered)."""
    for c in range(convs):
        ms.start_conversation()
        ms.add_to_short_term(f"conv {c}", "episodic", 0.7)
        ms.end_conversation()
    ms.index._IVF_MIN_ROWS = 1
    assert ms.index.ivf_maintenance()
    return ms


_COUNTED = ("search_fused_ivf_ragged", "search_fused_ivf_ragged_copy",
            "search_fused_ivf_ragged_read", "search_fused_quant_ragged",
            "search_fused_quant_ragged_copy",
            "search_fused_quant_ragged_read", "search_fused_ragged",
            "search_fused_ragged_copy", "search_fused_ragged_read",
            "arena_search", "arena_update_access",
            "arena_update_access_copy", "arena_boost", "arena_boost_copy",
            "arena_apply_boosts", "arena_apply_boosts_copy")


def _count_dispatches(monkeypatch):
    calls = {name: 0 for name in _COUNTED}
    for name in _COUNTED:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    return calls


def test_one_ivf_dispatch_per_chat_turn(monkeypatch):
    """The jit-call counter: with a published IVF build, a chat turn's
    retrieval (centroid prefilter + member gather + gate + neighbor boost
    + access boost) costs exactly ONE device dispatch — the donated
    ``search_fused_ivf`` program — and zero dense/quant/classic search or
    boost dispatches."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 3 body")                 # warm: compiles the kernel
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")
        assert calls["search_fused_ivf_ragged"] == 1   # donated single-writer
        for name in calls:
            if name != "search_fused_ivf_ragged":
                assert calls[name] == 0, (name, calls)
        ms.close()


def test_ivf_search_memories_takes_readonly_twin(monkeypatch):
    """A pure IVF read batch must take ``search_fused_ivf_read`` — same
    coarse prefilter + candidate scan, no donation dance, ONE dispatch per
    coalesced batch."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.search_memories("fact 1 body")      # warm the kernel
        calls = _count_dispatches(monkeypatch)
        hits = ms.search_memories("fact 3 body")
        assert hits
        assert calls["search_fused_ivf_ragged_read"] == 1
        assert calls["search_fused_ivf_ragged"] == 0
        ms.search_memories_batch([f"fact {i} body" for i in range(8)])
        assert calls["search_fused_ivf_ragged_read"] == 2
        ms.close()


def test_ivf_cached_hit_turn_pays_zero_dispatches(monkeypatch):
    """Zero-RTT query-cache hits survive IVF mode: a cached turn queues
    boost counts host-side and the flush stays ONE scatter."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest_built(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 7 body")                 # populates the query cache
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")                 # cache hit
        for name in calls:
            assert calls[name] == 0, (name, calls)
        assert ms._pending_boosts
        ms.end_conversation()
        assert calls["arena_apply_boosts"] == 1
        ms.close()


def _clustered_fixture(n=10_000, d=48, n_centers=64, seed=42, spread=0.5):
    """Genuinely clustered unit vectors: ``spread`` is the TOTAL noise norm
    relative to the unit center (per-dim noise would swamp the center at
    this d), so intra-cluster cosine ≈ 1/sqrt(1+spread²) ≈ 0.89."""
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
def test_fused_ivf_recall_parity_with_classic_ivf_10k(nprobe):
    """recall@10 vs the exact ranking on a clustered 10k fixture: the fused
    single-dispatch IVF path must be at least as good as the classic
    multi-dispatch IVF path (``search_batch`` routing through
    ``_ivf_search``) — both assemble the SAME candidate set
    (``ops.ivf.gather_rows``) and score it exactly, so fused recall can
    only differ through the in-kernel dedup, which mirrors the host
    decode's."""
    n, d, k, nq = 10_000, 48, 10, 64
    rng, emb = _clustered_fixture(n=n, d=d)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=nprobe)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    base = rng.integers(0, n, size=nq)
    queries = emb[base] + (0.3 / np.sqrt(d)) * rng.standard_normal(
        (nq, d)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    truth = np.argsort(-(queries @ emb.T), axis=1)[:, :k]

    classic = idx.search_batch(queries, "u0", k=k)      # classic IVF path
    classic_rows = [[idx.id_to_row[i] for i in ids_] for ids_, _ in classic]

    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k)
            for i in range(nq)]
    fused = idx.search_fused_requests(reqs, cap_take=5, max_nbr=8,
                                      super_gate=0.4, acc_boost=0.05,
                                      nbr_boost=0.02)
    fused_rows = [[idx.id_to_row[i] for i in r.ids] for r in fused]

    r_classic = _recall(classic_rows, truth, k)
    r_fused = _recall(fused_rows, truth, k)
    assert r_fused >= r_classic - 1e-9, (r_fused, r_classic)
    assert r_fused >= 0.85, r_fused
    # no duplicate rows in any fused result (in-kernel dedup)
    for rows in fused_rows:
        assert len(rows) == len(set(rows))


def test_ivf_residual_freshness_through_fused_path():
    """Rows added AFTER the build land in the fresh residual and must be
    served exactly through the fused kernel (the extras array carries
    them) — and a rebuilt residual cache can never hide them."""
    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=7)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=4)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    # post-build rows: orthogonal one-hot vectors, far from every centroid
    fresh = np.zeros((4, d), np.float32)
    for i in range(4):
        fresh[i, i] = 1.0
    idx.add([f"f{i}" for i in range(4)], fresh, [0.5] * 4, [0.0] * 4,
            ["semantic"] * 4, ["default"] * 4, "u0")
    reqs = [RetrievalRequest(query=fresh[i], tenant="u0", k=3)
            for i in range(4)]
    res = idx.search_fused_requests(reqs, cap_take=3, max_nbr=8,
                                    super_gate=0.4, acc_boost=0.05,
                                    nbr_boost=0.02)
    for i, r in enumerate(res):
        assert r.ids and r.ids[0] == f"f{i}", (i, r.ids)
        assert r.scores[0] > 0.999


def _numeric_cols(ms):
    cols = ms.index.pull_numeric()
    n = len(ms.index.id_to_row)
    return {k: cols[k][: n + 2] for k in ("salience", "access_count")}


def test_ivf_matches_classic_ivf_chat_turns():
    """Gate-miss boost parity: ids and boost side effects (salience +
    access counts on the arena AND host copies) match the classic
    multi-dispatch IVF serving path for plain ANN turns — including
    repeated (cached) turns."""
    a = _ingest_built(_system(tempfile.mkdtemp(), serve_fused=True))
    b = _ingest_built(_system(tempfile.mkdtemp(), serve_fused=False))
    try:
        a.start_conversation()
        b.start_conversation()
        for q in ("fact 3 body", "fact 17 body", "fact 31 body",
                  "fact 3 body"):             # last one is a cache hit
            ra = a.chat(q)
            rb = b.chat(q)
            assert ra == rb
        a.end_conversation()
        b.end_conversation()
        ca, cb = _numeric_cols(a), _numeric_cols(b)
        np.testing.assert_allclose(ca["salience"], cb["salience"], atol=1e-6)
        np.testing.assert_array_equal(ca["access_count"], cb["access_count"])
        ha = {n: (round(a.buffer.nodes[n].salience, 5),
                  a.buffer.nodes[n].access_count) for n in a.buffer.nodes}
        hb = {n: (round(b.buffer.nodes[n].salience, 5),
                  b.buffer.nodes[n].access_count) for n in b.buffer.nodes}
        assert ha == hb
    finally:
        a.close()
        b.close()


def test_ivf_matches_classic_super_gate_hit():
    """Gate-hit parity in IVF mode: the extras array carries EVERY super
    row, so the in-kernel gate top-1 is exact regardless of centroid
    routing — the device skips boosts exactly when the classic exact gate
    search would have fired, and the host fast path serves identical
    children."""
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
        assert mode_a == "classic"             # device skipped boosts
        assert mode_b == "classic"
        children = a.super_nodes[sid].child_ids
        assert ids_a[0] == children[0]
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


def test_ivf_k_shortfall_guard():
    """Visited clusters holding fewer than k live rows must yield exactly
    the live candidates — never phantom rows, never duplicates, never a
    crash — and deleted member rows must not surface."""
    n, d, k = 256, 16, 10
    rng = np.random.default_rng(9)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    idx = MemoryIndex(dim=d, capacity=511, ivf_nprobe=1)
    ids = [f"m{i}" for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
            ["default"] * n, "u0")
    idx._IVF_MIN_ROWS = 1
    assert idx.ivf_maintenance()
    # kill most of the arena so any visited cluster is nearly empty
    dead = ids[: n - 12]
    idx.delete(dead)
    res = idx.search_fused_requests(
        [RetrievalRequest(query=emb[n - 1], tenant="u0", k=k)],
        cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
        nbr_boost=0.02)
    got = res[0].ids
    assert got, "shortfall must not empty the result"
    assert len(got) == len(set(got))           # no duplicates
    assert len(got) <= k
    live = set(ids[n - 12:])
    assert all(g in live for g in got), got    # no dead rows surface


def test_ivf_int8_composition_single_dispatch(monkeypatch):
    """IVF + int8 shadow together: the candidate scan inside the fused IVF
    program becomes two-stage (int8 gathered coarse + exact rescore) and
    the turn is STILL one ``search_fused_ivf`` dispatch with exact top-1
    self-hits."""
    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=13)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=4,
                      int8_serving=True)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    assert idx.ivf_maintenance()
    reqs = [RetrievalRequest(query=emb[i], tenant="u0", k=5)
            for i in range(8)]
    kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02)
    idx.search_fused_requests(reqs, **kw)      # warm + shadow build
    calls = _count_dispatches(monkeypatch)
    res = idx.search_fused_requests(reqs, **kw)
    assert calls["search_fused_ivf_ragged_read"] == 1
    assert sum(calls.values()) == 1
    for i, r in enumerate(res):
        assert r.ids[0] == f"m{i}"             # exact rescore self-hit
        assert r.scores[0] > 0.999             # no quantization error


def test_ivf_multi_tenant_batch_isolation():
    """One coalesced IVF batch serving several tenants keeps isolation:
    the per-request tenant column masks the gathered candidates."""
    n, d = 5_000, 32
    rng, emb = _clustered_fixture(n=n, d=d, seed=21)
    idx = MemoryIndex(dim=d, capacity=n + 64, ivf_nprobe=4)
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    idx.add(["alien"], emb[:1], [0.9], [0.0], ["semantic"], ["default"],
            "t2")
    assert idx.ivf_maintenance()
    reqs = [RetrievalRequest(query=emb[0], tenant="u0", k=5),
            RetrievalRequest(query=emb[0], tenant="t2", k=5)]
    res = idx.search_fused_requests(reqs, cap_take=5, max_nbr=8,
                                    super_gate=0.4, acc_boost=0.05,
                                    nbr_boost=0.02)
    assert res[0].ids and res[0].ids[0] == "m0"
    assert "alien" not in res[0].ids
    assert res[1].ids == ["alien"]


def test_no_build_falls_back_to_dense_fused(monkeypatch):
    """IVF configured but not yet built: ``search_fused_requests`` serves
    the dense fused kernel (still one dispatch) instead of bailing out of
    fusion — builds belong to background maintenance, never the query
    path."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _system(tmp)
        for c in range(2):
            ms.start_conversation()
            ms.add_to_short_term(f"conv {c}", "episodic", 0.7)
            ms.end_conversation()
        assert ms.index._ivf is None           # below the build threshold
        ms.search_memories("fact 1 body")      # warm
        calls = _count_dispatches(monkeypatch)
        hits = ms.search_memories("fact 3 body")
        assert hits
        assert calls["search_fused_ragged_read"] == 1
        assert calls["search_fused_ivf_ragged_read"] == 0
        ms.close()
