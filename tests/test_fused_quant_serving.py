"""Quantized fused serving (ISSUE 3; tier-1 smoke, CPU, tiny arena).

With the int8 serving shadow active, the per-chat-turn retrieval sequence
must STILL run as ONE device program: ``state.search_fused_quant`` streams
the int8 codes for a coarse top-(k+slack), exactly rescores the survivors
from the master arena, and runs the super gate / CSR neighbor gather /
boost scatter unchanged. These tests count the actual jit entry points in
int8 mode, pin recall@10 against the pre-existing int8 shadow path on a
10k-row fixture, and pin boost-numerics parity with the classic int8 path
across gate-hit / gate-miss / multi-tenant cases.
"""

import tempfile

import numpy as np
import pytest

import lazzaro_tpu.ops.quant as Q
from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.serve import RetrievalRequest
from tests.test_fused_ingest import ClusteredEmb, QueueLLM

D = 24


def _system(tmp, serve_fused=True, int8=True, per=20, super_threshold=100):
    ms = MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=QueueLLM(per), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        super_node_threshold=super_threshold,
        config=MemoryConfig(journal=False, auto_consolidate=False,
                            decay_rate=0.0, int8_serving=int8))
    ms.config.serve_fused = serve_fused
    return ms


def _ingest(ms, convs=2):
    for c in range(convs):
        ms.start_conversation()
        ms.add_to_short_term(f"conv {c}", "episodic", 0.7)
        ms.end_conversation()
    return ms


_COUNTED = ("search_fused_quant_ragged",
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
    # the classic int8 shadow scan must not fire either
    orig_qt = Q.quantized_topk
    calls["quantized_topk"] = 0

    def wrapped_qt(*a, **kw):
        calls["quantized_topk"] += 1
        return orig_qt(*a, **kw)

    monkeypatch.setattr(Q, "quantized_topk", wrapped_qt)
    return calls


def test_one_quant_dispatch_per_chat_turn(monkeypatch):
    """The jit-call counter: in int8 mode a chat turn's retrieval (coarse
    int8 scan + exact rescore + gate + neighbor boost + access boost) costs
    exactly ONE device dispatch — the donated ``search_fused_quant``
    program — and zero classic search/boost/shadow-scan dispatches."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 3 body")                 # warm: builds the int8 shadow
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")
        assert calls["search_fused_quant_ragged"] == 1  # donated single-writer
        for name in calls:
            if name != "search_fused_quant_ragged":
                assert calls[name] == 0, (name, calls)
        ms.close()


def test_quant_search_memories_takes_readonly_twin(monkeypatch):
    """A pure int8 read batch must take ``search_fused_quant_read`` — same
    two-stage compute, no donation dance, ONE dispatch per batch."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.search_memories("fact 1 body")      # warm the shadow + kernel
        calls = _count_dispatches(monkeypatch)
        hits = ms.search_memories("fact 3 body")
        assert hits
        assert calls["search_fused_quant_ragged_read"] == 1
        assert calls["search_fused_quant_ragged"] == 0
        assert calls["quantized_topk"] == 0
        ms.search_memories_batch([f"fact {i} body" for i in range(8)])
        assert calls["search_fused_quant_ragged_read"] == 2
        ms.close()


def test_quant_cached_hit_turn_pays_zero_dispatches(monkeypatch):
    """Zero-RTT query-cache hits survive quantized mode: a cached turn
    queues boost counts host-side and the flush stays ONE scatter."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
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


def _recall(result_ids_rows, truth_rows, k):
    hits = sum(len(set(r) & set(t[:k])) for r, t in
               zip(result_ids_rows, truth_rows))
    return hits / (k * len(result_ids_rows))


def test_quant_fused_recall_not_worse_than_shadow_path_10k():
    """recall@10 vs the exact ranking on a 10k-row fixture: the fused
    coarse-scan + exact-rescore path must be at least as good as the
    pre-existing pure-int8 shadow scan (`search_batch` in int8 mode) — the
    exact rescore can only fix int8 ranking errors inside the slack
    window, never introduce new ones."""
    n, d, k, nq = 10_000, 48, 10, 64
    rng = np.random.default_rng(42)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    idx = MemoryIndex(dim=d, capacity=n + 64, int8_serving=True)
    ids = [f"m{i}" for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
            ["default"] * n, "u0")
    # queries near (not on) arena rows so the top-10 boundary has real ties
    base = rng.integers(0, n, size=nq)
    queries = emb[base] + 0.35 * rng.standard_normal((nq, d)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    truth = np.argsort(-(queries @ emb.T), axis=1)[:, :k]

    shadow = idx.search_batch(queries, "u0", k=k)          # classic int8 path
    shadow_rows = [[idx.id_to_row[i] for i in ids_] for ids_, _ in shadow]

    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k)
            for i in range(nq)]
    fused = idx.search_fused_requests(reqs, cap_take=5, max_nbr=8,
                                      super_gate=0.4, acc_boost=0.05,
                                      nbr_boost=0.02)
    fused_rows = [[idx.id_to_row[i] for i in r.ids] for r in fused]

    r_shadow = _recall(shadow_rows, truth, k)
    r_fused = _recall(fused_rows, truth, k)
    assert r_fused >= r_shadow, (r_fused, r_shadow)
    assert r_fused >= 0.95, r_fused


def test_quant_matches_classic_int8_chat_turns():
    """Ids and boost side effects (salience + access counts on the arena
    AND host copies) match the classic int8 serving path for plain ANN
    turns — including repeated (cached) turns."""
    a = _ingest(_system(tempfile.mkdtemp(), serve_fused=True))
    b = _ingest(_system(tempfile.mkdtemp(), serve_fused=False))
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

        def cols(ms):
            c = ms.index.pull_numeric()
            nn = len(ms.index.id_to_row)
            return {k: c[k][: nn + 2] for k in ("salience", "access_count")}

        ca, cb = cols(a), cols(b)
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


def test_quant_matches_classic_int8_super_gate_hit():
    """Gate-hit parity in int8 mode: the fused kernel's gate verdict uses
    the EXACT rescored super score (the 0.4 threshold is quantization-
    sensitive), so the device skips boosts exactly when the classic exact
    gate search would have fired, and the host fast path serves identical
    children."""
    def build(serve_fused):
        ms = _ingest(_system(tempfile.mkdtemp(), serve_fused=serve_fused,
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

        def cols(ms):
            c = ms.index.pull_numeric()
            nn = len(ms.index.id_to_row)
            return {k: c[k][: nn + 2] for k in ("salience", "access_count")}

        ca, cb = cols(a), cols(b)
        np.testing.assert_allclose(ca["salience"], cb["salience"], atol=1e-6)
        np.testing.assert_array_equal(ca["access_count"], cb["access_count"])
    finally:
        a.close()
        b.close()


def test_quant_multi_tenant_batch_isolation():
    """One coalesced int8 batch serving several tenants keeps isolation:
    the per-request tenant column masks the coarse scan AND the rescore."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        emb = ClusteredEmb()
        ms.index.add(["t2:alien_1"],
                     np.asarray([emb.embed("fact 3 body")], np.float32),
                     [0.9], [0.0], ["semantic"], ["default"], "t2")
        reqs = [
            RetrievalRequest(query=np.asarray(emb.embed("fact 3 body"),
                                              np.float32),
                             tenant=ms.user_id, k=5),
            RetrievalRequest(query=np.asarray(emb.embed("fact 3 body"),
                                              np.float32),
                             tenant="t2", k=5),
        ]
        res = ms.index.search_fused_requests(
            reqs, cap_take=5, max_nbr=8, super_gate=0.4,
            acc_boost=0.05, nbr_boost=0.02)
        assert res[0].ids and all(i.startswith(f"{ms.user_id}:")
                                  for i in res[0].ids)
        assert res[1].ids == ["t2:alien_1"]
        ms.close()


def test_quant_k_shortfall_guard():
    """Satellite fix: the coarse over-fetch slack is config-driven and the
    quantized path returns k live rows whenever k live rows exist — the
    exact rescore + host decode can never shrink the result below k."""
    n, d, k = 64, 16, 10
    rng = np.random.default_rng(7)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    idx = MemoryIndex(dim=d, capacity=255, int8_serving=True, coarse_slack=4)
    assert idx.coarse_slack == 4               # ctor knob wired
    idx.add([f"m{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "u0")
    res = idx.search_fused_requests(
        [RetrievalRequest(query=rng.standard_normal(d).astype(np.float32),
                          tenant="u0", k=k)],
        cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
        nbr_boost=0.02)
    assert len(res[0].ids) == k


@pytest.mark.slow
def test_fused_quant_1m_rows_fixture(monkeypatch):
    """1M-row bench fixture (slow lane ONLY — tier-1 stays fast, ISSUE 3
    satellite): dense quantized fused serving at the north-star row count
    (reduced dim so the CPU lane finishes). Pins ONE dispatch per batch at
    scale and exact top-1 agreement with the classic int8 shadow path."""
    n, d, k = 1_048_576, 64, 10
    rng = np.random.default_rng(5)
    import jax.numpy as jnp
    idx = MemoryIndex(dim=d, capacity=n + 64, dtype=jnp.bfloat16,
                      int8_serving=True)
    chunk = 131_072
    for c in range(0, n, chunk):
        emb = rng.standard_normal((chunk, d)).astype(np.float32)
        idx.add([f"f{c + i}" for i in range(chunk)], emb, [0.5] * chunk,
                [0.0] * chunk, ["semantic"] * chunk, ["default"] * chunk,
                "u0")
    probe_rows = rng.integers(0, n, size=16)
    queries = np.asarray(idx.state.emb[jnp.asarray(probe_rows)], np.float32)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k)
            for i in range(len(probe_rows))]
    kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02)
    idx.search_fused_requests(reqs, **kw)      # warm + shadow build
    calls = _count_dispatches(monkeypatch)
    res = idx.search_fused_requests(reqs, **kw)
    assert calls["search_fused_quant_ragged_read"] == 1
    assert sum(calls.values()) == 1
    shadow = idx.search_batch(queries, "u0", k=1)
    for i, r in enumerate(probe_rows):
        assert res[i].ids[0] == f"f{r}"        # exact self-hit at 1M rows
        assert shadow[i][0][0] == res[i].ids[0]


def test_sharded_serve_requests_single_dispatch_multi_tenant():
    """ROADMAP ceiling #4: the pod path serves a mixed-tenant coalesced
    batch with ONE distributed dispatch (per-row tenant column), with
    isolation intact per request."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (8,))
    idx = ShardedMemoryIndex(mesh, dim=16, capacity=256, dtype=np.float32)

    def basis(i):
        v = np.zeros(16, np.float32)
        v[i % 16] = 1.0
        return v

    idx.add([f"a{i}" for i in range(4)], np.stack([basis(i) for i in range(4)]),
            "alice")
    idx.add(["b0"], basis(0).reshape(1, -1), "bob")
    reqs = [RetrievalRequest(query=basis(0), tenant="alice", k=2),
            RetrievalRequest(query=basis(0), tenant="bob", k=2),
            RetrievalRequest(query=basis(2), tenant="alice", k=2),
            RetrievalRequest(query=basis(0), tenant="nobody", k=2)]
    calls = {"n": 0}
    res0 = idx.serve_requests(reqs)            # builds + warms the kernels
    orig = idx._dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig(fn, *a, **kw)

    idx._dispatch = counting
    res = idx.serve_requests(reqs)
    assert calls["n"] == 1                     # ONE dispatch, 3 tenants
    for r0, r in zip(res0, res):
        assert r0.ids == r.ids
    assert res[0].ids[0] == "a0" and all(i.startswith("a") for i in res[0].ids)
    assert res[1].ids == ["b0"]
    assert res[2].ids[0] == "a2"
    assert res[3].ids == []                    # unknown tenant matches nothing
