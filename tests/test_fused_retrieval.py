"""Fused single-dispatch retrieval (tier-1 smoke, CPU, tiny arena).

The per-chat-turn serving sequence — super-node top-1 gate, main-arena ANN
top-k, CSR neighbor gather, neighbor- + access-salience boosts — must run
as ONE device program (``state.search_fused``) with ONE packed readback.
These tests count the actual jit entry points during end-to-end ``chat()``
turns and pin exact semantic parity (ids, ordering, boost effects) with the
classic multi-dispatch path across super-gate hit, super-gate miss, and
empty-graph cases — mirroring ``test_fused_ingest.py`` for the serving side.
"""

import json
import tempfile

import numpy as np
import pytest

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.memory_system import MemorySystem
from tests.test_fused_ingest import ClusteredEmb, QueueLLM

D = 24


def _system(tmp, serve_fused=True, per=20, super_threshold=100):
    ms = MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=QueueLLM(per), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        super_node_threshold=super_threshold,
        config=MemoryConfig(journal=False, auto_consolidate=False,
                            decay_rate=0.0))
    ms.config.serve_fused = serve_fused
    return ms


def _ingest(ms, convs=2):
    for c in range(convs):
        ms.start_conversation()
        ms.add_to_short_term(f"conv {c}", "episodic", 0.7)
        ms.end_conversation()
    return ms


_COUNTED = ("search_fused_ragged", "search_fused_ragged_copy",
            "search_fused_ragged_read",
            "arena_search", "arena_update_access", "arena_update_access_copy",
            "arena_boost", "arena_boost_copy", "arena_apply_boosts",
            "arena_apply_boosts_copy")


def _count_dispatches(monkeypatch):
    calls = {name: 0 for name in _COUNTED}
    for name in _COUNTED:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    return calls


def test_one_fused_dispatch_per_chat_turn(monkeypatch):
    """The jit-call counter: a chat turn's retrieval (gate + ANN + neighbor
    boost + access boost) costs exactly ONE device dispatch — the donated
    ragged ``search_fused_ragged`` program (ISSUE 7: per-query k rides as
    device data) — and zero classic search/boost dispatches."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.start_conversation()
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")
        assert calls["search_fused_ragged"] == 1  # donated single-writer
        for name in _COUNTED:
            if name != "search_fused_ragged":
                assert calls[name] == 0, (name, calls)
        ms.close()


def test_search_memories_takes_readonly_twin(monkeypatch):
    """A pure read (no boosts requested anywhere in the batch) must take
    the ragged read twin — same compute, no donation dance, ONE dispatch."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        calls = _count_dispatches(monkeypatch)
        hits = ms.search_memories("fact 3 body")
        assert hits
        assert calls["search_fused_ragged_read"] == 1
        assert calls["search_fused_ragged"] == 0
        assert calls["arena_search"] == 0
        # a whole fleet is still one dispatch
        ms.search_memories_batch([f"fact {i} body" for i in range(8)])
        assert calls["search_fused_ragged_read"] == 2
        ms.close()


def test_cached_hit_turn_pays_zero_device_dispatches(monkeypatch):
    """Satellite fix: a query-cache hit used to pay the full device boost
    sequence anyway. Now the cached turn queues boost counts host-side
    (ZERO dispatches) and ``end_conversation`` flushes them as ONE
    ``arena_apply_boosts`` scatter before decay."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 7 body")                 # populates the query cache
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")                 # cache hit
        for name in _COUNTED:
            assert calls[name] == 0, (name, calls)
        assert ms._pending_boosts              # counts queued, not dropped
        ms.end_conversation()
        assert calls["arena_apply_boosts"] == 1
        assert not ms._pending_boosts
        ms.close()


def _numeric_cols(ms):
    cols = ms.index.pull_numeric()
    n = len(ms.index.id_to_row)
    return {k: cols[k][: n + 2] for k in ("salience", "access_count")}


def test_fused_matches_classic_chat_turns():
    """Ids, ordering, and boost side effects (salience + access counts on
    the arena AND host copies) identical across fused and classic serving
    for plain ANN turns — including repeated (cached) turns."""
    def build():
        return _ingest(_system(tempfile.mkdtemp(), serve_fused=True)), \
            _ingest(_system(tempfile.mkdtemp(), serve_fused=False))

    a, b = build()
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


def test_fused_matches_classic_super_gate_hit():
    """When the super-node gate fires, the kernel reports ``fast`` and skips
    device boosts; the host runs the identical hierarchy-children fast path
    and classic boosts — results and arena numerics must match exactly."""
    def build(serve_fused):
        ms = _ingest(_system(tempfile.mkdtemp(), serve_fused=serve_fused,
                             super_threshold=5))
        assert ms.super_nodes                  # threshold 5 < ~13 per shard
        return ms

    a, b = build(True), build(False)
    try:
        # query ON a super centroid: guaranteed > 0.4 gate
        sid = sorted(a.super_nodes)[0]
        centroid = np.asarray(a.super_nodes[sid].embedding, np.float32)
        ids_a, mode_a = a._retrieve_for_chat(centroid.tolist(), "probe-q")
        ids_b, mode_b = b._retrieve_for_chat(centroid.tolist(), "probe-q")
        assert ids_a == ids_b
        assert mode_a == "classic"             # device skipped boosts
        assert mode_b == "classic"
        # the fast-path signature: children served in child-list order
        children = a.super_nodes[sid].child_ids
        assert ids_a[0] == children[0]
        # full turns agree on the numerics too
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


def test_fused_matches_classic_empty_graph():
    """A fresh system (no nodes at all) serves empty results identically on
    both paths and never crashes in the kernel."""
    a = _system(tempfile.mkdtemp(), serve_fused=True)
    b = _system(tempfile.mkdtemp(), serve_fused=False)
    try:
        ids_a, _ = a._retrieve_for_chat(ClusteredEmb().embed("fact 1 body"),
                                        "fact 1 body")
        ids_b, _ = b._retrieve_for_chat(ClusteredEmb().embed("fact 1 body"),
                                        "fact 1 body")
        assert ids_a == ids_b == []
        assert a.search_memories("anything") == []
    finally:
        a.close()
        b.close()


def test_scheduler_coalesces_concurrent_turns():
    """Concurrent retrievals from many threads share device batches: the
    scheduler's flush policy coalesces them, and every caller still gets
    its own correct result (per-request demux)."""
    import threading

    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        expected = {q: [n.id for n in ms.search_memories(q)]
                    for q in (f"fact {i} body" for i in range(8))}
        # hold the worker hostage so submissions pile up into one batch
        results = {}

        def worker(q):
            results[q] = [n.id for n in ms.search_memories(q)]

        threads = [threading.Thread(target=worker, args=(q,))
                   for q in expected]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == expected
        stats = ms.query_scheduler.stats()
        assert stats["requests_served"] >= 2 * len(expected)
        ms.close()


def test_multi_tenant_batch_isolation():
    """One coalesced batch serving several tenants keeps isolation: the
    per-request tenant column masks rows inside the kernel."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        emb = ClusteredEmb()
        # second tenant's rows go straight into the index
        ms.index.add(["t2:alien_1"], np.asarray([emb.embed("fact 3 body")],
                                                np.float32),
                     [0.9], [0.0], ["semantic"], ["default"], "t2")
        from lazzaro_tpu.serve import RetrievalRequest
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


def test_fused_serving_covers_every_mode():
    """Since ISSUE 3 the fused path serves int8 mode itself (the quantized
    coarse-scan + exact-rescore kernel), since ISSUE 4 the IVF coarse
    stage rides INSIDE the fused program too (centroid prefilter + member
    gather, ``search_fused_ivf``), and since ISSUE 16 PQ member storage
    joined as well (``search_fused_pq`` — in-kernel ADC member scan +
    exact rescore) — no mode opts out of fusion anymore."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        assert ms._use_fused_serving()
        ms.index.int8_serving = True
        assert ms._use_fused_serving()     # quant kernel serves this mode
        ms.index.int8_serving = False
        ms.index.ivf_nprobe = 4
        assert ms._use_fused_serving()     # IVF rides the fused kernel now
        ms.index.pq_serving = True
        assert ms._use_fused_serving()     # PQ rides it too (ISSUE 16)
        ms.config.serve_fused = False
        assert not ms._use_fused_serving()  # only the config opts out
        ms.close()
