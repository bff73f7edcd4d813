"""Semantic query cache (ISSUE 20): similarity-keyed hits ride the fused
dispatch.

A device-resident ring of recent (query embedding, packed top-k) entries is
probed INSIDE the fused serving program: a query whose top-1 cosine against
its tenant's cached entries clears the threshold early-outs its arena scan
and returns the cached window — still ONE dispatch, ONE packed readback for
the whole batch. These tests pin the contract:

  * cold serve = bit-parity with a cache-off twin (ids, scores, gate);
    warm serve = hit, same window; a near-dup paraphrase also hits
  * hits are tenant-scoped — the same vector under another tenant misses
  * every mutation path invalidates exactly (add, delete, dedup-merge),
    so a stale window is never served
  * the ring survives a same-geometry checkpoint restore, is dropped on a
    geometry mismatch, and is ignored by a cache-off restore
  * a warm hit turn is still exactly one jit entry (counter test)
  * the pod path (ShardedMemoryIndex) carries the same semantics
"""

import numpy as np
import jax
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.checkpoint import save_index, load_index
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.query_cache import QueryCache
from lazzaro_tpu.parallel.index import ShardedMemoryIndex
from lazzaro_tpu.parallel.mesh import make_mesh
from lazzaro_tpu.serve import RetrievalRequest
from lazzaro_tpu.utils.telemetry import Telemetry

D = 32
EPOCH = 1000.0
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02, now=1234.5)
SEM_KW = dict(semantic_cache=True, semantic_cache_slots=16,
              semantic_cache_threshold=0.99)


def _vecs(n, seed, dim=D):
    r = np.random.default_rng(seed)
    v = r.standard_normal((n, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _build(**extra):
    """4 tenants x 32 rows with chain edges, telemetry attached."""
    idx = MemoryIndex(dim=D, capacity=255, epoch=EPOCH,
                      telemetry=Telemetry(), **extra)
    emb = _vecs(128, 0)
    for t in range(4):
        ids = [f"t{t}n{i}" for i in range(32)]
        idx.add(ids, emb[t * 32:(t + 1) * 32], [0.5] * 32, [0.0] * 32,
                ["semantic"] * 32, ["default"] * 32, f"u{t}")
        idx.add_edges([(f"t{t}n{i}", f"t{t}n{i + 1}", 0.7)
                       for i in range(31)], f"u{t}", now=EPOCH)
    return idx, emb


def _reqs(emb, boost=False, jitter=0.0, seed=7):
    """Two queries per tenant; jitter>0 makes near-dup paraphrases."""
    out = []
    r = np.random.default_rng(seed)
    for t in range(4):
        for j in range(2):
            q = emb[t * 32 + j] + jitter * r.standard_normal(D).astype(
                np.float32)
            out.append(RetrievalRequest(query=q, tenant=f"u{t}", k=8,
                                        gate_enabled=True, boost=boost))
    return out


def _sem_counts(idx):
    c = idx.telemetry.snapshot()["counters"]
    return (c.get("serve.semantic_hits", 0),
            c.get("serve.semantic_misses", 0))


def _same(a_list, b_list, tag):
    for a, b in zip(a_list, b_list):
        assert a.ids == b.ids, (tag, a.ids, b.ids)
        assert a.scores == b.scores, (tag, a.scores, b.scores)
        assert a.gate_id == b.gate_id, tag


# ------------------------------------------------- core hit/miss semantics
def test_cold_warm_paraphrase_parity_vs_cache_off():
    idx, emb = _build(**SEM_KW)
    off, _ = _build()
    r1 = idx.search_fused_requests(list(_reqs(emb)), **KW)
    assert _sem_counts(idx) == (0, 8)
    r_off = off.search_fused_requests(list(_reqs(emb)), **KW)
    _same(r1, r_off, "cold-vs-off")

    r2 = idx.search_fused_requests(list(_reqs(emb)), **KW)
    assert _sem_counts(idx) == (8, 8)
    _same(r2, r_off, "warm-vs-off")

    # a paraphrase (tiny jitter, cosine still above threshold) hits and
    # serves the cached intent's window
    r3 = idx.search_fused_requests(list(_reqs(emb, jitter=0.003)), **KW)
    assert _sem_counts(idx) == (16, 8)
    _same(r3, r_off, "paraphrase-vs-off")


def test_hits_are_tenant_scoped():
    idx, emb = _build(**SEM_KW)
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    h, m = _sem_counts(idx)
    assert h == 8
    # u0's warmed query asked under u1 must NOT hit u0's slots
    alien = [RetrievalRequest(query=emb[0], tenant="u1", k=8,
                              gate_enabled=True)]
    idx.search_fused_requests(alien, **KW)
    h2, m2 = _sem_counts(idx)
    assert h2 == h and m2 == m + 1, (h2, m2)


def test_ingest_invalidates_only_the_writing_tenant():
    idx, emb = _build(**SEM_KW)
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    h, m = _sem_counts(idx)
    idx.add(["t0new"], _vecs(1, 99), [0.9], [0.0], ["semantic"],
            ["default"], "u0")
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    h2, m2 = _sem_counts(idx)
    # u0's two queries miss again; the other six tenants' stay warm
    assert h2 - h == 6 and m2 - m == 2, (h2 - h, m2 - m)


def test_delete_evicts_slots_serving_the_row():
    idx, emb = _build(**SEM_KW)
    rq = _reqs(emb)
    idx.search_fused_requests(list(rq), **KW)
    idx.search_fused_requests(list(rq), **KW)
    _, m = _sem_counts(idx)
    idx.delete(["t0n0"])                   # t0n0 sits in u0's windows
    res = idx.search_fused_requests(list(rq), **KW)
    _, m2 = _sem_counts(idx)
    assert m2 > m, "delete must evict the slots whose window holds the row"
    for r in res:
        assert "t0n0" not in r.ids


def test_dedup_merge_invalidates_cached_window():
    """Ingest-readback slot invalidation: a device dedup-merge into a row
    inside a cached window must evict that window — the next serve misses
    and matches a cache-off twin that took the same merge."""
    idx, emb = _build(**SEM_KW)
    off, _ = _build()
    rq = _reqs(emb)
    idx.search_fused_requests(list(rq), **KW)
    idx.search_fused_requests(list(rq), **KW)
    h, m = _sem_counts(idx)
    assert h == 8

    # near-dup of t0n0 (= emb[0]): cosine ~1 clears the 0.9 dedup gate,
    # so the device merges it into t0n0 (salience/recency bump in place)
    dup = emb[0] + 0.001 * _vecs(1, 5)[0]
    dup = (dup / np.linalg.norm(dup)).astype(np.float32).reshape(1, -1)
    for target in (idx, off):
        pending = target.ingest_batch_dedup(
            dup, [0.9], [50.0], ["semantic"], ["default"], "u0",
            dedup_gate=0.9, link_k=3, link_gate=0.5, now=EPOCH + 1.0)
        _, _, merges, _ = target.commit_ingest_dedup(pending, ["dupe0"])
        assert merges and merges[0][1] == "t0n0", merges

    # row-level precision: only the ONE window holding t0n0 is evicted
    # (u0's other cached query stays warm, as do the other tenants')
    res = idx.search_fused_requests(list(rq), **KW)
    h2, m2 = _sem_counts(idx)
    assert m2 - m == 1 and h2 - h == 7, (h2 - h, m2 - m)
    _same(res, off.search_fused_requests(list(rq), **KW), "post-merge")


def test_boost_path_hits_match_cache_off_ids():
    idx, emb = _build(**SEM_KW)
    off, _ = _build()
    b1 = idx.search_fused_requests(list(_reqs(emb, boost=True)), **KW)
    _same(b1, off.search_fused_requests(list(_reqs(emb, boost=True)), **KW),
          "boost-cold-vs-off")
    b2 = idx.search_fused_requests(list(_reqs(emb, boost=True)), **KW)
    h, _ = _sem_counts(idx)
    assert h == 8
    # both twins accrued one round of boost drift; ids must still agree
    b_off = off.search_fused_requests(list(_reqs(emb, boost=True)), **KW)
    for a, b in zip(b2, b_off):
        assert a.ids == b.ids, (a.ids, b.ids)


def test_semantic_invalidate_public_api():
    idx, emb = _build(**SEM_KW)
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    assert idx.semantic_invalidate("u0") > 0
    assert idx.semantic_invalidate("u0") == 0      # already clean
    assert idx.semantic_invalidate("nope") == 0    # unknown tenant
    assert idx.semantic_invalidate() >= 0          # full flush
    st = idx.stats()["semantic_cache"]
    assert st["occupied"] == 0 and st["slots"] == 16


def test_cache_off_serve_records_no_semantic_counters():
    """sem_active gating: without the ring, no semantic counters move."""
    idx, emb = _build()
    idx.search_fused_requests(list(_reqs(emb)), **KW)
    assert _sem_counts(idx) == (0, 0)
    assert idx.stats()["semantic_cache"] is None


# -------------------------------------------------- one-dispatch guarantee
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


def test_warm_hit_turn_is_still_one_dispatch(monkeypatch):
    """The ring probe adds ZERO dispatches: a fully-warm batch (every
    query a hit) is still exactly one fused jit entry and no classic
    search/boost calls — the probe, early-out, and writeback all live
    inside the one program."""
    idx, emb = _build(**SEM_KW)
    rq = _reqs(emb)
    idx.search_fused_requests(list(rq), **KW)          # populate ring
    calls = _count_dispatches(monkeypatch)
    idx.search_fused_requests(list(rq), **KW)          # all 8 hit
    h, _ = _sem_counts(idx)
    assert h == 8
    fused = sum(calls[n] for n in _COUNTED if n.startswith("search_fused"))
    assert fused == 1, calls
    for n in _COUNTED:
        if not n.startswith("search_fused"):
            assert calls[n] == 0, (n, calls)


# ------------------------------------------------------ checkpoint ring
def test_checkpoint_ring_round_trip(tmp_path):
    dim = 16
    tel = Telemetry()
    idx = MemoryIndex(dim=dim, capacity=127, telemetry=tel,
                      semantic_cache=True, semantic_cache_slots=16,
                      semantic_cache_threshold=0.99)
    emb = _vecs(20, 11, dim)
    idx.add([f"n{i}" for i in range(20)], emb, [0.5] * 20,
            [1000.0 + i for i in range(20)], ["semantic"] * 20,
            ["s"] * 20, "alice")
    rq = [RetrievalRequest(query=emb[3], tenant="alice", k=4)]
    cold = [r.ids for r in idx.search_fused_requests(list(rq), **KW)]
    assert _sem_counts(idx) == (0, 1)
    save_index(idx, str(tmp_path))

    # same geometry -> ring survives; the very first serve is a HIT
    idx2 = load_index(str(tmp_path), telemetry=Telemetry(),
                      semantic_cache=True, semantic_cache_slots=16,
                      semantic_cache_threshold=0.99)
    warm = [r.ids for r in idx2.search_fused_requests(list(rq), **KW)]
    assert _sem_counts(idx2) == (1, 0)
    assert warm == cold

    # geometry mismatch -> ring dropped: cold start, never a wrong hit
    idx3 = load_index(str(tmp_path), telemetry=Telemetry(),
                      semantic_cache=True, semantic_cache_slots=8,
                      semantic_cache_threshold=0.99)
    res = [r.ids for r in idx3.search_fused_requests(list(rq), **KW)]
    assert _sem_counts(idx3) == (0, 1)
    assert res == cold

    # cache-off restore of a cache-on snapshot just ignores the ring
    idx4 = load_index(str(tmp_path), telemetry=Telemetry())
    res = [r.ids for r in idx4.search_fused_requests(list(rq), **KW)]
    assert res == cold
    assert _sem_counts(idx4) == (0, 0)


# ------------------------------------------------------------- pod path
def test_pod_semantic_cache_end_to_end():
    dim = 16
    tel = Telemetry()
    mesh = make_mesh(("data",), (4,), devices=jax.devices()[:4])
    idx = ShardedMemoryIndex(mesh, dim=dim, capacity=127, telemetry=tel,
                             semantic_cache=True, semantic_cache_slots=16,
                             semantic_cache_threshold=0.99)
    off = ShardedMemoryIndex(make_mesh(("data",), (4,),
                                       devices=jax.devices()[:4]),
                             dim=dim, capacity=127, telemetry=Telemetry())
    rng = np.random.default_rng(7)
    emb_a = rng.standard_normal((12, dim)).astype(np.float32)
    emb_b = rng.standard_normal((6, dim)).astype(np.float32)
    for target in (idx, off):
        target.add([f"a{i}" for i in range(12)], emb_a, "alice")
        target.add([f"b{i}" for i in range(6)], emb_b, "bob")

    def counts():
        return (tel.counter_total("serve.semantic_hits"),
                tel.counter_total("serve.semantic_misses"))

    rq = [RetrievalRequest(query=emb_a[1], tenant="alice", k=3),
          RetrievalRequest(query=emb_b[0], tenant="bob", k=3)]
    cold = [r.ids for r in idx.serve_requests(rq)]
    assert counts() == (0, 2)
    warm = [r.ids for r in idx.serve_requests(rq)]
    h1, m1 = counts()
    assert (h1, m1) == (2, 2) and warm == cold
    assert [r.ids for r in off.serve_requests(rq)] == warm

    # add() invalidates only alice; bob's entry stays warm
    idx.add(["a_new"], (emb_a[1] + 0.01).reshape(1, -1), "alice")
    off.add(["a_new"], (emb_a[1] + 0.01).reshape(1, -1), "alice")
    res = [r.ids for r in idx.serve_requests(rq)]
    h2, m2 = counts()
    assert h2 == h1 + 1 and m2 == m1 + 1    # bob hit, alice miss
    assert res == [r.ids for r in off.serve_requests(rq)]

    # delete() evicts the touched rows — no stale id in served windows
    victim = res[1][0]
    idx.delete([victim])
    res2 = [r.ids for r in idx.serve_requests(rq)]
    assert victim not in res2[1]

    snap = tel.snapshot()
    assert any("semantic_ring_occupancy" in k for k in snap["gauges"]), (
        snap["gauges"].keys())


# --------------------------------------------- observability surfaces
def test_metrics_summary_reports_both_cache_tiers():
    """serve.cache_hit_rate lands in the registry tier-labeled, and
    metrics_summary()/get_stats() surface both tiers' headline rates."""
    import tempfile

    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.memory_system import MemorySystem
    from tests.test_fused_ingest import ClusteredEmb, QueueLLM

    with tempfile.TemporaryDirectory() as tmp:
        ms = MemorySystem(
            enable_async=False, db_dir=tmp, verbose=False,
            load_from_disk=False, llm_provider=QueueLLM(20),
            embedding_provider=ClusteredEmb(), auto_prune=False,
            config=MemoryConfig(journal=False, auto_consolidate=False,
                                decay_rate=0.0, semantic_cache=True,
                                semantic_cache_slots=16,
                                semantic_cache_threshold=0.99))
        ms.start_conversation()
        ms.add_to_short_term("conv 0", "episodic", 0.7)
        ms.end_conversation()
        ms.search_memories("fact 3 body")
        ms.search_memories("fact 3 body")      # second pass: semantic hit
        summary = ms.metrics_summary()
        rates = summary["cache_hit_rate"]
        assert set(rates) == {"exact", "semantic"}
        assert rates["semantic"] is not None and rates["semantic"] > 0.0
        assert summary["semantic_stale_evictions"] >= 0
        stats = ms.get_stats()
        assert stats["performance"]["semantic_cache_hit_rate"] is not None
        gauges = ms.telemetry.snapshot()["gauges"]
        tiers = {k for k in gauges if k.startswith("serve.cache_hit_rate")}
        assert any('tier="exact"' in k for k in tiers), tiers
        assert any('tier="semantic"' in k for k in tiers), tiers
        ms.close()


# ------------------------------------------- QueryCache result tenancy
def test_query_cache_results_are_tenant_keyed():
    """Regression (ISSUE 20 satellite): the SAME query text cached by two
    tenants stores two distinct entries — a tenant can never be served
    another tenant's node ids."""
    qc = QueryCache(max_size=16)
    qc.set_results("what did I say", ["alice:n1"], tenant="alice")
    qc.set_results("what did I say", ["bob:n9"], tenant="bob")
    assert qc.get_results("what did I say", "alice") == ["alice:n1"]
    assert qc.get_results("what did I say", "bob") == ["bob:n9"]
    # untenanted lookups don't alias a tenant's entry either way
    assert qc.get_results("what did I say") is None
    qc.set_results("shared", ["s1"])
    assert qc.get_results("shared") == ["s1"]
    assert qc.get_results("shared", "alice") is None
