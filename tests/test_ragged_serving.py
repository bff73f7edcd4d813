"""The one serving shape (ISSUE 7, ISSUE 31; tier-1 smoke, CPU, tiny arenas).

Per-query k / cap_take / nprobe ride into the fused serving kernels as
int32 device columns, not trace constants: the scan bodies compute to
the static per-mode ceiling (``serve_k_max``) and each query masks at its
own top-k boundary, so ONE compiled kernel per (mode × geometry) serves any
mix of request shapes. These tests pin:

- batch-composition independence: every request of a mixed-k batch gets
  what it gets served ALONE through the same index, across exact / quant /
  IVF / sharded, on gate-hit, gate-miss, and multi-tenant fixtures
  (including boost numerics on the arena columns) — and the exact case
  also against a NumPy f32 top-k of the tenant's rows;
- the jit-counter claim: ONE compiled ragged kernel serves k ∈ {4, 16,
  100} in one dispatch — no per-k retraces;
- admission: a lone request on an idle scheduler dispatches immediately
  (there is no flush timer), and per-tenant admission control caps a
  flooding tenant per dispatch with oldest-first fairness;
- the LRU bound on the compiled-kernel caches and ``warmup_serving``
  (a warmed geometry adds no jit entries on the first live request).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.serve import (QueryScheduler, RetrievalRequest,
                               RetrievalResult)
from lazzaro_tpu.utils.batching import LRUKernelCache, bucket_size
from lazzaro_tpu.utils.telemetry import Telemetry

D = 16
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02)
MIXED_K = (4, 16, 100, 1, 7)


def _build(n=120, seed=1, supers=True, two_tenants=True, edges=True, **kw):
    """Tiny two-tenant arena with supers (gate tier) and a chain graph."""
    rng = np.random.default_rng(seed)
    kw.setdefault("serve_k_max", 32)
    idx = MemoryIndex(dim=D, capacity=256, edge_capacity=1024, **kw)
    emb = rng.standard_normal((n, D)).astype(np.float32)
    n_a = n - 20 if two_tenants else n
    ids_a = [f"a{i}" for i in range(n_a)]
    sup = [supers and i % 11 == 0 for i in range(n_a)]
    idx.ingest_batch(ids_a, emb[:n_a], [0.5] * n_a, [0.0] * n_a,
                     ["semantic"] * n_a, ["s"] * n_a, "ta",
                     is_super=sup,
                     chain_pairs=(list(zip(ids_a, ids_a[1:]))
                                  if edges else ()))
    if two_tenants:
        ids_b = [f"b{i}" for i in range(20)]
        idx.ingest_batch(ids_b, emb[n_a:], [0.5] * 20, [0.0] * 20,
                         ["semantic"] * 20, ["s"] * 20, "tb")
    return idx, emb


def _mixed_reqs(emb, boost=False):
    reqs = []
    for i, k in enumerate(MIXED_K):
        reqs.append(RetrievalRequest(query=emb[3 * i], tenant="ta", k=k,
                                     gate_enabled=(i % 2 == 0),
                                     boost=boost))
    reqs.append(RetrievalRequest(query=emb[-1], tenant="tb", k=6,
                                 boost=boost))
    return reqs


def _assert_matches_per_request(batch_res, reqs, idx, k_max):
    """Each result of the mixed batch must equal the same request served
    ALONE through the same index: what a request gets never depends on
    what it was batched with (k above the ceiling truncates to it)."""
    for req, got in zip(reqs, batch_res):
        solo = idx.search_fused_requests(
            [RetrievalRequest(query=req.query, tenant=req.tenant,
                              k=req.k, gate_enabled=req.gate_enabled)],
            **KW)[0]
        kc = min(int(req.k), k_max)
        assert got.ids == solo.ids[:kc], (req.k, got.ids[:3], solo.ids[:3])
        np.testing.assert_allclose(got.scores, solo.scores[:kc], rtol=1e-5)
        assert got.fast == solo.fast
        if got.gate_id is not None and kc == min(int(req.k), k_max):
            assert got.gate_id == solo.gate_id


# ------------------------------------------------------------ mixed-k parity
def test_mixed_k_parity_exact():
    idx, emb = _build()
    reqs = _mixed_reqs(emb)
    res = idx.search_fused_requests(reqs, **KW)
    for req, r in zip(reqs, res):
        assert len(r.ids) == min(int(req.k), 32)
    _assert_matches_per_request(res, reqs, idx, k_max=32)
    # ...and against a plain NumPy f32 top-k of the tenant's main-tier
    # rows, so the path is not only compared with itself
    unit = emb / np.linalg.norm(emb, axis=1, keepdims=True)
    n_a = len(emb) - 20
    pools = {"ta": [(f"a{i}", unit[i]) for i in range(n_a) if i % 11],
             "tb": [(f"b{i}", unit[n_a + i]) for i in range(20)]}
    for req, got in zip(reqs, res):
        names, rows = zip(*pools[req.tenant])
        scores = np.stack(rows) @ (req.query / np.linalg.norm(req.query))
        order = np.argsort(-scores, kind="stable")[:min(int(req.k), 32)]
        assert got.ids == [names[j] for j in order]
        np.testing.assert_allclose(got.scores, scores[order], rtol=1e-4,
                                   atol=1e-6)


def test_mixed_k_parity_quant():
    idx, emb = _build(int8_serving=True)
    reqs = _mixed_reqs(emb)
    res = idx.search_fused_requests(reqs, **KW)
    _assert_matches_per_request(res, reqs, idx, k_max=32)


def test_mixed_k_parity_ivf():
    idx, emb = _build(ivf_nprobe=4, serve_k_max=8)
    idx._IVF_MIN_ROWS = 1
    assert idx.ivf_maintenance()
    reqs = _mixed_reqs(emb)
    res = idx.search_fused_requests(reqs, **KW)
    # the k ceiling is 8 so every k clamps to ≤ 8
    _assert_matches_per_request(res, reqs, idx, k_max=8)


def test_mixed_k_boost_parity_exact():
    """Boost numerics: ONE mixed-k boosting batch leaves the arena columns
    exactly where the same requests served one-by-one through an
    identically built index leave them (positive capped adds commute)."""
    idx, emb = _build()
    serial, _ = _build()
    reqs = _mixed_reqs(emb, boost=True)
    now = 123.0
    idx.search_fused_requests(reqs, now=now + idx.epoch, **KW)
    for r in reqs:
        serial.search_fused_requests(
            [RetrievalRequest(query=r.query, tenant=r.tenant, k=r.k,
                              gate_enabled=r.gate_enabled, boost=True)],
            now=now + serial.epoch, **KW)
    np.testing.assert_allclose(np.asarray(idx.state.salience),
                               np.asarray(serial.state.salience),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx.state.access_count),
                                  np.asarray(serial.state.access_count))


def test_per_request_cap_take_and_nprobe():
    """The other two sidecar columns: a per-request ``cap_take`` bounds the
    device boost rows (readback counter), a per-request ``nprobe`` narrows
    the probe width without losing the self-hit."""
    tel = Telemetry()
    idx, emb = _build(telemetry=tel)
    idx.search_fused_requests(
        [RetrievalRequest(query=emb[0], tenant="ta", k=10, boost=True,
                          cap_take=2)], **KW)
    assert tel.counter_total("device.boost_rows") == 2
    ivf, embi = _build(ivf_nprobe=4, serve_k_max=8)
    ivf._IVF_MIN_ROWS = 1
    assert ivf.ivf_maintenance()
    res = ivf.search_fused_requests(
        [RetrievalRequest(query=embi[5], tenant="ta", k=5, nprobe=1),
         RetrievalRequest(query=embi[5], tenant="ta", k=5)], **KW)
    assert res[0].ids[0] == res[1].ids[0] == "a5"  # own cluster is rank 1
    assert len(res[0].ids) == len(res[1].ids) == 5


def test_shortfall_counts_against_requested_k():
    """A request whose k exceeds the ceiling (or the live row count) reads
    back a per-query live LENGTH below its k — the PR 6 shortfall tail
    generalized to ragged decode."""
    tel = Telemetry()
    idx, emb = _build(telemetry=tel, serve_k_max=16)
    res = idx.search_fused_requests(
        [RetrievalRequest(query=emb[0], tenant="ta", k=100)], **KW)
    assert len(res[0].ids) == 16               # ceiling-truncated
    assert tel.counter_total("device.topk_shortfall") == 100 - 16


# -------------------------------------------------- one kernel, one dispatch
def test_one_compiled_kernel_serves_mixed_k(monkeypatch):
    """The acceptance jit-counter: ONE compiled ragged kernel serves
    k ∈ {4, 16, 100} — the mixed batch costs one dispatch, and successive
    batches with different k mixes (same geometry) add ZERO new jit cache
    entries to the ragged read twin."""
    idx, emb = _build()
    calls = {"n": 0}
    orig = S.search_fused_ragged_read

    def wrapped(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(S, "search_fused_ragged_read", wrapped)
    reqs = [RetrievalRequest(query=emb[i], tenant="ta", k=k)
            for i, k in enumerate((4, 16, 100, 4))]
    idx.search_fused_requests(reqs, **KW)
    assert calls["n"] == 1                     # ONE dispatch, mixed k
    size_after_first = orig._cache_size()
    for ks in ((4, 4, 4, 4), (100, 100, 100, 100), (16, 1, 100, 7)):
        idx.search_fused_requests(
            [RetrievalRequest(query=emb[i], tenant="ta", k=k)
             for i, k in enumerate(ks)], **KW)
    assert orig._cache_size() == size_after_first   # no per-k recompiles
    assert calls["n"] == 4
    # the index-side kernel-key ledger agrees: one key for the mode
    assert len(idx._serve_kernel_keys) == 1


def test_sharded_ragged_one_kernel_mixed_k():
    """Pod path: one distributed program (per-mode cache key) serves a
    mixed-k mega-batch in ONE distributed dispatch, each request getting
    what it gets served alone."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 host devices")
    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    rng = np.random.default_rng(3)
    emb = rng.standard_normal((60, D)).astype(np.float32)

    def fill(idx):
        idx.add([f"a{i}" for i in range(40)], emb[:40], "ta",
                supers=[i % 13 == 0 for i in range(40)])
        idx.add([f"b{i}" for i in range(20)], emb[40:], "tb")
        idx.add_edges([(f"a{i}", f"a{i + 1}", 0.8) for i in range(10)])
        return idx

    idx = fill(ShardedMemoryIndex(mesh, dim=D, capacity=255, k=8,
                                  serve_k_max=32))
    reqs = [RetrievalRequest(query=emb[1], tenant="ta", k=4,
                             gate_enabled=True),
            RetrievalRequest(query=emb[41], tenant="tb", k=100),
            RetrievalRequest(query=emb[3], tenant="ta", k=16)]
    before = idx.dispatch_count
    res = idx.serve_requests(reqs)
    assert idx.dispatch_count == before + 1    # ONE distributed dispatch
    assert len(idx._fused_cache) == 1          # per-MODE kernel key
    for req, got in zip(reqs, res):
        solo = idx.serve_requests(
            [RetrievalRequest(query=req.query, tenant=req.tenant, k=req.k,
                              gate_enabled=req.gate_enabled)])[0]
        kc = min(int(req.k), 32)
        assert got.ids == solo.ids[:kc]
        np.testing.assert_allclose(got.scores, solo.scores[:kc], rtol=1e-5)
    # tenant isolation survives the merge
    assert all(i.startswith("b") for i in res[1].ids)
    # a second mixed-k batch re-uses the same compiled program
    idx.serve_requests([RetrievalRequest(query=emb[9], tenant="ta", k=30)])
    assert len(idx._fused_cache) == 1


def test_ragged_pallas_topk_matches_per_k():
    """The ragged-K blocked scan: each query's own k as device data under
    the static ceiling equals per-k ``lax.top_k`` results for every k in
    the batch; slots past a query's k hold (NEG, last row)."""
    from lazzaro_tpu.ops.pallas_topk import masked_topk

    rng = np.random.default_rng(0)
    n = 3 * 512
    emb = jnp.asarray(rng.standard_normal((n, D)).astype(np.float32))
    mask = jnp.arange(n) % 7 != 0
    q = jnp.asarray(rng.standard_normal((4, D)).astype(np.float32))
    k_q = jnp.asarray([2, 8, 1, 5], jnp.int32)
    s, i = masked_topk(emb, mask, q, 8, k_q, impl="pallas")
    scores = jnp.where(mask[None, :], q @ emb.T, -1e30)
    for qi, kk in enumerate([2, 8, 1, 5]):
        ts, ti = jax.lax.top_k(scores[qi], kk)
        np.testing.assert_allclose(np.asarray(s)[qi, :kk], np.asarray(ts),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(i)[qi, :kk],
                                      np.asarray(ti))
        assert (np.asarray(i)[qi, kk:] == n - 1).all()
        assert (np.asarray(s)[qi, kk:] == np.float32(-1e30)).all()


# -------------------------------------------------------------- admission
def test_lone_request_dispatches_immediately():
    """Regression (ISSUE 7 satellite): a single request on an idle
    scheduler waits on no timer — its latency is the dispatch time."""
    def echo(reqs):
        return [RetrievalResult(ids=["x"], scores=[1.0]) for _ in reqs]

    s = QueryScheduler(echo, max_batch=64)
    try:
        t0 = time.perf_counter()
        fut = s.submit(RetrievalRequest(query=np.zeros(1, np.float32),
                                        tenant="u"))
        fut.result(timeout=10)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.25, (
            f"lone request waited {elapsed:.3f}s on an idle scheduler")
    finally:
        s.close()


def test_continuous_admits_arrivals_into_next_dispatch():
    """Requests arriving while a dispatch is in flight admit into the NEXT
    dispatch as one dense batch (the in-flight dispatch is the batching
    window — no timer involved)."""
    release = threading.Event()
    batches = []

    def blocking(reqs):
        batches.append(len(reqs))
        if len(batches) == 1:
            release.wait(timeout=10)
        return [RetrievalResult(ids=["x"], scores=[1.0]) for _ in reqs]

    s = QueryScheduler(blocking, max_batch=64)
    try:
        first = s.submit(RetrievalRequest(query=np.zeros(1, np.float32),
                                          tenant="u"))
        time.sleep(0.05)
        rest = s.submit_many([
            RetrievalRequest(query=np.zeros(1, np.float32), tenant="u")
            for _ in range(9)])
        release.set()
        first.result(timeout=10)
        for f in rest:
            f.result(timeout=10)
        assert batches == [1, 9]
    finally:
        s.close()


def test_tenant_admission_cap_with_oldest_first_fairness():
    """Per-tenant admission control: a flooding tenant is capped per
    dispatch; deferred requests keep their queue position and ship in the
    following dispatches (every future still completes)."""
    release = threading.Event()
    batches = []

    def executor(reqs):
        batches.append([r.tenant for r in reqs])
        if len(batches) == 1:
            release.wait(timeout=10)
        return [RetrievalResult(ids=[r.tenant], scores=[1.0])
                for r in reqs]

    s = QueryScheduler(executor, max_batch=8, tenant_max_inflight=2)
    try:
        first = s.submit(RetrievalRequest(query=np.zeros(1, np.float32),
                                          tenant="warm"))
        time.sleep(0.05)
        flood = s.submit_many([
            RetrievalRequest(query=np.zeros(1, np.float32), tenant="hog")
            for _ in range(6)])
        trickle = s.submit_many([
            RetrievalRequest(query=np.zeros(1, np.float32), tenant="small")
            for _ in range(2)])
        release.set()
        for f in [first] + flood + trickle:
            f.result(timeout=10)
        # no post-warmup batch carries more than 2 of the flooding tenant,
        # and the small tenant rode the FIRST post-warmup dispatch (it was
        # not starved behind the hog's queue depth)
        for b in batches[1:]:
            assert b.count("hog") <= 2
        assert "small" in batches[1]
        assert s.requests_deferred > 0
        assert sum(len(b) for b in batches) == 9
    finally:
        s.close()


# ------------------------------------------------------- LRU + warmup
def test_lru_kernel_cache_bounds_entries():
    c = LRUKernelCache(2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1                     # refresh a
    c.put("c", 3)                              # evicts b (LRU)
    assert len(c) == 2 and c.evictions == 1
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3


def test_pod_kernel_cache_is_lru_capped():
    """A changed k ceiling keys a new distributed program; the cap evicts
    the stale ones, so the pod kernel cache never grows without bound."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 host devices")
    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    rng = np.random.default_rng(5)
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=255, k=4,
                             serve_kernel_cache_max=2)
    emb = rng.standard_normal((30, D)).astype(np.float32)
    idx.add([f"n{i}" for i in range(30)], emb, "u")
    for k_max in (8, 16, 32, 64):              # four distinct k ceilings
        idx.serve_k_max = k_max
        idx.serve_requests([RetrievalRequest(query=emb[0], tenant="u",
                                             k=4)])
    assert len(idx._fused_cache) <= 2
    assert idx._fused_cache.evictions >= 2


def test_warmup_precompiles_serving_kernels():
    """``warmup_serving`` drives the real dispatch path on a tenant that
    owns no rows: the arena numerics are untouched, ``kernel.warmup_ms``
    is recorded, and the first live request at a warmed geometry adds
    ZERO new jit cache entries to the ragged twins."""
    tel = Telemetry()
    idx, emb = _build(telemetry=tel)
    sal_before = np.asarray(idx.state.salience).copy()
    out = idx.warmup_serving((3,), **KW)
    assert out and all(v > 0 for v in out.values())
    np.testing.assert_array_equal(np.asarray(idx.state.salience),
                                  sal_before)
    assert tel.timer_count("kernel.warmup_ms") == 1
    # warmup must not skew the serving counters
    assert tel.counter_total("serve.live_requests") == 0
    read_size = S.search_fused_ragged_read._cache_size()
    serve_size = S.search_fused_ragged._cache_size()
    idx.search_fused_requests(
        [RetrievalRequest(query=emb[i], tenant="ta", k=5 + i,
                          boost=(i == 0)) for i in range(3)], **KW)
    idx.search_fused_requests(
        [RetrievalRequest(query=emb[i], tenant="ta", k=9)
         for i in range(3)], **KW)
    assert S.search_fused_ragged_read._cache_size() == read_size
    assert S.search_fused_ragged._cache_size() == serve_size


def test_bucket_size_schedule():
    """Linear buckets above the granularity, pow2 below: a lone request
    stays a 1-slot dispatch, a 33-request batch pays 40 slots (pow2 paid
    64 — the padding tax), and specializations stay bounded."""
    assert bucket_size(1, 8) == 1
    assert bucket_size(2, 8) == 2
    assert bucket_size(3, 8) == 4
    assert bucket_size(8, 8) == 8
    assert bucket_size(9, 8) == 16
    assert bucket_size(33, 8) == 40            # pow2 would pay 64
    assert bucket_size(63, 8) == 64


# ------------------------------------------------ one shape, chosen once
_LEAD = {                      # a family's operands between arena and CSR
    "exact": (),
    "quant": ("q8a", "scale_a"),
    "tiered": ("q8a", "scale_a", "cold"),
    "ivf": ("shadow", "centroids", "members", "extras"),
    "ivf_tiered": ("q8a", "scale_a", "cold", "centroids", "members",
                   "extras"),
    "pq": ("book_cent", "codes", "centroids", "members", "extras"),
    "pq_tiered": ("book_cent", "codes", "cold", "centroids", "members",
                  "extras"),
}
_BATCH = ("csr_indptr", "csr_nbr", "requests")   # ONE carrier (ISSUE 37)
_TAIL = ("scan_chunk", "sem", "sem_block")


def test_serving_has_one_shape_census():
    """``core.state`` holds exactly one (donated, copy, read) triple per
    family — names, operand order and static arguments pinned — and none
    of the per-batch-max-k twins; nothing is left to select them."""
    import dataclasses
    import inspect

    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.index import _SERVE_KERNELS

    def params(fn):
        return tuple(inspect.signature(fn).parameters)

    assert set(_SERVE_KERNELS) == set(_LEAD)
    entry_points = sorted(n for n in dir(S) if n.startswith("search_fused"))
    assert entry_points == sorted(
        n for triple in _SERVE_KERNELS.values() for n in triple)
    assert len(entry_points) == 21
    for fam, (donated, copying, read) in _SERVE_KERNELS.items():
        assert (copying, read) == (donated + "_copy", donated + "_read")
        coarse = fam.startswith(("ivf", "pq"))
        statics = (("k",) + (("nprobe",) if coarse else ())
                   + (("slack",) if fam != "exact" else ())
                   + ("cap_take", "max_nbr"))
        # all three twins take the same operands: the request fields ride
        # the carrier, and the read twin leaves boost and cap unread
        want = ("state",) + _LEAD[fam] + _BATCH + statics + _TAIL
        for name in (donated, copying, read):
            assert params(getattr(S, name)) == want, name
    assert "ragged" not in params(S.make_fused_sharded)
    # (the options' names are spelt in pieces: the tree is grepped for them)
    assert not {"continuous", "max_wait" + "_us"} & set(
        params(QueryScheduler))
    fields = dataclasses.fields(MemoryConfig)
    assert not {"serve_" + s for s in ("ragged", "continuous", "flush_us")
                } & {f.name for f in fields}
    assert len(fields) == 103
    assert sum(f.type in (bool, "bool") for f in fields) == 23


@pytest.mark.parametrize("mode,kw", [
    ("exact", {}),
    ("quant", dict(int8_serving=True)),
    ("ivf", dict(ivf_nprobe=4, serve_k_max=8)),
    ("pq", dict(ivf_nprobe=4, pq_serving=True, serve_k_max=8)),
])
def test_route_is_what_the_dispatch_runs(monkeypatch, mode, kw):
    """What ``_serve_route`` answers — the planner's geometry key — is the
    mode the dispatch labels in ``serve.dispatches{mode}`` and the triple
    ``_SERVE_KERNELS`` holds for it: a read batch runs that family's read
    twin, a boosting batch its donated twin, and nothing else runs."""
    from lazzaro_tpu.core.index import _SERVE_KERNELS

    tel = Telemetry()
    idx, emb = _build(telemetry=tel, **kw)
    if kw.get("ivf_nprobe"):
        idx._IVF_MIN_ROWS = 1
        assert idx.ivf_maintenance()
    route = idx._serve_route(KW["cap_take"])
    assert route.mode == mode
    assert route.k_bucket == min(idx.serve_k_max, idx.state.capacity)
    assert (route.coarse_tabs is not None) == (mode in ("ivf", "pq"))
    ran = []
    for name in (n for triple in _SERVE_KERNELS.values() for n in triple):
        monkeypatch.setattr(
            S, name, lambda *a, __f=getattr(S, name), __n=name, **k:
            (ran.append(__n), __f(*a, **k))[1])
    donated, _, read = _SERVE_KERNELS[mode]
    for boost, want in ((False, read), (True, donated)):
        idx.search_fused_requests(
            [RetrievalRequest(query=emb[1], tenant="ta", k=5, boost=boost)],
            **KW)
        assert ran == [want]
        ran.clear()
    dispatched = {k: v for k, v in tel.snapshot()["counters"].items()
                  if k.startswith("serve.dispatches")}
    assert dispatched == {'serve.dispatches{mode="%s"}' % mode: 2}
