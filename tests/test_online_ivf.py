"""Online IVF maintenance (ISSUE 12): cluster assignments kept by the
fused ingest dispatch itself — k-means build pauses gone.

The tentpole invariants these tests pin:

- ONE ingest dispatch per conversation with ``ivf_online`` on, single-chip
  AND on a 2-way mesh (the member append + mini-batch centroid step ride
  the dispatch that already scores the batch — jit counters prove no
  extra kernel runs);
- recall parity under churn: online-maintained tables vs a from-scratch
  offline ``build_ivf`` over the same drifted corpus, at nprobe ∈ {4, 8};
- member-pool overflow re-inserts host-side (exact-scan extras), on both
  ingest paths, with nothing ever dropped;
- ``ivf_maintenance`` is demoted to a re-seed: ingest growth alone never
  triggers it, a centroid-count change does;
- IVF × tiering: demote → serve → promote round-trips with no dense-scan
  fallback and exact scores;
- the readback-tail counters cost ZERO added dispatches.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.ops.ivf import assignment_staleness, build_ivf
from lazzaro_tpu.serve.scheduler import RetrievalRequest
from lazzaro_tpu.utils.telemetry import Telemetry

D = 24
SEED_N = 512


def _clustered(n, n_centers=8, seed=0, spread=0.15, centers=None,
               drift=0.0):
    rng = np.random.default_rng(seed)
    if centers is None:
        centers = rng.standard_normal((n_centers, D))
        centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    if drift:
        centers = centers + drift * rng.standard_normal(centers.shape)
        centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, len(centers), n)
    emb = centers[assign] + spread * rng.standard_normal((n, D))
    return emb.astype(np.float32), centers


def _seeded_index(n=SEED_N, nprobe=4, cap=2047, seed=0, online=True,
                  member_cap_factor=4, **kw):
    """Index with a seeded build over a clustered corpus (the build is
    published through the ``_ivf`` setter, which also seeds the live
    online tables)."""
    emb, centers = _clustered(n, seed=seed)
    idx = MemoryIndex(D, capacity=cap, ivf_nprobe=nprobe,
                      ivf_online=online,
                      ivf_member_cap_factor=member_cap_factor, **kw)
    ids = [f"n{i}" for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n, ["s"] * n,
            "t0")
    idx._ivf = build_ivf(idx.state.emb, np.asarray(idx.state.alive),
                         member_cap_factor=member_cap_factor)
    return idx, emb, centers


def _ingest(idx, emb, tenant="t0", prefix="x", gate=0.999):
    n = len(emb)
    pending = idx.ingest_batch_dedup(emb, [0.5] * n, [1.0] * n,
                                     ["semantic"] * n, ["s"] * n, tenant,
                                     dedup_gate=gate)
    ids = [None if pending["dup"][i] else f"{prefix}{i}" for i in range(n)]
    idx.commit_ingest_dedup(pending, ids)
    return [i for i in ids if i], pending


def _recall(idx, queries, truth_ids, k=10):
    got = idx.search_batch(queries, "t0", k=k)
    hits = 0
    for (ids, _), want in zip(got, truth_ids):
        hits += len(set(ids[:k]) & set(want[:k]))
    return hits / (k * len(queries))


def _exact_truth(idx, queries, k=10):
    return [ids for ids, _ in idx.search_batch(queries, "t0", k=k,
                                               exact=True)]


# ------------------------------------------------------------- assignments
def test_online_append_routes_rows_and_keeps_residual_empty():
    """Fused-ingested rows land in member tables in-dispatch: routed
    immediately, fresh residual stays EMPTY (the pre-ISSUE-12 behavior
    grew it with every batch until the next rebuild)."""
    idx, emb, centers = _seeded_index()
    occ0 = int(idx._ivf_dev[2].sum())
    batch, _ = _clustered(32, centers=centers, seed=5)
    live, pending = _ingest(idx, batch)
    assert pending["ivf_host"] is not None
    assert len(idx._ivf_fresh) == 0
    assert int(idx._ivf_dev[2].sum()) == occ0 + len(live)
    # every appended row's recorded cluster was the argmax under the
    # centroids the dispatch scored against
    pos = np.asarray(pending["ivf_host"][1])[:, 0]
    assert (pos[np.asarray(~pending["dup"])] >= 0).all()


def test_assignment_staleness_bounded_under_mild_drift():
    """The mini-batch centroid step moves centroids a bounded amount per
    batch, so existing assignments stay near-fresh (the bench gates the
    measured fraction at ≤ 0.02; here we pin the probe itself works and
    stays small on a mildly drifting stream)."""
    idx, emb, centers = _seeded_index()
    for r in range(6):
        batch, centers = _clustered(48, centers=centers, seed=10 + r,
                                    drift=0.01)
        _ingest(idx, batch, prefix=f"r{r}_")
    dev = idx._ivf_dev
    frac = assignment_staleness(idx.state.emb, np.asarray(idx.state.alive),
                                dev[0], dev[1])
    assert 0.0 <= frac <= 0.05
    assert idx.ivf_staleness_probe() == pytest.approx(frac)


# ------------------------------------------------------------ churn parity
@pytest.mark.parametrize("nprobe", [4, 8])
def test_churn_recall_parity_vs_offline_rebuild(nprobe):
    """Drifting clustered churn: online-maintained tables must match a
    from-scratch offline build's recall@10 within the floor — the
    acceptance bar that lets the stop-the-world rebuild go."""
    idx, emb, centers = _seeded_index(nprobe=nprobe, seed=1)
    rng = np.random.default_rng(9)
    for r in range(5):
        batch, centers = _clustered(64, centers=centers, seed=20 + r,
                                    drift=0.02)
        _ingest(idx, batch, prefix=f"c{r}_")
        # delete a few old rows: churn, not just growth
        dead = [f"n{i}" for i in rng.integers(0, SEED_N, 8)]
        idx.delete(dead)

    # offline oracle: SAME final corpus, fresh offline k-means build
    oracle = MemoryIndex(D, capacity=2047, ivf_nprobe=nprobe,
                         ivf_online=False)
    ids, embs = [], []
    for nid, row in idx.id_to_row.items():
        ids.append(nid)
        embs.append(np.asarray(idx.state.emb[row], np.float32))
    embs = np.stack(embs)
    oracle.add(ids, embs, [0.5] * len(ids), [0.0] * len(ids),
               ["semantic"] * len(ids), ["s"] * len(ids), "t0")
    oracle._ivf = build_ivf(oracle.state.emb,
                            np.asarray(oracle.state.alive))

    queries, _ = _clustered(32, centers=centers, seed=77)
    truth = _exact_truth(idx, queries)
    online = _recall(idx, queries, truth)
    offline = _recall(oracle, queries, truth)
    assert online >= offline - 0.05, (online, offline)


# ---------------------------------------------------------------- overflow
def test_member_pool_overflow_reinserts_into_extras():
    """A cluster at capacity spills its appends to the exact-scan extras
    (readback position -1, host re-insert — like link-pool overflow):
    nothing is dropped, the spilled rows serve exactly."""
    idx, emb, centers = _seeded_index(member_cap_factor=1,
                                      telemetry=Telemetry(256))
    # hammer ONE cluster until its table must spill
    target = centers[0]
    batch = (np.tile(target, (96, 1))
             + 0.05 * np.random.default_rng(3).standard_normal((96, D))
             ).astype(np.float32)
    live, pending = _ingest(idx, batch)
    dup = np.asarray(pending["dup"])
    pos = np.asarray(pending["ivf_host"][1])[:len(dup), 0]
    spilled = int(((pos < 0) & ~dup).sum())
    assert spilled > 0, "fixture failed to overflow the member pool"
    assert len(idx._ivf_fresh) == spilled
    # overflow flag rode the readback; the telemetry counter saw it
    snap = idx.telemetry.snapshot()
    assert any(k.startswith("ivf.member_overflows")
               for k in snap["counters"])
    # spilled rows are served (exactly, from the extras)
    got = idx.search(batch[-1], "t0", k=10)
    assert set(got[0]) & set(live)


def test_pod_member_overflow_reinserts_into_extras():
    """Same overflow contract on the distributed ingest path."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    idx = ShardedMemoryIndex(mesh, D, capacity=1023, edge_capacity=2047,
                             ivf_member_cap_factor=1)
    emb, centers = _clustered(300, seed=2)
    idx.add([f"n{i}" for i in range(300)], emb, "t0")
    assert idx.ivf_build(n_clusters=8, nprobe=4)
    target = centers[0]
    batch = (np.tile(target, (120, 1))
             + 0.05 * np.random.default_rng(4).standard_normal((120, D))
             ).astype(np.float32)
    out = idx.ingest([f"x{i}" for i in range(120)], batch, "t0",
                     dedup_gate=1.01)
    assert len(idx._ivf_fresh) > 0, "pod overflow should spill to extras"
    got = idx.search(batch[-1], "t0")
    assert set(got[0]) & set(out["created"])


# ------------------------------------------------------------ jit counters
_COUNTED = ("ingest_dedup_fused", "ingest_dedup_fused_copy", "arena_add",
            "arena_add_copy", "arena_merge_touch", "arena_merge_touch_copy",
            "edges_add", "edges_add_copy", "arena_search",
            "ivf_members_drop", "ivf_members_drop_copy")


def _count(monkeypatch):
    calls = {name: 0 for name in _COUNTED}
    for name in _COUNTED:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    return calls


def test_one_dispatch_per_conversation_with_online_ivf(monkeypatch):
    """The ISSUE 12 invariant: with live online tables the whole ingest —
    dedup probe, node scatter, links, member append, centroid step — is
    STILL one dispatch; no maintenance kernel appears beside it."""
    idx, emb, centers = _seeded_index(telemetry=Telemetry(256))
    batch, _ = _clustered(16, centers=centers, seed=6)
    calls = _count(monkeypatch)
    _ingest(idx, batch)
    assert calls["ingest_dedup_fused"] == 1
    for name in _COUNTED:
        if name != "ingest_dedup_fused":
            assert calls[name] == 0, (name, calls)
    # and the readback-tail counters landed without any extra dispatch
    snap = idx.telemetry.snapshot()
    assert any(k.startswith("ivf.appends") for k in snap["counters"])
    assert any(k.startswith("ivf.member_pool_occupancy")
               for k in snap["gauges"])


def test_one_distributed_dispatch_pod_online_ivf():
    """Pod twin of the counter: one ``ingest()`` mega-batch with live
    tables costs exactly ONE distributed dispatch."""
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    idx = ShardedMemoryIndex(mesh, D, capacity=1023, edge_capacity=255)
    emb, centers = _clustered(300, seed=8)
    idx.add([f"n{i}" for i in range(300)], emb, "t0")
    assert idx.ivf_build(n_clusters=8, nprobe=4)
    assert idx._ivf_dev is not None
    batch, _ = _clustered(24, centers=centers, seed=9)
    before = idx.ingest_dispatch_count
    idx.ingest([f"x{i}" for i in range(24)], batch, "t0", dedup_gate=0.999)
    assert idx.ingest_dispatch_count - before == 1
    # the pod serve tables are the live arrays the dispatch just updated
    tabs = idx._ivf_tables(8)
    assert tabs is not None and tabs[1] is idx._ivf_dev[1]


def test_nondedup_ingest_batch_mesh_one_distributed_dispatch():
    """ROADMAP residual closed: non-dedup ``ingest_batch`` under a mesh
    routes through the sharded factory's ``dedup=False`` program — ONE
    distributed dispatch (the GSPMD fallback re-replicated candidate
    tensors chip-to-chip); ``ingest_sharded=False`` keeps the plain-jit
    partitioning for A/B."""
    from lazzaro_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    emb0, _ = _clustered(50, seed=14)

    def run(sharded):
        idx = MemoryIndex(D, capacity=1023, edge_capacity=511, mesh=mesh,
                          ingest_sharded=sharded)
        idx.add([f"n{i}" for i in range(50)], emb0, [0.5] * 50, [0.0] * 50,
                ["semantic"] * 50, ["s"] * 50, "t0")
        before = idx.ingest_dispatch_count
        batch, _ = _clustered(10, seed=15)
        rows, cands, created = idx.ingest_batch(
            [f"m{i}" for i in range(10)], batch, [0.5] * 10, [1.0] * 10,
            ["semantic"] * 10, ["s"] * 10, "t0",
            merge_ids=["n0"], merge_saliences=[0.9],
            chain_pairs=[("n0", "n1")], link_k=3)
        return idx, idx.ingest_dispatch_count - before, cands

    idx_s, n_disp, cands_s = run(True)
    assert n_disp == 1
    idx_g, _, cands_g = run(False)         # GSPMD fallback, same semantics
    for sm in cands_s:
        for nid in cands_s[sm]:
            ids_s = [c for c, _ in cands_s[sm][nid]]
            ids_g = [c for c, _ in cands_g[sm][nid]]
            assert ids_s == ids_g, (sm, nid)
    got = idx_s.search_batch(_clustered(10, seed=15)[0], "t0", k=3)
    assert all(ids for ids, _ in got)


# ------------------------------------------------------- maintenance demote
def test_ingest_growth_never_triggers_reseed_but_count_change_does():
    """Online mode: ``ivf_maintenance`` no longer rebuilds on fresh-row
    growth (appends are routed), only on a centroid-count change or
    delete churn."""
    idx, emb, centers = _seeded_index(cap=2 ** 14 - 1)
    # bypass the min-rows floor: pretend the corpus is big enough
    monkey_min = MemoryIndex._IVF_MIN_ROWS
    try:
        MemoryIndex._IVF_MIN_ROWS = 1
        batch, _ = _clustered(256, centers=centers, seed=11)
        _ingest(idx, batch)
        assert idx.ivf_maintenance() is False, \
            "routed growth must not trigger a rebuild"
        # grow until the IDEAL √N cluster count doubles the live table's
        # (build C = pow2(√512) = 32 → re-seed once √N ≥ 64, N ≥ 4096)
        more, _ = _clustered(8 * SEED_N, centers=centers, seed=12)
        for i in range(0, len(more), 512):
            _ingest(idx, more[i:i + 512], prefix=f"g{i}_")
        assert idx.ivf_maintenance() is True
        assert len(idx._ivf_fresh) == 0
    finally:
        MemoryIndex._IVF_MIN_ROWS = monkey_min


def test_offline_mode_keeps_classic_rebuild_semantics():
    """``ivf_online=False`` preserves the PR 4 behavior: fresh rows pile
    into the residual and the 25% trigger still rebuilds."""
    idx, emb, centers = _seeded_index(online=False)
    assert idx._ivf_dev is None
    batch, _ = _clustered(40, centers=centers, seed=13)
    _ingest(idx, batch)
    assert len(idx._ivf_fresh) == 40


# ------------------------------------------------------------ IVF × tiering
def test_ivf_tiering_demote_promote_round_trip(monkeypatch):
    """The PR 8 residual is gone: with a build published and rows demoted,
    serving routes the IVF×tiered program (never the dense fallback),
    cold hits rescore exactly through the bounded finish, and a
    demote→promote round trip returns to exact IVF serving."""
    idx, emb, centers = _seeded_index(n=1024, cap=4095, int8_serving=True,
                                      telemetry=Telemetry(256))
    tm = idx.enable_tiering(hot_budget_rows=600)
    cold_rows = list(range(0, 400))
    assert tm.demote_rows(cold_rows) == 400

    kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02)
    reqs = [RetrievalRequest(query=emb[i], tenant="t0", k=10)
            for i in (0, 100, 700)]
    mode = idx._serve_route(5).mode
    assert mode == "ivf_tiered"
    res = idx.search_fused_requests(reqs, **kw)
    for i, r in zip((0, 100, 700), res):
        assert f"n{i}" in r.ids[:3]
        assert r.scores[r.ids.index(f"n{i}")] == pytest.approx(1.0,
                                                               abs=1e-3)
    # members were scrubbed on demote: no member slot points at a cold row
    members = np.asarray(idx._ivf_dev[1])
    safe = np.maximum(members, 0)
    assert not (tm.cold_np[safe] & (members >= 0)).any()

    tm.promote_rows(cold_rows)
    assert tm.cold_count == 0
    mode2 = idx._serve_route(5).mode
    assert mode2 == "ivf"                      # pure IVF serving again
    res2 = idx.search_fused_requests(reqs, **kw)
    for i, r in zip((0, 100, 700), res2):
        assert f"n{i}" in r.ids[:3]


def test_reseed_under_tiering_excludes_cold_rows():
    """A re-seed while rows are cold must never cluster their zeroed
    master embeddings — cold rows stay covered by the residency-masked
    shadow coarse path."""
    idx, emb, centers = _seeded_index(n=1024, cap=4095, int8_serving=True)
    tm = idx.enable_tiering(hot_budget_rows=600)
    tm.demote_rows(list(range(0, 300)))
    monkey_min = MemoryIndex._IVF_MIN_ROWS
    try:
        MemoryIndex._IVF_MIN_ROWS = 1
        idx._ivf_stale = 10 ** 9               # force the re-seed branch
        assert idx.ivf_maintenance() is True
    finally:
        MemoryIndex._IVF_MIN_ROWS = monkey_min
    members = np.asarray(idx._ivf_dev[1])
    safe = np.maximum(members, 0)
    assert not (tm.cold_np[safe] & (members >= 0)).any()
