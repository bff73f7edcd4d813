"""Pod-scale fused INGEST (ISSUE 9): single-chip vs mesh parity.

The full write-path program — dedup probe, intra-batch gram resolve, node
scatter, merge touch, both link scans, gated edge insert with prefix-sum
pool compaction, incremental int8 shadow update — must run as ONE
distributed shard_map dispatch (``state.make_ingest_fused_sharded``) and
be BIT-IDENTICAL to the single-chip ``ingest_dedup_fused``: the shard-
local scan cores are the same code, the grouped all_gather merge preserves
top-k order, and every write lands owner-chip-local. These tests pin that
parity at the state level (arena columns, edge pool, shadow, dedup
resolutions, overflow) on 2- and 4-way host-device meshes, plus the index
wiring: ``ShardedMemoryIndex.ingest`` fused-vs-classic semantic parity,
one distributed dispatch per coalesced mega-batch (jit counter), zero
added dispatches with telemetry on (the PR 6 guarantee extended to the
write path), ``MemoryIndex(mesh=...)`` routing, and ``warmup_ingest``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.parallel.index import ShardedMemoryIndex
from lazzaro_tpu.parallel.mesh import make_mesh

D = 16
CAP = 127          # cap+1 = 128 divides both mesh shapes
ECAP = 255
K = 3


def _mesh(n):
    return make_mesh(("data",), (n,), devices=jax.devices()[:n])


def _shard(pytree, mesh):
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, mat if a.ndim == 2 else row), pytree)


def _prefilled(n0=60, seed=0):
    """Arena with ``n0`` rows across 3 shard groups, some supers, plus an
    empty edge arena and a fresh int8 shadow."""
    from lazzaro_tpu.ops.quant import quantize_rows

    rng = np.random.default_rng(seed)
    arena = S.init_arena(CAP, D, jnp.float32)
    emb0 = rng.standard_normal((n0, D)).astype(np.float32)
    arena = S.arena_add_copy(
        arena, jnp.arange(n0, dtype=jnp.int32), jnp.asarray(emb0),
        jnp.full((n0,), 0.5, jnp.float32), jnp.zeros((n0,), jnp.float32),
        jnp.zeros((n0,), jnp.int32),
        jnp.asarray((np.arange(n0) % 3).astype(np.int32)),
        jnp.zeros((n0,), jnp.int32),
        jnp.asarray(np.arange(n0) % 9 == 0))
    edges = S.init_edges(ECAP)
    q8, scale = quantize_rows(arena.emb)
    return arena, edges, (q8, scale)


def _batch_args(arena, n=10, seed=3, pool_len=None):
    """A fact batch with one dup-of-existing, one intra-batch dup, one
    dup-of-the-dup, a sub-gate near-neighbor, and sentinel padding."""
    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n, D)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    base5 = np.array(arena.emb[5], np.float32)
    base5 /= max(float(np.linalg.norm(base5)), 1e-9)
    emb[3] = base5 + 0.03 * rng.standard_normal(D)     # dup of row 5
    emb[7] = emb[2] + 0.03 * rng.standard_normal(D)    # dup of fact 2
    emb[8] = emb[7] + 0.03 * rng.standard_normal(D)    # dup-of-the-dup
    b10 = np.array(arena.emb[10], np.float32)
    b10 /= max(float(np.linalg.norm(b10)), 1e-9)
    emb[0] = 0.8 * b10 + 0.45 * rng.standard_normal(D)  # links, no dup

    rows = np.arange(60, 60 + n, dtype=np.int32)
    padded = S.pad_rows(rows, CAP)
    b = len(padded)
    emb_p = np.zeros((b, D), np.float32)
    emb_p[:n] = emb
    emb_p[n:, 0] = 1.0

    def pad(vals, fill=0.0, dt=np.float32):
        out = np.full((b,), fill, dt)
        out[:n] = vals
        return out

    chain_slots = np.full((b,), ECAP, np.int32)
    chain_slots[:n] = np.arange(10, 10 + n)
    worst = 2 * n * K
    pool_list = list(range(40, 40 + worst))
    if pool_len is None:
        pool_len = worst
    link_pool = np.full((worst + 1,), ECAP, np.int32)
    link_pool[:len(pool_list)] = pool_list
    return (jnp.asarray(padded), jnp.asarray(emb_p),
            jnp.asarray(pad([0.6] * n)), jnp.asarray(pad([1.0] * n)),
            jnp.asarray(pad([0] * n, 0, np.int32)),
            jnp.asarray(pad(np.arange(n) % 3, -1, np.int32)),
            jnp.asarray(pad([0] * n, -1, np.int32)),
            jnp.asarray(pad([False] * n, False, bool)),
            jnp.asarray(pad([0] * n, -1, np.int32)),
            jnp.asarray(chain_slots), jnp.asarray(link_pool),
            jnp.int32(pool_len), jnp.float32(2.0), jnp.int32(0),
            jnp.float32(0.95), jnp.float32(0.5), jnp.float32(0.4),
            jnp.float32(0.8), jnp.float32(1.0))


ARENA_COLS = ("emb", "salience", "timestamp", "last_accessed",
              "access_count", "type_id", "shard_id", "tenant_id", "alive",
              "is_super")
EDGE_COLS = ("src", "tgt", "weight", "co", "last_updated", "alive",
             "tenant_id")


# A shard-local scan reduces the same products in a different order than
# the whole-arena scan, so link scores — and the edge weights scaled from
# them — agree to 2 ULP of a unit-scale f32 (the error accrues at the scale
# of the partial sums, not of the result); everything else is exact.
SCORE_ATOL = float(np.finfo(np.float32).eps)


def _assert_state_parity(a1, e1, a2, e2):
    """Arena + edge columns bit-identical (edge weights to ``SCORE_ATOL``)
    EXCLUDING the sentinel row/slot (duplicate-index scatter order at the
    sentinel is compiler-defined)."""
    for col in ARENA_COLS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a1, col))[:CAP],
            np.asarray(getattr(a2, col))[:CAP], err_msg=col)
    for col in EDGE_COLS:
        np.testing.assert_allclose(
            np.asarray(getattr(e1, col))[:ECAP],
            np.asarray(getattr(e2, col))[:ECAP], rtol=0,
            atol=SCORE_ATOL if col == "weight" else 0, err_msg="edge:" + col)


def _assert_readback_parity(out1, out2, n=10, n_modes=2):
    """Dedup verdicts, merge targets, chain sources, live rows' candidate
    rows and flags, and the counter tail must match bit for bit (dup/pad
    rows' candidate scores are readback noise both sides discard);
    candidate SCORES match to ``SCORE_ATOL``."""
    dup = np.asarray(out1[0])[:, 0]
    for wi in range(3):
        np.testing.assert_array_equal(np.asarray(out1[wi]),
                                      np.asarray(out2[wi]))
    live = ~dup.astype(bool)[:n]
    for mi in range(n_modes):
        s1 = np.asarray(out1[3 + 3 * mi])[:n][live]
        s2 = np.asarray(out2[3 + 3 * mi])[:n][live]
        lv = s1 > S.NEG_INF / 2
        np.testing.assert_allclose(s1[lv], s2[lv], rtol=0, atol=SCORE_ATOL)
        c1 = np.asarray(out1[3 + 3 * mi + 1])[:n][live]
        c2 = np.asarray(out2[3 + 3 * mi + 1])[:n][live]
        np.testing.assert_array_equal(c1[lv], c2[lv])
        np.testing.assert_array_equal(
            np.asarray(out1[3 + 3 * mi + 2])[:n][live],
            np.asarray(out2[3 + 3 * mi + 2])[:n][live])
    for ci in range(3 + 3 * n_modes, 6 + 3 * n_modes):
        np.testing.assert_array_equal(np.asarray(out1[ci])[0, 0],
                                      np.asarray(out2[ci])[0, 0])


@pytest.mark.parametrize("n_dev", [2, 4])
def test_sharded_ingest_bit_identical_to_single_chip(n_dev):
    """Arena columns, edge pool, int8 shadow, dedup resolutions, and the
    packed readback of the distributed ingest program must match the
    single-chip ``ingest_dedup_fused`` bit for bit."""
    arena, edges, shadow = _prefilled()
    args = _batch_args(arena)
    a1, e1, sh1, _, _, _, out1 = S.ingest_dedup_fused_copy(
        arena, edges, shadow, None, None, None, *args, k=K,
        shard_modes=(1, 0))
    dup = np.asarray(out1[0])[:10, 0]
    assert dup.sum() == 3, dup                 # the scenario does real work
    assert int(np.asarray(out1[10])[0, 0]) > 0  # some links accepted

    mesh = _mesh(n_dev)
    kern = S.make_ingest_fused_sharded(mesh, "data", k=K,
                                       shard_modes=(1, 0), with_shadow=True)
    row = NamedSharding(mesh, P("data"))
    mat = NamedSharding(mesh, P("data", None))
    a2, e2, q8b, sb, out2 = kern.ingest_copy(
        _shard(arena, mesh), _shard(edges, mesh),
        jax.device_put(shadow[0], mat), jax.device_put(shadow[1], row),
        *args)
    _assert_readback_parity(out1, out2)
    _assert_state_parity(a1, e1, a2, e2)
    np.testing.assert_array_equal(np.asarray(sh1[0])[:CAP],
                                  np.asarray(q8b)[:CAP])
    np.testing.assert_array_equal(np.asarray(sh1[1])[:CAP],
                                  np.asarray(sb)[:CAP])


def test_sharded_ingest_overflow_parity():
    """A pool smaller than the accepted-link count must raise the SAME
    in-kernel overflow flag, the same true prefix positions (so the host
    re-inserts exactly the overflowed edges), and the same edge-pool
    state on both paths."""
    arena, edges, shadow = _prefilled()
    args = _batch_args(arena, pool_len=2)      # force overflow
    a1, e1, _, _, _, _, out1 = S.ingest_dedup_fused_copy(
        arena, edges, None, None, None, None, *args, k=K,
        shard_modes=(1, 0))
    assert int(np.asarray(out1[9])[0, 0]) == 1  # overflow flag set
    mesh = _mesh(4)
    kern = S.make_ingest_fused_sharded(mesh, "data", k=K,
                                       shard_modes=(1, 0),
                                       with_shadow=False)
    a2, e2, out2 = kern.ingest_copy(_shard(arena, mesh),
                                    _shard(edges, mesh), *args)
    _assert_readback_parity(out1, out2)
    _assert_state_parity(a1, e1, a2, e2)


def test_donated_twin_matches_copy_twin():
    """The donated distributed program computes the same result as the
    copy twin (ownership handoff only, no numeric difference)."""
    mesh = _mesh(2)
    arena, edges, _ = _prefilled()
    args = _batch_args(arena)
    kern = S.make_ingest_fused_sharded(mesh, "data", k=K,
                                       shard_modes=(1, 0),
                                       with_shadow=False)
    a1, e1, out1 = kern.ingest_copy(_shard(arena, mesh),
                                    _shard(edges, mesh), *args)
    a2, e2, out2 = kern.ingest(_shard(arena, mesh), _shard(edges, mesh),
                               *args)
    _assert_readback_parity(out1, out2)
    _assert_state_parity(a1, e1, a2, e2)


# ------------------------------------------------------------ index wiring
_DIRS = np.random.default_rng(7).standard_normal((8, D)).astype(np.float32)
_DIRS /= np.linalg.norm(_DIRS, axis=1, keepdims=True)


def _clustered(n, seed):
    """Group-clustered vectors: intra-group cosine ~0.86 (> the 0.5 link
    gate, < the 0.95 dedup gate) so gated links do real work."""
    r = np.random.default_rng(seed)
    g = np.arange(n) % len(_DIRS)
    v = _DIRS[g] * 0.9 + 0.32 * r.standard_normal((n, D)).astype(np.float32)
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


def _pod_index(mesh, fused=True, **kw):
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=CAP, dtype=np.float32,
                             edge_capacity=511, ingest_fused=fused, **kw)
    idx.add([f"p{i}" for i in range(24)], _clustered(24, 1), "u")
    return idx


def _ingest_batch(idx, prefix="f"):
    batch = _clustered(10, 2)
    batch[3] = (_clustered(24, 1)[3]
                + 0.03 * np.random.default_rng(9).standard_normal(D))
    batch[7] = (batch[2]
                + 0.03 * np.random.default_rng(10).standard_normal(D))
    return idx.ingest([f"{prefix}{i}" for i in range(10)], batch, "u",
                      dedup_gate=0.95, chain=True, link_k=3, link_gate=0.5)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_pod_ingest_fused_matches_classic(n_dev):
    """``ShardedMemoryIndex.ingest`` fused vs the host-driven classic
    sequence: same created ids, same merge targets, same link edge set
    with matching weights, same chains — and the fused path costs ONE
    distributed dispatch where the classic pays several."""
    i1 = _pod_index(_mesh(n_dev), fused=True)
    i2 = _pod_index(_mesh(n_dev), fused=False)
    o1 = _ingest_batch(i1)
    o2 = _ingest_batch(i2)
    assert sorted(o1["created"]) == sorted(o2["created"])
    assert o1["merged"] == o2["merged"] and o1["merged"]
    assert sorted(o1["chains"]) == sorted(o2["chains"])
    l1 = sorted((s, t, w) for s, t, w in o1["links"])
    l2 = sorted((s, t, w) for s, t, w in o2["links"])
    assert [x[:2] for x in l1] == [x[:2] for x in l2]
    for a, b in zip(l1, l2):
        assert abs(a[2] - b[2]) < 1e-5
    assert set(i1.edges) == set(i2.edges)
    assert i1.ingest_dispatch_count == 1
    assert i2.ingest_dispatch_count > 1


def test_pod_ingest_one_distributed_dispatch_and_telemetry_free():
    """Jit counter: one coalesced mega-batch == ONE distributed dispatch
    (after warmup), and turning telemetry ON adds ZERO dispatches — the
    PR 6 serving guarantee extended to the write path."""
    idx = _pod_index(_mesh(4), fused=True)
    _ingest_batch(idx, prefix="w")             # warm/compile
    for enabled, prefix in ((True, "a"), (False, "b")):
        idx.telemetry.enabled = enabled
        calls = {"n": 0}
        orig = idx._ingest_dispatch

        def counting(fn, *a, __o=orig, **kw):
            calls["n"] += 1
            return __o(fn, *a, **kw)

        idx._ingest_dispatch = counting
        _ingest_batch(idx, prefix=prefix)
        idx._ingest_dispatch = orig
        assert calls["n"] == 1, (enabled, calls)
    idx.telemetry.enabled = True
    # the device-counter tail landed in the registry off the SAME readback
    assert idx.telemetry.counter_total("ingest.dedup_hits") > 0
    assert idx.telemetry.counter_total("ingest.links_accepted") > 0


def test_pod_ingest_overflow_reinsert_parity():
    """A tiny link-accept hint forces pool overflow: the overflowed edges
    are re-inserted host-side bit-identically (same edge set and weights
    as the hint=1.0 run), one pool-overflow counter bump."""
    i1 = _pod_index(_mesh(2), fused=True)
    i2 = _pod_index(_mesh(2), fused=True)
    batch = _clustered(10, 2)
    o1 = i1.ingest([f"f{i}" for i in range(10)], batch, "u", link_k=3,
                   link_gate=0.5, link_accept_hint=1.0)
    o2 = i2.ingest([f"f{i}" for i in range(10)], batch, "u", link_k=3,
                   link_gate=0.5, link_accept_hint=0.05)
    assert o1["links"] and o2["counters"]["overflow"]
    assert sorted(o1["links"]) == sorted(o2["links"])
    assert i1.link_pool_overflows == 0 and i2.link_pool_overflows == 1
    assert set(i1.edges) == set(i2.edges)


def test_pod_ingest_maintains_int8_shadow_incrementally():
    """With int8 serving on and a built shadow, the fused pod ingest
    updates the codes in-kernel (no dirty mark, codes equal a fresh
    requantize of the post-ingest master)."""
    from lazzaro_tpu.ops.quant import quantize_rows

    idx = _pod_index(_mesh(4), fused=True, int8_serving=True)
    idx._int8_shadow_for()
    _ingest_batch(idx)
    assert not idx._int8_dirty
    q8_ref, sc_ref = quantize_rows(idx.state.emb)
    np.testing.assert_array_equal(np.asarray(q8_ref)[:CAP],
                                  np.asarray(idx._int8_shadow[0])[:CAP])
    np.testing.assert_array_equal(np.asarray(sc_ref)[:CAP],
                                  np.asarray(idx._int8_shadow[1])[:CAP])


def test_pod_ingest_then_serve_roundtrip():
    """Rows written by the fused pod ingest serve through the fused pod
    retrieval path (the write and read programs share one arena)."""
    from lazzaro_tpu.serve import RetrievalRequest

    idx = _pod_index(_mesh(4), fused=True)
    _ingest_batch(idx)
    q = _clustered(10, 2)[0]
    res = idx.serve_requests([RetrievalRequest(query=q, tenant="u",
                                               k=3)])[0]
    assert res.ids and res.ids[0] == "f0"


def test_pod_warmup_ingest_leaves_corpus_unchanged():
    idx = _pod_index(_mesh(2), fused=True)
    before = set(idx.id_to_row)
    out = idx.warmup_ingest((4,))
    assert out and all(v > 0 for v in out.values())
    assert set(idx.id_to_row) == before
    key = 'kernel.warmup_ms{batch="4",path="ingest"}'
    assert idx.telemetry.timer_count("kernel.warmup_ms") >= 1
    assert any("ingest" in k for k in idx.telemetry.timers
               if k.startswith("kernel.warmup_ms"))
    del key


def test_mesh_memory_index_routes_sharded_and_matches_single_chip():
    """``MemoryIndex(mesh=...)`` ingest_batch_dedup runs the distributed
    program (one ingest dispatch) and its dedup verdicts, edges, and
    arena columns match the single-chip index on the same facts."""
    def run(mesh):
        rng = np.random.default_rng(0)
        idx = MemoryIndex(dim=D, capacity=CAP, edge_capacity=511,
                          dtype=np.float32, mesh=mesh)
        pre = rng.standard_normal((20, D)).astype(np.float32)
        idx.add([f"p{i}" for i in range(20)], pre, [0.5] * 20, [0.0] * 20,
                ["semantic"] * 20, ["a"] * 20, "u")
        batch = rng.standard_normal((6, D)).astype(np.float32)
        batch[4] = (pre[2] / np.linalg.norm(pre[2])
                    + 0.02 * rng.standard_normal(D))
        pending = idx.ingest_batch_dedup(batch, [0.6] * 6, [0.0] * 6,
                                         ["semantic"] * 6, ["a"] * 6, "u",
                                         dedup_gate=0.95)
        ids = [None if pending["dup"][i] else f"f{i}" for i in range(6)]
        _, _, merges, chains = idx.commit_ingest_dedup(pending, ids)
        return idx, np.asarray(pending["dup"]), merges, chains

    i1, d1, m1, c1 = run(_mesh(4))
    assert i1.ingest_sharded and len(i1._ingest_sharded_cache) == 1
    assert i1.ingest_dispatch_count == 1
    i2, d2, m2, c2 = run(None)
    np.testing.assert_array_equal(d1, d2)
    assert m1 == m2 and c1 == c2
    assert set(i1.edge_slots) == set(i2.edge_slots)
    for col in ("emb", "salience", "alive", "tenant_id", "access_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(i1.state, col))[:CAP],
            np.asarray(getattr(i2.state, col))[:CAP], err_msg=col)


def test_mesh_memory_index_gspmd_fallback_still_works():
    """``ingest_sharded=False`` keeps the GSPMD-partitioned plain jit
    kernel as fallback — same verdicts, no sharded kernel built."""
    rng = np.random.default_rng(1)
    idx = MemoryIndex(dim=D, capacity=CAP, edge_capacity=511,
                      dtype=np.float32, mesh=_mesh(2),
                      ingest_sharded=False)
    idx.add(["p0", "p1"], rng.standard_normal((2, D)).astype(np.float32),
            [0.5] * 2, [0.0] * 2, ["semantic"] * 2, ["a"] * 2, "u")
    pending = idx.ingest_batch_dedup(
        rng.standard_normal((4, D)).astype(np.float32), [0.5] * 4,
        [0.0] * 4, ["semantic"] * 4, ["a"] * 4, "u", dedup_gate=0.95)
    idx.commit_ingest_dedup(pending, [f"f{i}" for i in range(4)])
    assert len(idx._ingest_sharded_cache) == 0
    assert len(idx) == 6


def test_single_chip_warmup_ingest():
    """``MemoryIndex.warmup_ingest`` populates the ingest jit caches via
    the real path, records kernel.warmup_ms{path="ingest"}, and leaves
    the live corpus untouched."""
    rng = np.random.default_rng(2)
    idx = MemoryIndex(dim=D, capacity=CAP, edge_capacity=511,
                      dtype=np.float32)
    idx.add(["p0"], rng.standard_normal((1, D)).astype(np.float32),
            [0.5], [0.0], ["semantic"], ["a"], "u")
    out = idx.warmup_ingest((4,))
    assert out and all(v > 0 for v in out.values())
    assert len(idx) == 1
    assert any(k.startswith("kernel.warmup_ms") and "ingest" in k
               for k in idx.telemetry.timers)


def test_coalesce_wait_span_recorded():
    """The per-mega-batch coalesce-wait span (ISSUE 9 satellite) lands in
    the registry when consolidation drains the coalescer."""
    from lazzaro_tpu.utils.batching import IngestCoalescer

    co = IngestCoalescer(max_facts=100, max_wait_s=60.0)
    co.add_conversation([{"content": "x"}], now=100.0)
    co.add_conversation([{"content": "y"}], now=101.0)
    assert co.oldest_age_s(103.0) == pytest.approx(3.0)
    co.drain()
    assert co.oldest_age_s(104.0) == 0.0


def test_pod_add_rides_fused_ingest_no_extras_spills():
    """ISSUE 18 satellite: with live online-IVF tables, an all-fresh pod
    ``add()`` routes through the fused ingest program — the in-kernel
    assignment lands the rows in member slots, so ``ivf.add_extras_spills``
    stays flat — while add() semantics are untouched: duplicate
    embeddings still get their own rows (nothing merges), no similarity
    edges insert, and re-adds keep the classic overwrite-in-place path."""
    from lazzaro_tpu.utils.telemetry import Telemetry

    tel = Telemetry()
    rng = np.random.default_rng(9)
    idx = ShardedMemoryIndex(_mesh(2), dim=D, capacity=CAP,
                             dtype=np.float32, telemetry=tel)
    emb = rng.standard_normal((40, D)).astype(np.float32)
    idx.add([f"s{i}" for i in range(40)], emb, "u")
    assert idx.ivf_build(nprobe=4)
    spills0 = tel.counter_total("ivf.add_extras_spills")
    ing0 = idx.ingest_dispatch_count
    edges0 = len(idx.edges)
    dup = rng.standard_normal((1, D)).astype(np.float32)
    batch = np.concatenate([dup, dup,
                            rng.standard_normal((4, D)).astype(np.float32)])
    rows = idx.add([f"f{i}" for i in range(6)], batch, "u")
    # happy path: fused write, zero extras spills, rows routed in-kernel
    assert idx.ingest_dispatch_count == ing0 + 1
    assert tel.counter_total("ivf.add_extras_spills") == spills0
    assert all(idx._ivf_routed[r] for r in rows)
    assert not idx._ivf_fresh
    # add() semantics intact: 6 distinct rows (the identical pair did NOT
    # merge), every id registered, and no edges appeared
    assert len(set(rows)) == 6
    assert all(idx.id_to_row[f"f{i}"] == r for i, r in enumerate(rows))
    assert len(idx.edges) == edges0
    # a re-add of an existing id keeps the classic overwrite path
    spills1 = tel.counter_total("ivf.add_extras_spills")
    again = idx.add(["f0"], rng.standard_normal((1, D)).astype(np.float32),
                    "u")
    assert again == [rows[0]]
    assert tel.counter_total("ivf.add_extras_spills") >= spills1
    # the new facts are servable
    ids, _ = idx.search(batch[2], "u")
    assert ids[0] == "f2"
