"""The pod deployment as a user of ``MemorySystem(mesh=...)`` stands it up
(PR 29; tier-1, CPU, 4 of conftest's 8 host devices, seeded rows): the arena
is created and grown in its shards, a sharded exact top-k equals the plain
unsharded one whichever shard holds the row, a boosting dispatch donates the
state unless a reader really holds a snapshot, and the sharded path's puts lie
under ``lz.index.stage``."""

import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402  (the benchmark's plain top-k)
from lazzaro_tpu.config import MemoryConfig  # noqa: E402
from lazzaro_tpu.core import state as S  # noqa: E402
from lazzaro_tpu.core.index import MemoryIndex  # noqa: E402
from lazzaro_tpu.core.memory_system import MemorySystem  # noqa: E402
from lazzaro_tpu.parallel.mesh import make_mesh  # noqa: E402
from lazzaro_tpu.reliability.errors import DeviceOom  # noqa: E402
from lazzaro_tpu.serve import RetrievalRequest  # noqa: E402
from lazzaro_tpu.utils import telemetry as T  # noqa: E402

N = 4
D = 32
BLOCKS_A_SHARD = 3
SHARD = BLOCKS_A_SHARD * S.TOPK_BLOCK            # 12,288 rows a chip
CAPACITY = N * SHARD - 1
TENANT_ROWS = 4000                               # 12 tenants, tenant-major
TENANTS = 12
LIMITS = {"score_gap": 2e-4, "rank_errors": 0, "foreign_ids": 0,
          "count_errors": 0, "unanswered": 0, "swallowed": 0}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(("data",), (N,), devices=jax.devices()[:N])


def _rows(t):
    rng = np.random.default_rng([29, t])
    return rng.standard_normal((TENANT_ROWS, D)).astype(np.float32)


def _columns(state):
    return [(type(state).__name__ + "." + str(path[0].name), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]]


def _each_chip_holds_a_share(state, mesh):
    devices = list(mesh.devices.flat)
    for name, col in _columns(state):
        shards = col.addressable_shards
        assert {s.device for s in shards} == set(devices), name
        assert {s.data.shape[0] for s in shards} == {col.shape[0] // N}, name
        # contiguous row blocks in mesh order, none replicated
        starts = sorted(s.index[0].start or 0 for s in shards)
        assert starts == [i * (col.shape[0] // N) for i in range(N)], name


@pytest.fixture(scope="module")
def system(mesh, tmp_path_factory):
    ms = MemorySystem(
        config=MemoryConfig(
            embed_dim=D, dtype="bfloat16", initial_capacity=CAPACITY,
            max_edges=1023, serve_batch_max=16, max_buffer_size=4 * N * SHARD,
            enable_async=False, enable_hierarchy=False, auto_consolidate=False,
            load_from_disk=False,
            db_dir=str(tmp_path_factory.mktemp("pod") / "db")),
        verbose=False, mesh=mesh)
    for t in range(TENANTS):
        ms.index.add([f"t{t}:f{j}" for j in range(TENANT_ROWS)], _rows(t),
                     [0.6] * TENANT_ROWS, [0.0] * TENANT_ROWS,
                     ["semantic"] * TENANT_ROWS, ["default"] * TENANT_ROWS,
                     f"t{t}")
    yield ms
    ms.close()


# ------------------------------------------- (b) made and grown in its shards

def test_arena_and_edges_are_constructed_in_their_shards(system, mesh):
    idx = system.index
    assert idx.state.capacity == CAPACITY            # as configured, no growth
    _each_chip_holds_a_share(idx.state, mesh)
    _each_chip_holds_a_share(idx.edge_state, mesh)


@pytest.mark.parametrize("make", [
    lambda c: S.init_arena(c, D, jnp.bfloat16), S.init_edges],
    ids=["arena", "edges"])
def test_lowered_construction_holds_no_whole_column_and_no_collective(
        mesh, make):
    text = S.sharded_init(make, CAPACITY, mesh, "data").lower().compile().as_text()
    assert not re.search(r"all-gather|all-reduce|collective-permute|all-to-all",
                         text)
    lengths = {int(m) for m in re.findall(r"\b[a-z]+[0-9]*\[([0-9]+)[,\]]", text)}
    assert SHARD in lengths and max(lengths) == SHARD   # never N * SHARD rows


def test_growth_under_a_mesh_keeps_every_column_in_its_shards(mesh):
    idx = MemoryIndex(dim=8, capacity=4 * N - 1, edge_capacity=4 * N - 1,
                      mesh=mesh)
    rng = np.random.default_rng(7)
    emb = rng.standard_normal((40, 8)).astype(np.float32)
    ids = [f"n{i}" for i in range(40)]
    idx.add(ids[:12], emb[:12], [0.5] * 12, [0.0] * 12, ["semantic"] * 12,
            ["default"] * 12, "u")
    idx.add_edges([(f"n{i}", f"n{i + 1}", 0.5) for i in range(11)], "u")
    before = (idx.state.capacity, idx.edge_state.capacity)
    old_rows = np.asarray(idx.state.emb)[:12].copy()
    idx.add(ids[12:], emb[12:], [0.5] * 28, [0.0] * 28, ["semantic"] * 28,
            ["default"] * 28, "u")                       # arena grows
    idx.add_edges([(f"n{i}", f"n{i + 2}", 0.4) for i in range(30)], "u")
    assert idx.state.capacity > before[0]
    assert idx.edge_state.capacity > before[1]
    _each_chip_holds_a_share(idx.state, mesh)
    _each_chip_holds_a_share(idx.edge_state, mesh)
    # rows kept their global numbers through the move between chips
    np.testing.assert_array_equal(np.asarray(idx.state.emb)[:12], old_rows)
    for i in (0, 11, 12, 39):
        assert idx.search(emb[i], "u", k=1)[0] == [f"n{i}"]
    assert idx.edge_weights_for([("n0", "n1"), ("n5", "n7")]).keys() == {
        ("n0", "n1"), ("n5", "n7")}


@pytest.mark.parametrize("old,new", [(4 * N, 8 * N), (5 * N, 7 * N),
                                     (100 * N, 333 * N), (7 * N, 64 * N)])
def test_sharded_growth_equals_the_whole_one(mesh, old, new):
    make = lambda c: S.init_arena(c, 8, jnp.bfloat16)   # noqa: E731
    rng = np.random.default_rng(old)
    host = jax.tree_util.tree_map(
        lambda a: rng.integers(0, 100, a.shape).astype(a.dtype),
        S.sharded_init(make, old - 1, mesh, "data")())
    placed = jax.tree_util.tree_map(
        lambda h, spec: jax.device_put(
            h, jax.sharding.NamedSharding(mesh, spec)),
        host, S._row_specs(host, "data"))
    grown = S.grow_sharded(make, placed, new - 1, mesh, "data")
    whole = S.grow_arena(jax.tree_util.tree_map(jnp.asarray, host), new - 1)
    for (name, a), (_, b) in zip(_columns(grown), _columns(whole)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)
    _each_chip_holds_a_share(grown, mesh)


def test_growth_a_chip_cannot_hold_is_refused_typed(mesh, monkeypatch):
    idx = MemoryIndex(dim=8, capacity=4 * N - 1, edge_capacity=4 * N - 1,
                      mesh=mesh)
    dev = type(jax.devices()[0])
    monkeypatch.setattr(dev, "memory_stats", lambda self: {
        "bytes_limit": 1000, "bytes_in_use": 900}, raising=False)
    state = idx.state
    with pytest.raises(DeviceOom, match="Preallocate the deployment"):
        idx._alloc_rows(4 * N + 1)
    assert idx.state is state                    # nothing was touched


# ------------------------- (a) the sharded top-k equals the plain unsharded one

STRADDLING = [3, 6, 9]        # rows 12,000-16,000 / 24,000-28,000 / 36,000-40,000
LAST_SHARD = [10, 11]         # rows 40,000-48,000, all on chip 3


def test_tenants_lie_where_the_test_says(system):
    for t in STRADDLING:
        lo, hi = t * TENANT_ROWS, (t + 1) * TENANT_ROWS - 1
        assert lo // SHARD + 1 == hi // SHARD
    for t in LAST_SHARD:
        assert t * TENANT_ROWS // SHARD == N - 1
    rows = system.index.id_to_row
    assert all(rows[f"t{t}:f{j}"] == t * TENANT_ROWS + j
               for t in STRADDLING + LAST_SHARD for j in (0, TENANT_ROWS - 1))


@pytest.mark.parametrize("t", STRADDLING + LAST_SHARD + [0])
def test_served_answers_equal_the_plain_reference(system, t):
    k = 5
    rows = reference.stored(_rows(t), "bfloat16")
    live = np.ones(TENANT_ROWS, bool)
    # facts on both sides of the tenant's shard boundary, nudged
    rng = np.random.default_rng([31, t])
    edge = SHARD - (t * TENANT_ROWS) % SHARD if t in STRADDLING else 2000
    facts = [0, edge - 1, edge % TENANT_ROWS, TENANT_ROWS - 1,
             *rng.integers(0, TENANT_ROWS, 12)]
    q = (_rows(t)[facts]
         + 0.05 * rng.standard_normal((len(facts), D)).astype(np.float32))
    sched = system._ensure_scheduler()
    futs = sched.submit_many([RetrievalRequest(query=v, tenant=f"t{t}", k=k)
                              for v in q])
    variants = reference.query_variants(rows, live, q, k, "bfloat16")
    cmp = reference.Comparison(LIMITS)
    for n, fut in enumerate(futs):
        res = fut.result(timeout=120)
        who = [nid.partition(":") for nid in res.ids]
        assert all(name == f"t{t}" for name, _, _ in who)
        cmp.answer(f"tenant {t} request {n}", [int(f[1:]) for _, _, f in who],
                   list(res.scores), [tuple(v[n] for v in var)
                                      for var in variants], live)
    assert cmp.answers == len(facts)
    assert cmp.correct, cmp.first_fault
    numbers = cmp.numbers()
    assert numbers["score_gap"]["value"] <= 2e-4
    assert numbers["rank_errors"]["value"] == 0


# ----------------------------------------------- (c) sole ownership under a mesh

def _boosting(system, t=0, n=3):
    q = _rows(t)[:n]
    return [RetrievalRequest(query=v, tenant=f"t{t}", k=5, boost=True)
            for v in q]


def _serve(system, reqs):
    return system.index.search_fused_requests(
        reqs, cap_take=3, max_nbr=8, super_gate=0.4, acc_boost=0.05,
        nbr_boost=0.02)


def test_boosting_dispatch_with_no_snapshot_donates_the_state(system):
    idx, tel = system.index, system.telemetry
    _serve(system, _boosting(system))                     # compiled, warm
    copies = tel.counter_total("serve.copy_dispatches")
    old = idx._state                                      # no public snapshot
    leaves = jax.tree_util.tree_leaves(old)
    del old
    out = _serve(system, _boosting(system))
    assert [r.ids[0] for r in out] == ["t0:f0", "t0:f1", "t0:f2"]
    assert tel.counter_total("serve.copy_dispatches") == copies
    assert all(a.is_deleted() for a in leaves)            # donated, in place
    assert not any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(idx.state))


def test_boosting_dispatch_with_a_held_snapshot_copies_and_leaves_it_readable(
        system):
    idx, tel = system.index, system.telemetry
    _serve(system, _boosting(system))
    snap = idx.state                                      # a reader's snapshot
    before = np.asarray(snap.access_count).copy()
    copies = tel.counter_total("serve.copy_dispatches")
    _serve(system, _boosting(system))
    assert tel.counter_total("serve.copy_dispatches") == copies + 1
    assert tel.snapshot()["counters"][
        'serve.copy_dispatches{mode="sharded_exact"}'] >= 1
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(snap))
    np.testing.assert_array_equal(np.asarray(snap.access_count), before)
    after = np.asarray(idx.state.access_count)
    assert (after[:3] == before[:3] + 1).all()            # the boost landed
    _each_chip_holds_a_share(idx.state, system.mesh)


def test_one_chip_boosting_dispatch_counts_its_copy_too(tmp_path):
    idx = MemoryIndex(dim=8, capacity=63, edge_capacity=31)
    rng = np.random.default_rng(3)
    emb = rng.standard_normal((20, 8)).astype(np.float32)
    idx.add([f"n{i}" for i in range(20)], emb, [0.5] * 20, [0.0] * 20,
            ["semantic"] * 20, ["default"] * 20, "u")
    reqs = [RetrievalRequest(query=emb[0], tenant="u", k=3, boost=True)]
    kw = dict(cap_take=3, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02)
    idx.search_fused_requests(reqs, **kw)
    assert idx.telemetry.counter_total("serve.copy_dispatches") == 0
    snap = idx.state
    idx.search_fused_requests(reqs, **kw)
    assert idx.telemetry.counter_total("serve.copy_dispatches") == 1
    assert not snap.emb.is_deleted()


def test_merge_candidates_count_what_the_merge_gathers(system):
    tel = system.telemetry
    before = tel.counter_total("serve.merge_candidates")
    reqs = [RetrievalRequest(query=v, tenant="t1", k=5)
            for v in _rows(1)[:5]]
    _serve(system, reqs)
    padded = system.index.serve_pad_granularity         # 5 requests pad to it
    k_merge = system.index.serve_k_max                   # the ragged ceiling
    assert -(-5 // padded) * padded == padded
    assert (tel.counter_total("serve.merge_candidates") - before
            == N * padded * k_merge)


# ------------------- (d) the stage span holds the staging, and no put (PR 37)

def test_sharded_dispatch_puts_nothing_beside_its_carrier(system,
                                                          monkeypatch):
    log = []

    class Recorded(T.Span):
        def __enter__(self):
            super().__enter__()
            log.append((threading.current_thread().name, self.name,
                        self.parent))
            return self

    sched = system._ensure_scheduler()
    req = RetrievalRequest(query=_rows(2)[7], tenant="t2", k=5)
    assert sched.submit(req).result(timeout=120).ids[0] == "t2:f7"
    time.sleep(0.05)
    monkeypatch.setattr(T, "Span", Recorded)
    puts = []

    def spy(real):
        def put(x, *a, **kw):
            if isinstance(x, np.ndarray):
                span = T.current_span()
                puts.append(span.name if span else None)
            return real(x, *a, **kw)
        return put
    monkeypatch.setattr("lazzaro_tpu.core.index.jnp.asarray",
                        spy(jnp.asarray))
    monkeypatch.setattr("lazzaro_tpu.core.index.jax.device_put",
                        spy(jax.device_put))
    tel = system.telemetry
    before = (tel.counter_total("serve.h2d_puts"),
              tel.counter_total("serve.dispatches"))
    assert sched.submit(req).result(timeout=120).ids[0] == "t2:f7"
    deadline = time.time() + 10
    while (not any(n == "sched.idle" for _, n, _ in log)
           and time.time() < deadline):
        time.sleep(0.005)
    monkeypatch.undo()
    mine = [(n, p) for t, n, p in log if t != "MainThread"]
    assert [n for n, _ in mine] == [
        "sched.account", "index.pack", "index.stage", "serve.sharded_exact",
        "dispatch.launch", "dispatch.readback", "index.decode", "sched.demux",
        "sched.idle"]
    parents = dict(mine)
    assert parents["dispatch.launch"] == "serve.sharded_exact"
    assert parents["dispatch.readback"] == "serve.sharded_exact"
    assert parents["index.stage"] == parents["serve.sharded_exact"] is None
    # the parent made nine puts under index.stage; the dispatch's requests
    # now cross as ONE carrier, handed to the launch as the host array it is
    assert puts == []
    assert (tel.counter_total("serve.h2d_puts") - before[0]
            == tel.counter_total("serve.dispatches") - before[1] == 1)
