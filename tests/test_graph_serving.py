"""A chat retrieval's boosts over an installed graph (ISSUE 39; tier-1, CPU):
the program against a plain NumPy replay on seeded rows, on one device and
on four of conftest's host devices. A FULL batch of ``serve_batch_max``
boosting requests over several tenants leaves ``access_count`` / ``salience``
/ ``last_accessed`` as the replay says: the five served rows +1 each, every
graph neighbour of a served row once a request however many served rows
list it, a served row never as a neighbour, a row with more than
``serve_max_nbr`` slots reaching the first 32 of its list, nothing of
another tenant. The CSR is built by the first dispatch after the topology
changed and by no other; ``lz.index.csr`` opens only then.

The corpus makes every request's answer unambiguous (groups of five facts at
cosine ~0.9, everything else under 0.6), so the replay needs no top-k of its
own: a request for a fact serves that fact's group."""

import time

import jax
import numpy as np
import pytest

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.parallel.mesh import make_mesh
from lazzaro_tpu.serve import RetrievalRequest
from tests.test_span_names import opened  # noqa: F401  (the span recorder)

N = 4
D = 64
SHARD = S.TOPK_BLOCK                             # 4,096 rows a chip
CAPACITY = N * SHARD - 1
GROUP = 5
TENANT_ROWS = 1000                               # 200 groups of five
TENANTS = 12                                     # three straddle a shard
BATCH = 64
K = 5
MAX_NBR = 32
SAL0, ACC, NBR = 0.6, 0.05, 0.02
HUB = (0, 500)        # (tenant, fact) listed with more than MAX_NBR slots


def _rows(t):
    rng = np.random.default_rng([39, t])
    centre = rng.standard_normal((TENANT_ROWS // GROUP, D))
    centre /= np.linalg.norm(centre, axis=1, keepdims=True)
    noise = rng.standard_normal((TENANT_ROWS, D))
    noise /= np.linalg.norm(noise, axis=1, keepdims=True)
    v = np.repeat(centre, GROUP, axis=0) + 0.3 * noise
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


def _edges(t):
    """Tenant ``t``'s (fact, fact) edge keys, in the order they are added:
    the chain j -> j + 1 (inside a group its ends are served together), a
    fact two groups on that BOTH of a group's first two facts point to, one
    pair linked both ways, and in tenant 0 a hub with 40 in-edges."""
    rng = np.random.default_rng([3939, t])
    keys = [(j, j + 1) for j in range(TENANT_ROWS - 1)]
    for g in rng.choice(TENANT_ROWS // GROUP - 3, 60, replace=False):
        a = int(g) * GROUP
        keys += [(a, a + 2 * GROUP + 3), (a + 1, a + 2 * GROUP + 3)]
        keys += [(a + 2, a + 3 * GROUP), (a + 3 * GROUP, a + 2)]
    if t == HUB[0]:
        keys += [(j, HUB[1]) for j in range(40)]
    return list(dict.fromkeys(keys))


# one edge across tenants, and what a request that serves either end may
# NOT touch: the other tenant's fact
FOREIGN = ((1, 7), (3, 9))


def _lists(t):
    """The program's own order of a fact's neighbour list, stated apart from
    it: the facts it points to, as added, then the facts that point to it,
    as added. (tenant, fact) entries; the foreign edge takes a slot."""
    out = [[] for _ in range(TENANT_ROWS)]
    keys = [((t, a), (t, b)) for a, b in _edges(t)]
    if t in (FOREIGN[0][0], FOREIGN[1][0]):
        keys.append(FOREIGN)
    for (st, a), (tt, b) in keys:
        if st == t:
            out[a].append((tt, b))
    for (st, a), (tt, b) in keys:
        if tt == t:
            out[b].append((st, a))
    return out


def replay(requests):
    """(access boosts, neighbour boosts) per (tenant, fact) of ``requests``,
    (tenant, fact) pairs: the fact's group is served; the first MAX_NBR
    slots of each served fact's list are looked up, and every fact found
    there takes one neighbour boost a request unless it is served itself or
    another tenant's."""
    acc, nbr = {}, {}
    lists = {}
    for t, fact in requests:
        if t not in lists:
            lists[t] = _lists(t)
        g = fact - fact % GROUP
        served = [(t, j) for j in range(g, g + GROUP)]
        for key in served:
            acc[key] = acc.get(key, 0) + 1
        near = {n for _, j in served for n in lists[t][j][:MAX_NBR]}
        for key in near - set(served):
            if key[0] == t:
                nbr[key] = nbr.get(key, 0) + 1
    return acc, nbr


@pytest.fixture(scope="module", params=["one_device", "mesh"])
def system(request, tmp_path_factory):
    mesh = (make_mesh(("data",), (N,), devices=jax.devices()[:N])
            if request.param == "mesh" else None)
    ms = MemorySystem(
        config=MemoryConfig(
            embed_dim=D, dtype="bfloat16", initial_capacity=CAPACITY,
            max_edges=32767, serve_batch_max=BATCH,
            max_buffer_size=4 * N * SHARD, enable_async=False,
            enable_hierarchy=False, auto_consolidate=False,
            load_from_disk=False,
            db_dir=str(tmp_path_factory.mktemp("graph") / "db")),
        verbose=False, mesh=mesh)
    idx = ms.index
    for t in range(TENANTS):
        idx.add([f"t{t}:f{j}" for j in range(TENANT_ROWS)], _rows(t),
                [SAL0] * TENANT_ROWS, [0.0] * TENANT_ROWS,
                ["semantic"] * TENANT_ROWS, ["default"] * TENANT_ROWS, f"t{t}")
    for t in range(TENANTS):
        idx.add_edges([(f"t{t}:f{a}", f"t{t}:f{b}", 0.5)
                       for a, b in _edges(t)], f"t{t}")
    (ta, a), (tb, b) = FOREIGN
    idx.add_edges([(f"t{ta}:f{a}", f"t{tb}:f{b}", 0.5)], f"t{ta}")
    yield ms
    ms.close()


def _columns(ms):
    st = ms.index.state
    return {n: np.asarray(getattr(st, n)).copy()
            for n in ("access_count", "salience", "last_accessed")}


def _row(ms, key):
    return ms.index.id_to_row[f"t{key[0]}:f{key[1]}"]


def _batch(seed, tenants, extra=()):
    """BATCH (tenant, fact) pairs over ``tenants``; ``extra`` come first."""
    rng = np.random.default_rng([seed, 0xBA7C4])
    out = list(extra)
    while len(out) < BATCH:
        out.append((int(rng.choice(tenants)), int(rng.integers(TENANT_ROWS))))
    return out


def _serve(ms, pairs, now):
    rows = {t: _rows(t) for t in {t for t, _ in pairs}}
    reqs = []
    for n, (t, fact) in enumerate(pairs):
        rng = np.random.default_rng([39, t, fact, n])
        q = rows[t][fact] + 0.02 * rng.standard_normal(D).astype(np.float32)
        reqs.append(RetrievalRequest(query=q, tenant=f"t{t}", k=K, boost=True))
    cfg = ms.config
    return ms.index.search_fused_requests(
        reqs, cap_take=cfg.retrieval_cap, max_nbr=cfg.serve_max_nbr,
        super_gate=cfg.super_node_gate, acc_boost=cfg.access_salience_boost,
        nbr_boost=cfg.neighbor_salience_boost, now=now)


def _hold(ms, before, after, pairs, stamp):
    """``after`` is ``before`` with the replay of ``pairs`` laid over it,
    row for row, over the WHOLE arena."""
    acc, nbr = replay(pairs)
    cap = before["access_count"].shape[0]
    want_acc = np.zeros(cap, np.int64)
    want_nbr = np.zeros(cap, np.int64)
    for key, c in acc.items():
        want_acc[_row(ms, key)] = c
    for key, c in nbr.items():
        want_nbr[_row(ms, key)] = c
    np.testing.assert_array_equal(
        after["access_count"], before["access_count"] + want_acc)
    touched = (want_acc + want_nbr) > 0
    sal = np.minimum(1.0, before["salience"] + ACC * want_acc + NBR * want_nbr)
    np.testing.assert_allclose(
        after["salience"], np.where(touched, sal, before["salience"]),
        rtol=0, atol=1e-5)
    np.testing.assert_allclose(
        after["last_accessed"],
        np.where(touched, np.float32(stamp - ms.index.epoch),
                 before["last_accessed"]), rtol=0, atol=1e-2)
    return want_acc, want_nbr


def _shared(t):
    """(a, x): facts a and a + 1 of one group both point to fact x."""
    keys = _edges(t)
    return next((a, x) for a, x in keys
                if x == a + 2 * GROUP + 3 and (a + 1, x) in keys)


def test_a_full_boosting_batch_leaves_the_state_the_replay_says(system):
    ms = system
    (ta, a), (tb, b) = FOREIGN
    hub_t, hub = HUB
    two, x = _shared(4)
    # alone in their tenants: the hub's group; one group of tenant 4 twice
    # (two requests of one dispatch add up). Then the foreign edge's two
    # ends, and the rest drawn over four tenants
    extra = [HUB, (4, two), (4, two + 2), (ta, a), (tb, b)]
    pairs = _batch(1, [1, 3, 7, 8], extra)
    before = _columns(ms)
    stamp = time.time()
    out = _serve(ms, pairs, stamp)
    after = _columns(ms)
    assert len(out) == BATCH
    for (t, fact), res in zip(pairs, out):
        g = fact - fact % GROUP
        assert sorted(res.ids) == sorted(
            f"t{t}:f{j}" for j in range(g, g + GROUP))
        assert res.boosted
    want_acc, want_nbr = _hold(ms, before, after, pairs, stamp)

    def boosts(t, fact):
        r = _row(ms, (t, fact))
        return int(want_acc[r]), int(want_nbr[r])
    # the cases by name. The hub's list is cut at MAX_NBR slots: the facts
    # in them took a boost, the facts past them none ...
    lst = _lists(hub_t)[hub]
    assert len(lst) > MAX_NBR + 5
    assert all(boosts(hub_t, j) == (0, 1) for _, j in lst[:MAX_NBR]
               if not hub <= j < hub + GROUP)
    assert all(boosts(hub_t, j) == (0, 0) for _, j in lst[MAX_NBR:])
    # ... a fact two served facts point to takes ONE boost a request ...
    assert boosts(4, x) == (0, 2)
    # ... a served fact's chain neighbours inside its group take none ...
    assert boosts(4, two + 1) == (2, 0)
    # ... neither end of the foreign edge is the other tenant's neighbour
    # (each was served by its own tenant's request) ...
    assert boosts(ta, a)[0] >= 1 and boosts(tb, b)[0] >= 1
    assert (tb, b) in _lists(ta)[a] and (ta, a) in _lists(tb)[b]
    assert (tb, b) not in replay([(ta, a)])[1]
    assert (ta, a) not in replay([(tb, b)])[1]
    # ... and the tenants nobody asked for are as they were
    for t in set(range(TENANTS)) - {t for t, _ in pairs}:
        rows = [_row(ms, (t, j)) for j in range(TENANT_ROWS)]
        for col in before:
            np.testing.assert_array_equal(after[col][rows], before[col][rows])


def test_a_second_batch_adds_to_the_first_and_salience_stops_at_one(system):
    ms = system
    pairs = _batch(2, [0, 4])
    before = _columns(ms)
    for n in range(3):
        stamp = time.time()
        _serve(ms, pairs, stamp)
    after = _columns(ms)
    _hold(ms, before, after, pairs * 3, stamp)
    assert after["salience"].max() <= 1.0


def test_the_csr_is_built_once_a_topology_and_its_span_opens_only_then(
        system, opened):  # noqa: F811
    ms = system
    tel = ms.telemetry
    _serve(ms, _batch(3, [2]), time.time())         # whatever was dirty, built
    built, looked = (tel.counter_total("index.csr_builds"),
                     tel.counter_total("index.csr_lookups"))
    edges = tel.counter_total("index.csr_edges")
    del opened[:]
    for n in range(3):
        _serve(ms, _batch(4 + n, [2, 5]), time.time())
    assert tel.counter_total("index.csr_builds") == built
    assert tel.counter_total("index.csr_lookups") == looked + 3
    names = [n for _, n, _, _ in opened]
    assert "index.csr" not in names and names.count("index.stage") == 3
    # one more edge: the next dispatch builds, inside its stage span, walks
    # every key, and the one after it finds the build
    added = tel.counter_total("index.edges_added")
    ms.index.add_edges([("t2:f10", "t2:f900", 0.5)], "t2")
    assert tel.counter_total("index.edges_added") == added + 1
    assert [n for _, n, _, _ in opened].count("index.edges") == 1
    del opened[:]
    before = _columns(ms)
    stamp = time.time()
    _serve(ms, [(2, 10)], stamp)
    _serve(ms, [(2, 10)], stamp)
    assert tel.counter_total("index.csr_builds") == built + 1
    assert tel.counter_total("index.csr_edges") == edges + len(
        ms.index.edge_slots)
    spans = [(n, parent) for _, n, parent, _ in opened if n == "index.csr"]
    assert spans == [("index.csr", "index.stage")]
    assert tel.snapshot()["timers"]["index.csr_ms"]["count"] == built + 1
    # and the new edge is served: fact 900 is fact 10's neighbour now
    after = _columns(ms)
    r = _row(ms, (2, 900))
    assert after["salience"][r] == pytest.approx(
        min(1.0, before["salience"][r] + 2 * NBR), abs=1e-5)
    assert after["access_count"][r] == before["access_count"][r]
