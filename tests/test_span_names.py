"""The program's span taxonomy (PR 25; tier-1, CPU, debug geometry): one
conversation through the API and one dispatch through the scheduler open
exactly the spans PERF.md section 3 lists, nested as listed. A recorder
stands where ``Span`` is made, so the test reads names and parents from the
helper's own thread-local stack, not from a profile."""

import threading
import time

import numpy as np
import pytest

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.serve import RetrievalRequest
from lazzaro_tpu.utils import telemetry as T
from tests.test_fused_ingest import ClusteredEmb, D, QueueLLM

# span -> the spans it may open directly
CONVERSATION = {
    None: {"api.switch_user", "api.start_conversation", "journal.turn",
           "api.end_conversation"},
    "api.switch_user": {"store.save", "store.load", "journal.setup"},
    "api.start_conversation": {"journal.sync"},
    "api.end_conversation": {
        "write.extract", "journal.append", "write.embed", "write.prepare",
        "write.apply", "store.save", "journal.sync", "journal.commit",
        "write.decay"},
    "write.prepare": {"ingest.dedup_fused"},
    "write.apply": {"store.add"},
    "store.save": {"store.io"}, "store.load": {"store.io"},
    "store.add": {"store.io"},
    "journal.setup": {"journal.io"}, "journal.sync": {"journal.io"},
    "journal.turn": {"journal.io"}, "journal.append": {"journal.io"},
    "journal.commit": {"journal.io"},
}
DISPATCH = {
    None: {"sched.idle", "sched.hold", "sched.account", "index.pack",
           "index.stage", "serve.exact", "index.decode", "sched.demux"},
    "serve.exact": {"dispatch.launch", "dispatch.readback"},
}
# opened only by the dispatch that finds the edge topology dirty (ISSUE 39):
# the CSR's build, inside ``index.stage``
BUILDS = {"index.csr"}
# a worker's two waits: which of them a dispatch opens is the callers' doing
WAITS = {"sched.idle", "sched.hold"}
# the names accepted metrics select by prefix (index.host_p50_ms.lat,
# kernel.ingest_dev_ms): a new span under them would redefine the metric
ACCEPTED = {"serve.exact", "ingest.dedup_fused"}
# a request's stages (ISSUE 41): unlabelled sums over every served request,
# bumped once a served batch — the first three by any dispatch, the last
# four only for callers that waited in ``result()`` (and, the last two,
# came back inside their bound)
STAGES = {"serve.account_us", "serve.exec_us", "serve.demux_wait_us"}
WAY_BACK = {"serve.wake_us", "serve.wakes", "serve.return_us",
            "serve.returns"}
SUMMED = ("journal.turn", "journal.sync", "journal.append", "journal.commit",
          "journal.setup", "store.save", "store.load", "store.add")


@pytest.fixture()
def opened(monkeypatch):
    log = []

    class Recorded(T.Span):
        def __enter__(self):
            super().__enter__()
            chain = [s.name for s in T._open_spans()]
            log.append((threading.current_thread().name, self.name,
                        self.parent, chain[:-1]))
            return self

    monkeypatch.setattr(T, "Span", Recorded)
    return log


@pytest.fixture()
def system(tmp_path):
    ms = MemorySystem(
        enable_async=False, db_dir=str(tmp_path / "db"), verbose=False,
        load_from_disk=False, llm_provider=QueueLLM(20),
        embedding_provider=ClusteredEmb(), auto_prune=False,
        max_buffer_size=10_000,
        config=MemoryConfig(auto_consolidate=False, enable_hierarchy=False))
    yield ms
    ms.close()


def _converse(ms, user, n):
    ms.switch_user(user)
    ms.start_conversation()
    ms.add_to_short_term(f"conv {n}", "episodic", 0.7)
    ms.end_conversation()


def _tree(log):
    tree = {}
    for _, name, parent, _ in log:
        tree.setdefault(parent, set()).add(name)
    return tree


def test_one_conversation_opens_exactly_the_listed_spans(system, opened):
    _converse(system, "alice", 0)
    del opened[:]
    _converse(system, "bob", 1)
    assert {t for t, *_ in opened} == {"MainThread"}     # synchronous writer
    assert _tree(opened) == CONVERSATION
    names = [n for _, n, _, _ in opened]
    assert names.count("api.end_conversation") == 1
    # a conversation passes the persist three times today (after the
    # consolidation, at its end, and at the next switch_user): shown, not
    # changed, here
    assert names.count("store.save") == 3
    assert names.count("store.load") == names.count("store.add") == 1
    # the per-conversation metrics SUM these: none may lie inside another
    for _, name, _, chain in opened:
        if name in SUMMED:
            assert not set(chain) & set(SUMMED), (name, chain)
    # and every file operation lies inside one of them
    for _, name, parent, _ in opened:
        if name in ("store.io", "journal.io"):
            assert parent in SUMMED


def test_file_operations_per_conversation_repeat(system, opened):
    _converse(system, "alice", 0)
    counts = []
    for n, user in enumerate(("bob", "carol", "dave"), 1):
        del opened[:]
        _converse(system, user, n)
        counts.append(sum(name in ("store.io", "journal.io")
                          for _, name, _, _ in opened))
    assert len(set(counts)) == 1 and counts[0] > 0


def test_one_dispatch_opens_at_most_twelve_spans(system, opened):
    _converse(system, "alice", 0)
    sched = system._ensure_scheduler()
    req = RetrievalRequest(query=np.ones(D, np.float32), tenant="alice", k=5)
    assert sched.submit(req).result(timeout=60).ids     # warm: compiles
    time.sleep(0.05)            # the worker is back in its wait
    del opened[:]
    assert sched.submit(req).result(timeout=60).ids
    deadline = time.time() + 10
    while (not any(n == "sched.idle" for _, n, _, _ in opened)
           and time.time() < deadline):
        time.sleep(0.005)
    mine = [e for e in opened if e[0] != "MainThread"]
    assert len({t for t, *_ in mine}) == 1              # the one worker
    # one sequential caller: released, back, nobody else expected — no hold
    assert _tree(mine) == {**DISPATCH, None: DISPATCH[None] - {"sched.hold"}}
    assert len(mine) == 9 <= 12
    order = [n for _, n, _, _ in mine]
    assert order == ["sched.account", "index.pack", "index.stage", "serve.exact",
                     "dispatch.launch", "dispatch.readback", "index.decode",
                     "sched.demux", "sched.idle"]
    assert system.telemetry.counter_total("serve.lone_batches") == 2
    assert system.telemetry.counter_total("serve.queue_wait_us") > 0
    # a request's stages: both dispatches stamped; the first answer's
    # wake-up was folded by the second submission and rode its counters,
    # the second's waits for the next batch (or ``close()``)
    counters = system.telemetry.counters
    assert STAGES <= set(counters) and counters["serve.wakes"] == 1
    # nothing overlapped: the counter has no entry at all (a bump of 0)
    assert "serve.overlapped_batches" not in system.telemetry.counters


def test_overlapped_dispatch_opens_the_same_spans_on_the_other_worker(
        system, opened):
    """ISSUE 30: a full batch admitted over the dispatch in flight runs the
    same path under the same names on the second worker; the counter says
    it happened. The first dispatch is held inside the executor, so the
    order of events is the test's."""
    from lazzaro_tpu.serve import QueryScheduler
    _converse(system, "alice", 0)
    hold, entered = threading.Event(), threading.Event()

    def executor(reqs):
        if len(reqs) == 1:
            entered.set()
            assert hold.wait(timeout=60)
        return system._serve_requests(reqs)

    req = RetrievalRequest(query=np.ones(D, np.float32), tenant="alice", k=5)
    system._serve_requests([req] * 4)                   # warm: compiles
    sched = QueryScheduler(executor, max_batch=4, telemetry=system.telemetry,
                           overlap_check=system._reads_may_overlap,
                           name="lz-overlap")
    try:
        del opened[:]
        first = sched.submit(req)
        assert entered.wait(timeout=60)
        rest = sched.submit_many([req] * 4)
        assert all(f.result(timeout=60).ids for f in rest)   # over the first
        hold.set()
        assert first.result(timeout=60).ids
        sched.flush(timeout=60)
    finally:
        hold.set()
        sched.close()
    tel = system.telemetry
    assert tel.counter_total("serve.overlapped_batches") == 1
    assert tel.counter_total("serve.batches") == 2
    by_thread = {}
    for t, name, parent, _ in opened:
        if t != "MainThread" and name != "sched.idle":
            by_thread.setdefault(t, []).append((name, parent))
    assert set(by_thread) == {"lz-overlap", "lz-overlap-2"}
    path = ["sched.account", "index.pack", "index.stage", "serve.exact",
            "dispatch.launch", "dispatch.readback", "index.decode",
            "sched.demux"]
    for t, spans in by_thread.items():
        assert [n for n, _ in spans] == path, t
        tree = {}
        for n, parent in spans:
            tree.setdefault(parent, set()).add(n)
        assert tree == {None: DISPATCH[None] - WAITS,
                        "serve.exact": DISPATCH["serve.exact"]}, t


def test_a_hold_is_a_top_level_span_of_the_worker_beside_its_idle_wait(
        system, opened):
    """ISSUE 32: two callers that wait, in step. Once both have been seen to
    come back the worker holds its (empty) window open from the demux on:
    ``sched.hold`` opens before ``sched.idle``, at the top level and never
    under the dispatch, and the path of a dispatch keeps its names.
    The executor takes 0.4 s a batch, so the callers have 0.2 s to return."""
    from lazzaro_tpu.serve import QueryScheduler
    _converse(system, "alice", 0)

    def executor(reqs):
        time.sleep(0.4)
        return system._serve_requests(reqs)

    req = RetrievalRequest(query=np.ones(D, np.float32), tenant="alice", k=5)
    system._serve_requests([req] * 2)                   # warm: compiles
    sched = QueryScheduler(executor, max_batch=4, telemetry=system.telemetry,
                           name="lz-hold")

    def caller():
        for _ in range(8):
            assert sched.submit(req).result(timeout=60).ids
    threads = [threading.Thread(target=caller) for _ in range(2)]
    try:
        del opened[:]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        sched.flush(timeout=60)
    finally:
        sched.close()
    tel = system.telemetry
    assert tel.counter_total("serve.held_batches") >= 2
    assert tel.counter_total("serve.hold_us") > 0
    mine = [e for e in opened if e[0] == "lz-hold"]
    assert _tree(mine) == DISPATCH
    for _, name, parent, chain in mine:
        if name in WAITS:
            assert parent is None and chain == []
    # a held batch runs the pinned path: the hold comes first, then the
    # idle wait, which admits at once, and nothing else moved
    order = [n for _, n, _, _ in mine]
    at = order.index("sched.hold")
    assert order[at - 8:at + 3] == [
        "sched.account", "index.pack", "index.stage", "serve.exact",
        "dispatch.launch", "dispatch.readback", "index.decode",
        "sched.demux", "sched.hold", "sched.idle", "sched.account"]
    # and no hold opens unless the worker waits in it
    assert (tel.snapshot()["timers"]["sched.hold_ms"]["count"]
            == order.count("sched.hold"))
    # callers that wait and come back inside their bound: all seven of a
    # request's stage counters, unlabelled, and every answer one wake-up
    assert STAGES | WAY_BACK <= set(tel.counters)
    assert tel.counters["serve.wakes"] == tel.counters["serve.requests"] == 16
    assert 1 <= tel.counters["serve.returns"] <= 14


def test_the_pod_index_builds_its_csr_under_the_one_chip_index_s_span(opened):
    """``ShardedMemoryIndex`` builds its per-shard CSR under ``index.csr``,
    inside the ``index.stage`` of the dispatch that found the topology
    dirty, with the one-chip index's three counters; a dispatch on a clean
    topology opens none and counts a look-up."""
    import jax
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    tel = T.Telemetry()
    mesh = make_mesh(("data",), (4,), devices=jax.devices()[:4])
    si = ShardedMemoryIndex(mesh, dim=D, capacity=255, telemetry=tel)
    emb = np.random.default_rng(0).standard_normal((40, D)).astype(np.float32)
    si.add([f"n{i}" for i in range(40)], emb, "u0")
    si.add_edges([(f"n{i}", f"n{i + 1}", 0.5) for i in range(39)])
    reqs = [RetrievalRequest(query=emb[i], tenant="u0", k=5, boost=True)
            for i in range(3)]
    def serve():
        del opened[:]
        assert all(r.ids for r in si.serve_requests(reqs))
        return [(n, parent) for _, n, parent, _ in opened if n == "index.csr"]

    def counted():
        return tuple(tel.counter_total("index.csr_" + n)
                     for n in ("builds", "edges", "lookups"))

    assert serve() == [("index.csr", "index.stage")]    # the first one builds
    assert counted() == (1, 39, 1)
    assert serve() == [] and counted() == (1, 39, 2)    # a clean topology
    si.add_edges([("n0", "n7", 0.3)])                   # dirties it
    assert serve() == [("index.csr", "index.stage")]
    assert counted() == (2, 79, 3)
    assert tel.snapshot()["timers"]["index.csr_ms"]["count"] == 2


def test_no_new_name_falls_under_an_accepted_metrics_prefix():
    names = {n for tree in (CONVERSATION, DISPATCH)
             for kids in tree.values() for n in kids}
    taken = [n for n in names - ACCEPTED
             if n.startswith(("serve.", "ingest."))]
    assert not taken


# ----------------------------------------------- the int8 family (ISSUE 36)

@pytest.fixture()
def int8_system(tmp_path):
    ms = MemorySystem(
        enable_async=False, db_dir=str(tmp_path / "db"), verbose=False,
        load_from_disk=False, llm_provider=QueueLLM(20),
        embedding_provider=ClusteredEmb(), auto_prune=False,
        max_buffer_size=10_000,
        config=MemoryConfig(auto_consolidate=False, enable_hierarchy=False,
                            int8_serving=True))
    yield ms
    ms.close()


def test_int8_dispatch_opens_the_same_path_and_the_shadow_build_once(
        int8_system, opened):
    """An int8 dispatch runs the pinned path under ``serve.quant``; the
    shadow's (re)build is the span ``index.shadow`` inside the launch that
    found it dirty, with its timer and its two counters, and a dispatch on
    a clean index opens none."""
    system = int8_system
    _converse(system, "alice", 0)
    sched = system._ensure_scheduler()
    req = RetrievalRequest(query=np.ones(D, np.float32), tenant="alice", k=5)
    path = ["sched.account", "index.pack", "index.stage", "index.csr",
            "serve.quant", "dispatch.launch", "index.shadow",
            "dispatch.readback", "index.decode", "sched.demux"]
    for build in (True, False):
        time.sleep(0.05)        # the worker is back in its wait
        del opened[:]
        assert sched.submit(req).result(timeout=120).ids
        deadline = time.time() + 10
        while (not any(n == "sched.idle" for _, n, _, _ in opened)
               and time.time() < deadline):
            time.sleep(0.005)
        mine = [e for e in opened if e[0] != "MainThread"]
        want = [n for n in path
                if build or n not in BUILDS | {"index.shadow"}]
        assert [n for _, n, _, _ in mine] == want + ["sched.idle"]
        tree = _tree(mine)
        assert tree.get("index.stage", set()) == (BUILDS if build else set())
        assert tree["serve.quant"] == {"dispatch.launch", "dispatch.readback"}
        assert tree.get("dispatch.launch", set()) == (
            {"index.shadow"} if build else set())
    tel = system.telemetry
    assert tel.counter_total("index.shadow_builds") == 1
    assert tel.counter_total("index.shadow_dispatches") == 1
    assert tel.snapshot()["timers"]["index.shadow_ms"]["count"] == 1
    assert tel.counters['serve.dispatches{mode="quant"}'] == 2
    # the int8 core's own label beside the exact core's
    select = {k: v for k, v in tel.counters.items()
              if k.startswith("serve.select")}
    assert set(select) == {'serve.select{core="whole_pool_q8"}'}, select
    assert sum(select.values()) == 2
    # and the accepted metrics' prefixes still select what they selected
    assert not [n for n in BUILDS | {"index.shadow", "index.edges"}
                if n.startswith(("serve.", "ingest."))]


# ------------------------------------- the way in is one transfer (ISSUE 37)

@pytest.mark.parametrize("fam,boost", [
    ("exact", False), ("exact", True), ("quant", False), ("quant", True),
    ("mesh_exact", False), ("mesh_exact", True)])
def test_a_dispatch_stages_its_requests_in_one_transfer(monkeypatch, fam,
                                                        boost):
    """``serve.h2d_puts{mode}`` == ``serve.dispatches{mode}`` after a read,
    a boosting, an int8 and a mesh batch — and the counter tells the truth:
    the warm dispatch hands its program ONE host operand, the carrier, and
    between pack and readback puts nothing on the device beside it (no
    ``jax.device_put`` / ``jnp.asarray`` of host data)."""
    import jax
    import jax.numpy as jnp
    from lazzaro_tpu.core import state as S
    from lazzaro_tpu.core.index import _SERVE_KERNELS
    from tests.test_request_carrier import routed_index, served

    host = (np.ndarray, np.generic, int, float, bool)
    tel = T.Telemetry()
    idx, emb = routed_index(fam, telemetry=tel)
    served(idx, emb, boost)                 # warm: compiles, builds the CSR
    puts, handed = [], []
    for mod, name in ((jax, "device_put"), (jnp, "asarray")):
        real = getattr(mod, name)

        def counted(x, *a, __real=real, **kw):
            if isinstance(x, host):
                puts.append(np.shape(x))
            return __real(x, *a, **kw)

        monkeypatch.setattr(mod, name, counted)

    def spy(fn):            # the positional operands are the dynamic ones
        def call(*a, **kw):
            handed.append([np.shape(x) for x in jax.tree_util.tree_leaves(a)
                           if isinstance(x, host)])
            return fn(*a, **kw)
        return call

    if idx.mesh is None:
        for name in _SERVE_KERNELS[fam]:
            monkeypatch.setattr(S, name, spy(getattr(S, name)))
    else:
        real_kernels = idx._fused_sharded_kernels
        monkeypatch.setattr(
            idx, "_fused_sharded_kernels",
            lambda *a, **kw: S.FusedShardedKernels(
                *map(spy, real_kernels(*a, **kw))))
    out = served(idx, emb, boost)
    monkeypatch.undo()
    assert any(r["ids"] for r in out["results"])
    assert handed == [[(8, 32 + 11)]], handed   # seven requests: bucket 8
    assert puts == [], puts
    mode = fam.replace("mesh_", "sharded_")
    assert (tel.counters[f'serve.h2d_puts{{mode="{mode}"}}']
            == tel.counters[f'serve.dispatches{{mode="{mode}"}}'] == 2)
    assert (tel.counter_total("serve.h2d_puts")
            == tel.counter_total("serve.dispatches") == 2)


def test_the_pod_index_stages_one_transfer_too():
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh
    import jax

    tel = T.Telemetry()
    mesh = make_mesh(("data",), (4,), devices=jax.devices()[:4])
    si = ShardedMemoryIndex(mesh, dim=D, capacity=255, telemetry=tel)
    emb = np.random.default_rng(0).standard_normal((40, D)).astype(np.float32)
    si.add([f"n{i}" for i in range(40)], emb, "u0")
    for boost in (False, True):
        res = si.serve_requests([RetrievalRequest(
            query=emb[i], tenant="u0", k=5, boost=boost) for i in range(3)])
        assert all(r.ids for r in res)
    assert (tel.counters['serve.h2d_puts{mode="pod"}']
            == tel.counters['serve.dispatches{mode="pod"}'] == 2)
