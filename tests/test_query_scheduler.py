"""Cross-request query batching (serve.QueryScheduler) + the ingest
side's time/size flush policy (utils.batching.FlushPolicy)."""

import queue
import tempfile
import threading
import time

import numpy as np
import pytest

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core.memory_system import MemorySystem
from lazzaro_tpu.serve import (QueryScheduler, RetrievalRequest,
                               RetrievalResult)
from lazzaro_tpu.utils.batching import FlushPolicy, IngestCoalescer
from tests.test_fused_ingest import ClusteredEmb, QueueLLM


# ------------------------------------------------------------- FlushPolicy
def test_flush_policy_size_and_time():
    p = FlushPolicy(max_items=4, max_wait_s=10.0)
    t0 = 1000.0
    p.note_add(t0)
    assert not p.should_flush(1, t0 + 1)          # small AND young: wait
    assert p.should_flush(4, t0 + 1)              # size threshold
    assert p.should_flush(1, t0 + 10.0)           # age threshold
    p.reset()
    assert not p.should_flush(1, t0 + 10.0)       # reset: the clock restarts


def test_flush_policy_eager_mode():
    p = FlushPolicy(max_items=100, max_wait_s=0.0)
    p.note_add(0.0)
    assert p.should_flush(1, 0.0)                 # wait<=0: always flush
    assert not p.should_flush(0, 0.0)             # ...except when empty


def test_coalescer_time_policy():
    c = IngestCoalescer(max_facts=100, max_wait_s=30.0)
    t0 = 2000.0
    c.add_conversation([{"content": "a"}], now=t0)
    assert not c.should_flush(now=t0 + 1)          # trickle: hold
    assert c.should_flush(now=t0 + 31)             # aged out: ship
    for i in range(100):
        c.add_conversation([{"content": f"b{i}"}], now=t0 + 2)
    assert c.should_flush(now=t0 + 2)              # full: ship now
    c.drain()
    c.add_conversation([{"content": "c"}], now=t0 + 60)
    # drain reset the clock: the new lone fact is young again
    assert not c.should_flush(now=t0 + 61)


# ---------------------------------------------------------- QueryScheduler
def _echo_executor(reqs):
    out = []
    for r in reqs:
        res = RetrievalResult()
        res.ids = [f"{r.tenant}:{int(r.query[0])}"]
        res.scores = [1.0]
        out.append(res)
    return out


def test_scheduler_demuxes_in_order():
    s = QueryScheduler(_echo_executor, max_batch=8)
    try:
        reqs = [RetrievalRequest(query=np.asarray([i], np.float32),
                                 tenant="u") for i in range(20)]
        futures = s.submit_many(reqs)
        got = [f.result(timeout=10).ids[0] for f in futures]
        assert got == [f"u:{i}" for i in range(20)]
        stats = s.stats()
        assert stats["requests_served"] == 20
        # max_batch=8 bounds every flush
        assert stats["max_batch_seen"] <= 8
    finally:
        s.close()


def test_scheduler_coalesces_while_executor_busy():
    """Requests arriving while a flush is in flight pile up and ship as one
    dense batch — the core amortization claim."""
    release = threading.Event()
    batches = []

    def slow_executor(reqs):
        batches.append(len(reqs))
        if len(batches) == 1:
            release.wait(timeout=10)
        return _echo_executor(reqs)

    s = QueryScheduler(slow_executor, max_batch=64)
    try:
        first = s.submit(RetrievalRequest(query=np.zeros(1, np.float32),
                                          tenant="u"))
        time.sleep(0.05)                       # worker is now blocked
        rest = s.submit_many([
            RetrievalRequest(query=np.asarray([i], np.float32), tenant="u")
            for i in range(10)])
        release.set()
        first.result(timeout=10)
        for f in rest:
            f.result(timeout=10)
        assert batches[0] == 1
        assert batches[1] == 10                # coalesced into ONE batch
    finally:
        s.close()


def test_scheduler_propagates_executor_errors():
    def boom(reqs):
        raise RuntimeError("kernel exploded")

    s = QueryScheduler(boom, max_batch=4)
    try:
        f = s.submit(RetrievalRequest(query=np.zeros(1, np.float32),
                                      tenant="u"))
        with pytest.raises(RuntimeError, match="kernel exploded"):
            f.result(timeout=10)
    finally:
        s.close()


def test_scheduler_close_drains_then_rejects():
    s = QueryScheduler(_echo_executor, max_batch=4)
    futures = s.submit_many([
        RetrievalRequest(query=np.asarray([i], np.float32), tenant="u")
        for i in range(3)])
    s.close()                                  # drains pending before exit
    assert [f.result(timeout=1).ids[0] for f in futures] == \
        ["u:0", "u:1", "u:2"]
    assert s.closed
    with pytest.raises(RuntimeError):
        s.submit(RetrievalRequest(query=np.zeros(1, np.float32), tenant="u"))


def test_scheduler_flush_barrier():
    s = QueryScheduler(_echo_executor, max_batch=64)
    try:
        futures = s.submit_many([
            RetrievalRequest(query=np.asarray([i], np.float32), tenant="u")
            for i in range(5)])
        s.flush(timeout=10)
        assert all(f.done() for f in futures)
    finally:
        s.close()


# ----------------------------------------- ingest deferral (MemorySystem)
def _system(tmp, wait_s):
    ms = MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=QueueLLM(6), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        config=MemoryConfig(journal=False, auto_consolidate=False,
                            decay_rate=0.0, ingest_flush_wait_s=wait_s))
    return ms


def test_trickle_ingest_defers_then_coalesces():
    """With ingest_flush_wait_s > 0 a lone conversation's facts wait in the
    coalescer (journal-visible) instead of draining immediately; the next
    consolidation inside the window lands BOTH conversations in one fused
    mega-batch; close() force-drains whatever remains."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _system(tmp, wait_s=3600.0)
        ms.start_conversation()
        ms.add_to_short_term("conv 0", "episodic", 0.7)
        ms.end_conversation()
        assert ms.buffer.size()[0] == 0            # deferred, not ingested
        assert len(ms._ingest_coalescer) == 6
        assert ms._deferred_batches                # still journal-visible
        # aging past the window flushes on the next consolidation
        ms._ingest_coalescer.policy._oldest -= 7200.0
        ms.start_conversation()
        ms.add_to_short_term("conv 1", "episodic", 0.7)
        ms.end_conversation()
        assert ms.buffer.size()[0] == 12           # both conversations
        assert len(ms._ingest_coalescer) == 0
        assert not ms._deferred_batches
        ms.close()


def test_close_force_drains_deferred_facts():
    with tempfile.TemporaryDirectory() as tmp:
        ms = _system(tmp, wait_s=3600.0)
        ms.start_conversation()
        ms.add_to_short_term("conv 0", "episodic", 0.7)
        ms.end_conversation()
        assert ms.buffer.size()[0] == 0
        ms.close()                                 # force-drain
        assert ms.buffer.size()[0] == 6


def test_eager_default_preserves_behavior():
    with tempfile.TemporaryDirectory() as tmp:
        ms = _system(tmp, wait_s=0.0)
        ms.start_conversation()
        ms.add_to_short_term("conv 0", "episodic", 0.7)
        ms.end_conversation()
        assert ms.buffer.size()[0] == 6            # ingested immediately
        ms.close()


# ------------------------------------------------- sharded serve executor
def test_sharded_index_serve_requests():
    import jax
    from jax.sharding import Mesh
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    idx = ShardedMemoryIndex(mesh, dim=8, capacity=64, k=4)
    rng = np.random.default_rng(0)
    emb_a = rng.standard_normal((4, 8)).astype(np.float32)
    emb_b = rng.standard_normal((2, 8)).astype(np.float32)
    idx.add([f"a{i}" for i in range(4)], emb_a, "ta")
    idx.add([f"b{i}" for i in range(2)], emb_b, "tb")

    sched = QueryScheduler(idx.serve_requests, max_batch=8)
    try:
        futures = sched.submit_many([
            RetrievalRequest(query=emb_a[1], tenant="ta", k=2),
            RetrievalRequest(query=emb_b[0], tenant="tb", k=1),
            RetrievalRequest(query=emb_a[3], tenant="ta", k=2),
        ])
        res = [f.result(timeout=30) for f in futures]
        assert res[0].ids[0] == "a1" and len(res[0].ids) == 2
        assert res[1].ids == ["b0"]                # tenant isolated
        assert res[2].ids[0] == "a3"
        assert all(i.startswith("a") for i in res[0].ids + res[2].ids)
    finally:
        sched.close()


# ------------------------------------------- the admission rule (ISSUE 30)
# A full pending batch is admitted over ONE dispatch in flight when the
# executor's owner lets both run together; everything else waits as before.
# The executor here holds every batch on an event of its own, so the tests
# decide the order of events; the only clock is the bounded wait that shows
# something did NOT happen.

B = 4                                   # max_batch in these tests
QUIET_S = 0.25                          # "nothing was admitted" wait


class Gate:
    """Executor that reports each batch as it enters and holds it until
    the test opens it."""

    def __init__(self, fail=()):
        self.lock = threading.Lock()
        self.batches = []               # requests of batch i
        self.log = []                   # ("enter" | "exit", i)
        self.live = self.max_live = 0
        self.entered = queue.Queue()
        self.events = {}
        self.fail = set(fail)

    def _event(self, i):
        with self.lock:
            return self.events.setdefault(i, threading.Event())

    def __call__(self, reqs):
        with self.lock:
            i = len(self.batches)
            self.batches.append(list(reqs))
            self.log.append(("enter", i))
            self.live += 1
            self.max_live = max(self.max_live, self.live)
        self.entered.put(i)
        assert self._event(i).wait(timeout=30)
        with self.lock:
            self.live -= 1
            self.log.append(("exit", i))
        if i in self.fail:
            raise RuntimeError(f"batch {i} exploded")
        return _echo_executor(reqs)

    def wait_entered(self):
        return self.entered.get(timeout=10)

    def quiet(self):
        """True when no batch enters within QUIET_S."""
        try:
            self.entered.get(timeout=QUIET_S)
        except queue.Empty:
            return True
        return False

    def open(self, *batches):
        for i in batches:
            self._event(i).set()


def _req(i, tenant="u", boost=False):
    return RetrievalRequest(query=np.asarray([i], np.float32), tenant=tenant,
                            boost=boost)


def _reads_only(reqs):
    return not any(r.boost for r in reqs)


def _overlap_sched(gate, overlap_check=_reads_only, **kw):
    from lazzaro_tpu.utils.telemetry import Telemetry
    return QueryScheduler(gate, max_batch=B, telemetry=Telemetry(),
                          overlap_check=overlap_check, **kw)


def _values(futures):
    return [f.result(timeout=10).ids[0] for f in futures]


def _overlapped(s):
    return s.telemetry.counter_total("serve.overlapped_batches")


def _eventually(cond, timeout=10.0):
    """Bounded wait for something another thread is about to do."""
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.002)
    return cond()


def test_full_pending_batch_is_admitted_over_the_dispatch_in_flight():
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        first = s.submit(_req(0))
        assert gate.wait_entered() == 0             # in flight, held
        rest = s.submit_many([_req(i) for i in range(1, 1 + B)])
        assert gate.wait_entered() == 1             # while batch 0 is held
        assert s.load() == 2 and gate.live == 2
        gate.open(1, 0)                             # the second returns first
        assert _values(rest) == [f"u:{i}" for i in range(1, 1 + B)]
        assert _values([first]) == ["u:0"]
        s.flush(timeout=10)
        assert _overlapped(s) == 1
        assert s.telemetry.counter_total("serve.batches") == 2
        assert s.stats()["requests_served"] == 1 + B
    finally:
        gate.open(0, 1)
        s.close()


def test_batch_one_short_of_full_waits_for_the_dispatch_to_return():
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        first = s.submit(_req(0))
        assert gate.wait_entered() == 0
        rest = s.submit_many([_req(i) for i in range(1, B)])     # B - 1
        assert gate.quiet() and s.load() == B
        gate.open(0)
        assert gate.wait_entered() == 1
        gate.open(1)
        assert _values([first] + rest) == [f"u:{i}" for i in range(B)]
        assert gate.log == [("enter", 0), ("exit", 0), ("enter", 1),
                            ("exit", 1)]
        s.flush(timeout=10)
        assert _overlapped(s) == 0                  # no such entry at all
        assert "serve.overlapped_batches" not in s.telemetry.counters
    finally:
        gate.open(0, 1)
        s.close()


def test_never_three_dispatches_in_flight():
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        futs = [s.submit(_req(0))]
        assert gate.wait_entered() == 0
        futs += s.submit_many([_req(i) for i in range(1, 1 + B)])
        assert gate.wait_entered() == 1
        futs += s.submit_many([_req(i) for i in range(1 + B, 1 + 2 * B)])
        assert gate.quiet()                         # full, and still waits
        assert s.load() == 2 + B
        gate.open(0)                                # one returns: the full
        assert gate.wait_entered() == 2             # window goes over batch 1
        gate.open(1, 2)
        assert _values(futs) == [f"u:{i}" for i in range(1 + 2 * B)]
        s.flush(timeout=10)
        assert gate.max_live == 2 and _overlapped(s) == 2
    finally:
        gate.open(0, 1, 2)
        s.close()


@pytest.mark.parametrize("case", ["boost_in_flight", "boost_pending",
                                  "boost_pending_last", "owner_says_no",
                                  "no_predicate", "predicate_raises"])
def test_serial_order_is_kept_where_overlap_is_not_vouched_for(case):
    def raises(reqs):
        raise ValueError("broken predicate")

    check = {"owner_says_no": lambda reqs: False, "no_predicate": None,
             "predicate_raises": raises}.get(case, _reads_only)
    gate = Gate()
    s = _overlap_sched(gate, overlap_check=check)
    try:
        first = s.submit(_req(0, boost=(case == "boost_in_flight")))
        assert gate.wait_entered() == 0
        boosted = {"boost_pending": 1, "boost_pending_last": B}.get(case)
        rest = s.submit_many([_req(i, boost=(i == boosted))
                              for i in range(1, 2 + B)])   # B + 1 pending
        assert gate.quiet()                         # a barrier on both sides
        gate.open(0)
        assert gate.wait_entered() == 1             # first B, oldest first
        assert [int(r.query[0]) for r in gate.batches[1]] == \
            list(range(1, 1 + B))
        assert gate.quiet()                         # the last one: not full
        gate.open(1)
        assert gate.wait_entered() == 2
        gate.open(2)
        assert _values([first] + rest) == [f"u:{i}" for i in range(2 + B)]
        assert gate.max_live == 1
        assert gate.log == [(w, i) for i in range(3)
                            for w in ("enter", "exit")]
        s.flush(timeout=10)
        assert _overlapped(s) == 0
        assert len(s._workers) == (1 if case == "no_predicate" else 2)
    finally:
        gate.open(0, 1, 2)
        s.close()


def test_tenant_cap_and_oldest_first_hold_across_overlapped_batches():
    gate = Gate()
    s = _overlap_sched(gate, tenant_max_inflight=2)
    try:
        futs = [s.submit(_req(0, "z"))]
        assert gate.wait_entered() == 0
        # capped selection of a full queue is only two: nothing overlaps
        futs += s.submit_many([_req(i, "a") for i in range(1, 5)])
        assert gate.quiet() and s.load() == 5
        futs += s.submit_many([_req(5, "b"), _req(6, "b"), _req(7, "c"),
                               _req(8, "c")])
        assert gate.wait_entered() == 1             # a, a, b, b: full
        gate.open(0)
        assert gate.wait_entered() == 2             # a, a, c, c over batch 1
        gate.open(1, 2)
        assert _values(futs) == (["z:0"] + [f"a:{i}" for i in range(1, 5)]
                                 + ["b:5", "b:6", "c:7", "c:8"])
        picked = [[(r.tenant, int(r.query[0])) for r in b]
                  for b in gate.batches]
        assert picked == [[("z", 0)],
                          [("a", 1), ("a", 2), ("b", 5), ("b", 6)],
                          [("a", 3), ("a", 4), ("c", 7), ("c", 8)]]
        s.flush(timeout=10)
        assert _overlapped(s) == 2
        assert s.stats()["requests_deferred"] >= 2
    finally:
        gate.open(0, 1, 2)
        s.close()


def test_lone_request_on_an_idle_overlapping_scheduler_ships_at_once():
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        for i in range(3):                          # whichever worker is idle
            fut = s.submit(_req(i))
            assert gate.wait_entered() == i
            gate.open(i)
            assert _values([fut]) == [f"u:{i}"]
        s.flush(timeout=10)
        assert s.telemetry.counter_total("serve.lone_batches") == 3
        assert _overlapped(s) == 0 and gate.max_live == 1
    finally:
        s.close()


# --------------------------------------- the failure model, per batch, under overlap
def _two_in_flight(gate, s):
    first = s.submit(_req(0))
    assert gate.wait_entered() == 0
    rest = s.submit_many([_req(i) for i in range(1, 1 + B)])
    assert gate.wait_entered() == 1
    return first, rest


def test_executor_error_in_the_overlapped_batch_fails_its_futures_alone():
    gate = Gate(fail={1})
    s = _overlap_sched(gate, breaker_threshold=5)
    try:
        first, rest = _two_in_flight(gate, s)
        gate.open(1)
        for f in rest:
            with pytest.raises(RuntimeError, match="batch 1 exploded"):
                f.result(timeout=10)
        assert not first.done()
        gate.open(0)
        assert _values([first]) == ["u:0"]
        s.flush(timeout=10)
        assert s.telemetry.counter_total("serve.batches") == 1
        assert s.stats()["requests_served"] == 1
        # the breaker saw every batch once: one failure, then one success
        assert s.breaker.stats()["consecutive_failures"] == 0
    finally:
        gate.open(0, 1)
        s.close()


@pytest.mark.parametrize("late", [0, 1])
def test_each_batch_has_its_own_watchdog(late):
    from lazzaro_tpu.reliability.errors import DispatchTimeout
    gate = Gate()
    s = _overlap_sched(gate, dispatch_timeout_s=1.0)
    try:
        first, rest = _two_in_flight(gate, s)
        groups = [[first], rest]
        gate.open(1 - late)                         # the other one returns
        assert _values(groups[1 - late]) == \
            [["u:0"], [f"u:{i}" for i in range(1, 1 + B)]][1 - late]
        for f in groups[late]:                      # its own deadline passes
            with pytest.raises(DispatchTimeout):
                f.result(timeout=10)
        gate.open(late)                             # the late result
        s.flush(timeout=10)
        for f in groups[late]:                      # ... is discarded
            with pytest.raises(DispatchTimeout):
                f.result(timeout=1)
        assert s.stats()["watchdog_timeouts"] == 1
        assert s.telemetry.counter_total("serve.batches") == 1
        assert s.stats()["requests_served"] == len(groups[1 - late])
    finally:
        gate.open(0, 1)
        s.close()


def test_worker_death_under_overlap_restarts_that_thread_alone():
    from lazzaro_tpu.reliability.errors import WorkerCrashed
    from lazzaro_tpu.reliability.faults import INJECTOR
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        first = s.submit(_req(0))
        assert gate.wait_entered() == 0
        INJECTOR.arm("scheduler.worker", times=1)   # the next admission dies
        rest = s.submit_many([_req(i) for i in range(1, 1 + B)])
        for f in rest:
            with pytest.raises(WorkerCrashed):
                f.result(timeout=10)
        assert gate.live == 1 and len(gate.batches) == 1   # never dispatched
        gate.open(0)
        assert _values([first]) == ["u:0"]                 # the other: served
        again = s.submit_many([_req(i) for i in range(10, 10 + B)])
        assert gate.wait_entered() == 1
        gate.open(1)
        assert _values(again) == [f"u:{i}" for i in range(10, 10 + B)]
        # the dead thread counts its restart after its futures failed
        assert _eventually(lambda: s.stats()["worker_restarts"] == 1)
        assert all(w.is_alive() for w in s._workers)
        # and the restarted pair still overlaps
        held = s.submit(_req(20))
        assert gate.wait_entered() == 2
        over = s.submit_many([_req(i) for i in range(21, 21 + B)])
        assert gate.wait_entered() == 3
        gate.open(2, 3)
        assert _values([held] + over) == [f"u:{i}" for i in range(20, 21 + B)]
    finally:
        INJECTOR.disarm("scheduler.worker")
        gate.open(*range(4))
        s.close()


def test_close_with_two_in_flight_resolves_every_future():
    gate = Gate()
    s = _overlap_sched(gate)
    first, rest = _two_in_flight(gate, s)
    tail = s.submit_many([_req(i) for i in range(100, 103)])      # pending
    closer = threading.Thread(target=s.close)
    closer.start()
    gate.open(0, 1)
    assert gate.wait_entered() == 2                 # close drains the queue
    gate.open(2)
    closer.join(timeout=30)
    assert not closer.is_alive() and s.closed
    assert _values([first] + rest + tail) == (
        [f"u:{i}" for i in range(1 + B)] + ["u:100", "u:101", "u:102"])
    assert not any(w.is_alive() for w in s._workers)


def test_flush_returns_only_when_nothing_is_in_flight():
    gate = Gate()
    s = _overlap_sched(gate)
    try:
        first, rest = _two_in_flight(gate, s)
        flushed = threading.Event()

        def flusher():
            s.flush(timeout=30)
            flushed.set()
        t = threading.Thread(target=flusher)
        t.start()
        gate.open(1)
        assert _values(rest) == [f"u:{i}" for i in range(1, 1 + B)]
        assert not flushed.wait(QUIET_S)            # batch 0 is still out
        assert s.load() == 1
        gate.open(0)
        assert flushed.wait(10)
        t.join(timeout=10)
        assert first.done() and s.load() == 0
    finally:
        gate.open(0, 1)
        s.close()
