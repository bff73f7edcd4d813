"""Cross-request query batching (serve.QueryScheduler) + the ingest
side's time/size flush policy (utils.batching.FlushPolicy)."""

import queue
import statistics
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


# ------------------------------------------------- the hold (ISSUE 32)
# A free worker holds a window that is not full while callers its last
# demux released are still expected back. The callers here are threads in a
# closed loop (each blocks in ``result()`` and sends its next request when
# the last returned); the executor takes PACE_S a batch, so the bound of a
# hold is HOLD_FRACTION * PACE_S and a caller has that long to come back.
# No assertion rests on a thread being quicker than 225 ms, and where the
# suite's other workers stall a caller for longer than the bound — which
# costs one short batch, after which the loop has to heal again — the
# tests ask for a RUN of batches (``Paced.settled``), not for fixed places.

from lazzaro_tpu.serve.scheduler import HOLD_FRACTION  # noqa: E402

PACE_S = 0.6
BOUND_S = HOLD_FRACTION * PACE_S


class Paced:
    """Executor that takes ``pace_s`` a batch and notes what entered when."""

    def __init__(self, pace_s=PACE_S):
        self.pace_s = pace_s
        self.lock = threading.Lock()
        self.batches = []               # [(tenant, value)] of batch i
        self.at = []                    # monotonic time batch i entered

    def __call__(self, reqs):
        with self.lock:
            self.batches.append([(r.tenant, int(r.query[0])) for r in reqs])
            self.at.append(time.monotonic())
        self.work()
        return _echo_executor(reqs)

    def work(self):
        time.sleep(self.pace_s)

    @property
    def sizes(self):
        with self.lock:
            return [len(b) for b in self.batches]

    def wait_batches(self, n, timeout=30.0):
        assert _eventually(lambda: len(self.sizes) >= n, timeout), self.sizes

    def settled(self, n, run=4, timeout=30.0):
        """Waits for ``run`` consecutive batches of ``n`` and one more
        batch entered after them (so the run's last gap is known); the
        index of the run's first."""
        def first():
            sizes = self.sizes[:-1]
            for i in range(len(sizes) - run + 1):
                if sizes[i:i + run] == [n] * run:
                    return i
            return None
        assert _eventually(lambda: first() is not None, timeout), self.sizes
        return first()

    def gaps(self, first, last=None):
        """Entry of batch i less entry of batch i - 1, for ``first`` <= i
        < ``last``."""
        with self.lock:
            at = self.at[first - 1:last]
        return [b - a for a, b in zip(at, at[1:])]


class Callers:
    """``n`` closed-loop callers. ``after(c, k)`` runs in caller ``c`` when
    its k-th answer is back and returns False to stop it."""

    def __init__(self, s, n, after=lambda c, k: True, tenants=None):
        self.s, self.stop = s, threading.Event()
        self.after = after
        self.tenants = tenants or ["u"] * n
        self.errors = [[] for _ in range(n)]
        self.answers = [0] * n
        self.idents = [0] * n
        self.threads = [threading.Thread(target=self._run, args=(c,),
                                         daemon=True) for c in range(n)]

    def _run(self, c):
        self.idents[c] = threading.get_ident()
        k = 0
        while not self.stop.is_set():
            try:
                fut = self.s.submit(_req(c, self.tenants[c]))
                assert fut.result(timeout=30).ids == [f"{self.tenants[c]}:{c}"]
                self.answers[c] += 1
            except RuntimeError as e:       # closed under us, or typed
                self.errors[c].append(e)
                if self.s.closed:
                    return
            k += 1
            if not self.after(c, k):
                return

    def start(self, *which):
        for c in which or range(len(self.threads)):
            self.threads[c].start()

    def finish(self):
        self.stop.set()
        for t in self.threads:
            if t.ident is not None:
                t.join(timeout=30)
                assert not t.is_alive()


def _hold_sched(exe, max_batch=B, **kw):
    from lazzaro_tpu.utils.telemetry import Telemetry
    return QueryScheduler(exe, max_batch=max_batch, telemetry=Telemetry(), **kw)


def _held(s):
    return s.telemetry.counter_total("serve.held_batches")


@pytest.mark.parametrize("n,start", [(B, "together"), (B - 1, "together"),
                                     (B, "split")],
                         ids=["full_window", "everyone_back", "from_a_split"])
def test_callers_that_wait_are_served_together_and_a_split_heals(n, start):
    """N callers that wait are served N to a dispatch once each has been
    seen to come back, whatever split the loop starts in — and (1, N - 1),
    a stable cycle of the rule that admits whatever is pending, is gone
    by the fourth dispatch (a few later if the machine stalls a caller
    for longer than the bound meanwhile)."""
    exe = Paced()
    s = _hold_sched(exe, overlap_check=_reads_only)
    callers = Callers(s, n)
    try:
        if start == "split":
            callers.start(0)
            exe.wait_batches(1)                     # caller 0 rides alone
            callers.start(*range(1, n))
        else:
            callers.start()
        at = exe.settled(n, run=5)
        sizes = exe.sizes
        if start == "split":
            assert sizes[:3] == [1, n - 1, 1]
        assert at <= 5, sizes           # 3 where the machine stalls nobody
        # every one of those was admitted after a hold, and the holds are
        # the callers' way back, not the bound
        assert _held(s) >= 4
        assert (statistics.median(exe.gaps(at + 1, at + 6))
                < PACE_S + 0.75 * BOUND_S)
        assert _eventually(lambda: s._returners == set(callers.idents))
        assert _overlapped(s) == 0
    finally:
        callers.finish()
        s.close()
    assert not any(callers.errors)


def test_one_caller_that_waits_is_never_held_for():
    """A sequential caller is released, comes back, and nobody else is
    expected: no hold, no span, no counter — it has nobody to wait for."""
    exe = Paced(0.02)
    s = _hold_sched(exe)
    callers = Callers(s, 1, after=lambda c, k: k < 12)
    try:
        callers.start()
        callers.threads[0].join(timeout=30)
        s.flush(timeout=10)
        assert exe.sizes == [1] * 12
        assert "serve.held_batches" not in s.telemetry.counters
        assert "serve.hold_us" not in s.telemetry.counters
        assert "sched.hold_ms" not in s.telemetry.snapshot()["timers"]
        assert s._returners == {callers.idents[0]}  # seen, and not enough
    finally:
        callers.finish()
        s.close()


def test_callers_that_do_not_wait_never_cause_a_hold():
    """Callbacks (the open-loop mix, an async server): four streams, each
    sends its next request from another thread when the last returned, and
    nobody ever blocks in ``result()``."""
    exe = Paced(0.05)
    s = _hold_sched(exe)
    todo = queue.Queue()
    done = []

    def pump():
        while True:
            c = todo.get()
            if c is None:
                return
            fut = s.submit(_req(c))
            fut.add_done_callback(lambda f, c=c: (done.append(f.result().ids),
                                                  todo.put(c)))
    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        for c in range(4):
            todo.put(c)
        exe.wait_batches(10)
    finally:
        todo.put(None)
        t.join(timeout=10)
        s.close()
    assert len(done) >= 10 and all(len(ids) == 1 for ids in done)
    assert "serve.held_batches" not in s.telemetry.counters
    assert "sched.hold_ms" not in s.telemetry.snapshot()["timers"]
    assert not (s._returners or s._expected or s._watched)


@pytest.mark.parametrize("what", ["leave", "think"])
def test_callers_that_stop_coming_back_are_held_for_once(what):
    """Five callers in step; then three leave, or think for longer than the
    bound after every answer. The window is held for them ONCE, to the
    bound and no longer; after that the two that stay are served the
    moment both are back, whatever the others do."""
    exe = Paced()
    s = _hold_sched(exe, max_batch=8)
    changed = threading.Event()

    def after(c, k):
        if c < 2 or not changed.is_set():
            return True
        if what == "leave":
            return False
        time.sleep(BOUND_S + 0.25)
        return True
    callers = Callers(s, 5, after=after)
    try:
        callers.start()
        exe.settled(5)
        first = len(exe.sizes)          # batch first - 1 is in flight: its
        changed.set()                   # answers are the last the three
        exe.wait_batches(first + 6)     # come back for
        gaps = exe.gaps(first)
        sizes = exe.sizes[first:]
        # the dispatch after the change waited for three that were expected
        to_the_bound = [g for g in gaps if g >= PACE_S + BOUND_S - 0.01]
        assert len(to_the_bound) == 1 and gaps.index(to_the_bound[0]) <= 1
        # property 4: held to the bound and no longer
        assert to_the_bound[0] < PACE_S + BOUND_S + 0.25
        stayers = {callers.idents[0], callers.idents[1]}
        assert _eventually(lambda: s._returners == stayers)
        if what == "leave":
            assert sizes[1:6] == [2] * 5, sizes
    finally:
        callers.finish()
        s.close()
    assert not any(callers.errors)


@pytest.mark.parametrize("how", ["flush", "close"])
def test_flush_and_close_end_a_hold_and_resolve_every_future(how):
    exe = Paced(1.0)                    # a bound of 0.5 s to cut short
    s = _hold_sched(exe, max_batch=8)
    pause, paused = threading.Event(), threading.Semaphore(0)
    resume = threading.Event()
    once_more = [True]

    def after(c, k):
        if pause.is_set():
            if c == 0 and once_more[0]:             # the request to be held
                once_more[0] = False
                return True
            paused.release()
            assert resume.wait(timeout=30)
        return True
    callers = Callers(s, 3, after=after)
    try:
        callers.start()
        exe.settled(3, run=2)
        pause.set()                     # two of the three do not come back
        assert paused.acquire(timeout=10) and paused.acquire(timeout=10)
        # caller 0 is back and pending; the worker holds for the other two
        assert _eventually(lambda: s._holding and s.load() == 1)
        n = len(exe.sizes)
        t0 = time.monotonic()
        if how == "flush":
            s.flush(timeout=10)
        else:
            s.close()
        assert exe.sizes[n:] == [1]     # the held request, alone
        assert exe.at[n] - t0 < 0.25                 # not at the bound
        assert paused.acquire(timeout=10)            # answered: caller 0 too
        assert s.load() == 0
    finally:
        resume.set()
        callers.finish()
        s.close()
    if how == "close":
        assert not any(w.is_alive() for w in s._workers)
        # the callers found it closed; none of them hangs
        assert all(isinstance(e, RuntimeError)
                   for errs in callers.errors for e in errs)
    else:
        assert not any(callers.errors)


def test_worker_crash_after_a_hold_fails_that_batch_typed_and_serves_on():
    from lazzaro_tpu.reliability.errors import WorkerCrashed
    from lazzaro_tpu.reliability.faults import INJECTOR
    exe = Paced()
    s = _hold_sched(exe, max_batch=8)
    callers = Callers(s, 3)
    try:
        callers.start()
        exe.settled(3, run=3)
        held = _held(s)
        INJECTOR.arm("scheduler.worker", times=1)   # the next admission dies
        assert _eventually(lambda: s.stats()["worker_restarts"] == 1)
        n = len(exe.sizes)
        exe.wait_batches(n + 3)
        assert exe.sizes[n:n + 3] == [3] * 3        # the next ones: served
        assert [len(e) for e in callers.errors] == [1, 1, 1]
        assert all(isinstance(e[0], WorkerCrashed) for e in callers.errors)
        assert _held(s) >= held + 2                 # and held for, as before
    finally:
        INJECTOR.disarm("scheduler.worker")
        callers.finish()
        s.close()


@pytest.mark.parametrize("cap", [0, 2])
def test_the_tenant_cap_still_defines_a_full_window(cap):
    """Two callers in step, then one of them stays away while four requests
    of ONE tenant arrive by callback. Without a cap the window is full and
    ships at the demux; under a cap of two it is two of them — not full,
    though as many requests as a batch holds are pending — so the worker
    holds it for the caller that does come back."""
    exe = Paced()
    s = _hold_sched(exe, tenant_max_inflight=cap)
    pause = threading.Event()
    resume = threading.Event()

    def after(c, k):
        if c == 1 and pause.is_set():
            assert resume.wait(timeout=30)
        return True
    callers = Callers(s, 2, after=after, tenants=["x", "y"])
    try:
        callers.start()
        exe.settled(2, run=3)
        n = len(exe.sizes)              # batch n - 1 is in flight
        pause.set()
        futs = s.submit_many([_req(10 + i, "a") for i in range(4)])
        exe.wait_batches(n + 1)
        mine = exe.batches[n]
        if cap:
            assert mine == [("a", 10), ("a", 11), ("x", 0)]
            assert exe.gaps(n)[0] >= PACE_S + BOUND_S - 0.01   # to the bound
        else:
            assert mine == [("a", 10 + i) for i in range(4)]
            assert exe.gaps(n)[0] < PACE_S + 0.75 * BOUND_S
        resume.set()
        assert _values(futs) == [f"a:{10 + i}" for i in range(4)]
    finally:
        resume.set()
        callers.finish()
        s.close()
    assert not any(callers.errors)


def test_a_caller_waiting_for_the_rest_of_its_group_is_not_on_its_way_back():
    """One thread, ``submit_many`` of more than a batch, ``result()`` on
    each in turn (``search_memories_batch``): when the first dispatch
    returns, that thread is blocked on one of its futures and the rest of
    its group is still pending — it is not released, so nothing holds the
    rest for it. No hold, no span, no counter, and no time added."""
    exe = Paced()
    s = _hold_sched(exe)
    answers = []

    def caller():
        for round_ in range(5):
            futs = s.submit_many([_req(10 * round_ + i) for i in range(B + 3)])
            answers.append([f.result(timeout=30).ids[0] for f in futs])
    t = threading.Thread(target=caller, daemon=True)
    t.start()
    try:
        t.join(timeout=60)
        assert not t.is_alive()
        assert exe.sizes == [B, 3] * 5
        assert answers == [[f"u:{10 * r + i}" for i in range(B + 3)]
                           for r in range(5)]
        # the thread IS seen to come back, after the last of each group
        assert s._returners == {t.ident}
        # the rest of a group enters when its first batch is out, the next
        # group when the thread is back: not at the bound (the tree that
        # held the rest of every group read five of the nine there)
        assert (statistics.median(exe.gaps(1))
                < PACE_S + 0.75 * BOUND_S), exe.gaps(1)
        assert "serve.held_batches" not in s.telemetry.counters
        assert "serve.hold_us" not in s.telemetry.counters
        assert "sched.hold_ms" not in s.telemetry.snapshot()["timers"]
    finally:
        s.close()


class Device(Paced):
    """A paced executor that runs one batch at a time, as a chip does its
    passes: two batches in flight finish a pace apart, never together."""

    def __init__(self, pace_s=PACE_S):
        super().__init__(pace_s)
        self.chip = threading.Lock()

    def work(self):
        with self.chip:
            time.sleep(self.pace_s)


def test_two_batches_of_callers_keep_overlapping_and_are_never_held():
    """Property 4: the hold stands only where nothing is in flight and the
    window has room for everyone expected. Twice ``max_batch`` callers
    that wait keep one batch running and one queued behind it (PR 30's
    clause): every window is full, a finishing worker finds the other
    batch in flight — and where it does not, more callers are pending and
    on their way back than a batch holds, so the window ships as it is —
    and nobody is held for, though every caller is a known returner."""
    exe = Device()
    s = _hold_sched(exe, overlap_check=_reads_only)
    callers = Callers(s, 2 * B)
    try:
        callers.start()
        at = exe.settled(B, run=6)
        assert at <= 5, exe.sizes
        before = _overlapped(s)
        n = len(exe.sizes)
        exe.wait_batches(n + 8)
        assert exe.sizes[n:n + 7] == [B] * 7, exe.sizes
        assert _overlapped(s) - before >= 6
        assert _held(s) == 0
        assert s._returners == set(callers.idents)
        assert (statistics.median(exe.gaps(n + 1, n + 8))
                < PACE_S + 0.75 * BOUND_S)
    finally:
        callers.finish()
        s.close()
    assert not any(callers.errors)


@pytest.mark.parametrize("pending,expected,held", [
    (0, 1, False),          # one caller has nobody to wait for
    (0, 2, True),           # an empty window, so the first back rides along
    (1, 1, True),
    (1, B - 1, True),       # everyone fits: the split heals here
    (B, 1, False),          # a full window cannot grow
    (2, B - 1, False),      # more than a batch holds: the rest ride next
    (0, 2 * B, False),      # two batches' worth: PR 30's regime
], ids=["one", "two_coming", "one_here_one_coming", "everyone_fits", "full",
        "one_too_many", "two_batches"])
def test_the_hold_stands_only_where_it_can_bring_everyone_together(
        pending, expected, held):
    """The predicate alone, on a scheduler whose worker is busy elsewhere
    (a batch in flight is taken out of the books for the question)."""
    gate = Gate()
    s = _hold_sched(gate)
    try:
        s.submit(_req(99))
        assert gate.wait_entered() == 0
        with s._cond:
            s._pending = [(_req(i), None, 0.0) for i in range(pending)]
            until = time.monotonic() + 60.0
            s._expected = {c: until for c in range(1, expected + 1)}
            s._returners = set(s._expected)
            assert s._hold_left_locked() == 0.0         # one is in flight
            inflight, s._inflight_batches = s._inflight_batches, []
            try:
                assert (s._hold_left_locked() > 0) is held
                # the bound is over: nobody is expected, or known, any more
                s._expected = dict.fromkeys(s._expected, until - 61.0)
                assert s._hold_left_locked() == 0.0
                assert not (s._expected or s._returners)
            finally:
                s._inflight_batches = inflight
                s._pending = []
    finally:
        gate.open(0)
        s.close()


def test_each_caller_has_its_own_bound_so_a_busy_scheduler_forgets_too():
    """A scheduler that is never quiet (a demux every few milliseconds,
    each starting a bound) still forgets the callers that left: whoever is
    out past ITS bound is dropped at the next submission or demux, while a
    later demux's callers are still waited for."""
    s = _hold_sched(_echo_executor)
    try:
        with s._cond:
            now = time.monotonic()
            s._watched = {1: now - 1.0, 2: now + 60.0}
            s._expected = {3: now - 1.0, 4: now + 60.0}
            s._returners = {3, 4}
            s._note_return_locked(2)        # back inside its bound: learnt
            assert s._watched == {} and s._expected == {4: now + 60.0}
            assert s._returners == {2, 4}   # 1 never known, 3 forgotten
            s._expected.clear()
    finally:
        s.close()


def test_the_hold_brings_no_option():
    """Everything the hold reads the scheduler observes; its bound is a
    constant share (at most half) of an observed time."""
    import inspect
    assert tuple(inspect.signature(QueryScheduler).parameters) == (
        "executor", "max_batch", "name", "telemetry", "tenant_max_inflight",
        "dispatch_timeout_s", "breaker_threshold", "breaker_cooldown_s",
        "shed_depth", "shed_bytes", "degrade_cap_take", "degrade_nprobe",
        "admission_check", "overlap_check")
    assert 0.0 < HOLD_FRACTION <= 0.5
