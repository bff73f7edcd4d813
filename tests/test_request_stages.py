"""A request's stages (ISSUE 41; tier-1, CPU, fake executors): the scheduler
stamps every served request where it changes hands — submit, flush, around
the executor call, answer set, caller awake, caller back — on one monotonic
clock and sums the differences into seven counters. The stamps are taken
together so that they add up: for a request served through ``result()``,
woke − submit = queue + account + exec + demux_wait + wake; for a callback
request the same without the wake. The clock below stands where the
scheduler reads ``perf_counter_ns``: real time cut to whole microseconds (so
a counter in microseconds loses nothing, and the hold's bounds stay real),
logged by the thread that read it — the test adds up what each caller's own
thread saw and holds the counters to it, to the nanosecond."""

import threading
import time
import types

import pytest

from lazzaro_tpu.reliability.errors import DispatchTimeout
from lazzaro_tpu.serve import QueryScheduler
from lazzaro_tpu.serve import scheduler as sched_mod
from lazzaro_tpu.utils.telemetry import Telemetry
from tests.test_query_scheduler import (B, Callers, Gate, Paced,
                                        _echo_executor, _req)

STAGES = ("serve.queue_wait_us", "serve.account_us", "serve.exec_us",
          "serve.demux_wait_us")
WAY_BACK = ("serve.wake_us", "serve.wakes", "serve.return_us", "serve.returns")
PACE_S = 0.3                    # a paced batch; a caller's bound is half
BOUND_S = sched_mod.HOLD_FRACTION * PACE_S


class Clock:
    """``perf_counter_ns`` in whole microseconds, every reading logged under
    the thread that took it."""

    def __init__(self):
        self.lock = threading.Lock()
        self.log = {}

    def __call__(self):
        now = time.perf_counter_ns() // 1000 * 1000
        with self.lock:
            self.log.setdefault(threading.get_ident(), []).append(now)
        return now

    def last(self):
        with self.lock:
            return self.log[threading.get_ident()][-1]


@pytest.fixture()
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(sched_mod, "_clock_ns", c)
    return c


def _sched(exe, tel=None, **kw):
    return QueryScheduler(exe, max_batch=kw.pop("max_batch", B),
                          telemetry=tel or Telemetry(), **kw)


def _us(tel, names):
    return sum(tel.counter_total(n) for n in names)


class Loop(Callers):
    """Closed-loop callers that stop after ``k`` answers each."""

    def __init__(self, s, n, k, **kw):
        super().__init__(s, n, after=lambda c, done: done < k, **kw)

    def run(self):
        self.start()
        for t in self.threads:
            t.join(timeout=60)
            assert not t.is_alive()


@pytest.mark.parametrize("n,k", [(1, 6), (3, 5)], ids=["one", "three"])
def test_waiting_callers_stages_add_up_to_what_their_threads_saw(clock, n, k):
    exe = Paced(PACE_S)
    s = _sched(exe)
    loop = Loop(s, n, k)
    loop.run()
    s.close()                           # folds the last answers' wake-ups
    tel = s.telemetry
    assert loop.answers == [k] * n and not any(loop.errors)
    felt = backs = 0
    for ident in loop.idents:           # a caller reads: submit, woke, ...
        log = clock.log[ident]
        assert len(log) == 2 * k
        felt += sum(woke - sub for sub, woke in zip(log[0::2], log[1::2]))
        backs += sum(sub - woke for woke, sub in zip(log[1::2], log[2::2]))
    assert tel.counter_total("serve.requests") == n * k
    assert tel.counter_total("serve.wakes") == n * k
    assert felt == 1000 * _us(tel, STAGES + ("serve.wake_us",))
    # a caller has BOUND_S to come back: all do, and a return is from the thread's wake-up to its next submission
    assert tel.counter_total("serve.returns") == n * (k - 1)
    assert backs == 1000 * tel.counter_total("serve.return_us")
    assert s._woke == {} and s._way_back == [0, 0, 0, 0]


def test_callback_requests_add_up_without_a_wake_and_each_stage_is_its_own(
        clock):
    sizes, seen = [], {}

    def exe(reqs):
        sizes.append(len(reqs))
        return _echo_executor(reqs)

    def on_done(fut):                   # on the worker, right after t_set
        seen[fut.i] = clock.last()
        assert fut.result().ids         # a callback's read is no wake-up

    s = _sched(exe)
    subs = {}
    for group in ([0], [1, 2, 3], [4, 5]):
        futs = s.submit_many([_req(i) for i in group])
        for i, fut in zip(group, futs):
            subs[i] = clock.last()      # this thread's last reading: t_submit
            fut.i = i
            fut.add_done_callback(on_done)
        s.flush(timeout=10)
    s.close()
    tel = s.telemetry
    assert sum(sizes) == 6 == tel.counter_total("serve.requests")
    felt = sum(seen[i] - subs[i] for i in subs)
    assert felt == 1000 * _us(tel, STAGES)
    assert not {k for k in tel.counters if k in WAY_BACK}      # no entry
    # the one worker reads, a batch: flush, exec0, exec1, one set a request
    (log,) = [v for k, v in clock.log.items() if k != threading.get_ident()]
    account = execute = demux = 0
    for n in sizes:
        flush, exec0, exec1, *sets = log[:3 + n]
        del log[:3 + n]
        account += n * (exec0 - flush)
        execute += n * (exec1 - exec0)
        demux += sum(t - exec1 for t in sets)
    assert not log
    assert account == 1000 * tel.counter_total("serve.account_us")
    assert execute == 1000 * tel.counter_total("serve.exec_us")
    assert demux == 1000 * tel.counter_total("serve.demux_wait_us")


def test_a_caller_that_does_not_come_back_wakes_but_never_returns(clock):
    exe = Paced(PACE_S)
    s = _sched(exe)
    try:
        assert s.submit(_req(0)).result(timeout=10).ids
        me = threading.get_ident()
        assert me in s._watched and s._woke[me][1] == 1
        time.sleep(BOUND_S + 0.05)      # its bound is over
        with s._cond:
            s._expire_locked()
        assert me not in s._watched and me not in s._woke    # expired
        assert s._way_back[1] == 1 and s._way_back[3] == 0
        # back at last: the wake-up rides the next batch's counters, and a
        # thread that comes back after its bound is not "on its way back"
        assert s.submit(_req(1)).result(timeout=10).ids
    finally:
        s.close()
    tel = s.telemetry
    assert tel.counter_total("serve.wakes") == 2
    assert "serve.returns" not in tel.counters
    assert "serve.return_us" not in tel.counters


def test_each_request_of_two_batches_in_flight_still_adds_up(clock):
    gate = Gate()
    s = _sched(gate, overlap_check=lambda reqs: True)
    first = s.submit(_req(0))
    assert gate.wait_entered() == 0
    rest = s.submit_many([_req(i) for i in range(1, B + 1)])
    assert gate.wait_entered() == 1     # admitted over the first
    gate.open(1)
    for f in rest:
        assert f.result(timeout=10).ids
    gate.open(0)
    assert first.result(timeout=10).ids
    s.close()
    tel = s.telemetry
    assert tel.counter_total("serve.overlapped_batches") == 1
    sub0, sub1, *woke = clock.log[threading.get_ident()]
    assert len(woke) == B + 1 == tel.counter_total("serve.wakes")
    felt = sum(woke) - sub0 - B * sub1
    assert felt == 1000 * _us(tel, STAGES + ("serve.wake_us",))


@pytest.mark.parametrize("how", ["executor_failed", "watchdog_failed"])
def test_a_failed_batch_counts_in_no_stage(clock, how):
    gate = Gate(fail={0})
    s = _sched(gate, dispatch_timeout_s=0.2 if how == "watchdog_failed" else 0)
    try:
        fut = s.submit(_req(0))
        assert gate.wait_entered() == 0
        if how == "watchdog_failed":
            with pytest.raises(DispatchTimeout):
                fut.result(timeout=10)
        gate.open(0)                    # the late dispatch raises besides
        if how == "executor_failed":
            with pytest.raises(RuntimeError, match="exploded"):
                fut.result(timeout=10)
        s.flush(timeout=10)
    finally:
        gate.open(0)
        s.close()
    assert not [k for k in s.telemetry.counters if k.startswith("serve.")]
    assert s._woke == {} and fut.t_set == 0


def test_with_the_registry_off_nothing_is_bumped_or_stored(clock):
    s = _sched(Paced(0.02), Telemetry(enabled=False))
    loop = Loop(s, 2, 4)
    loop.run()
    assert loop.answers == [4, 4]
    assert s._woke == {} and s._way_back == [0, 0, 0, 0]
    s.close()
    assert not s.telemetry.counters and not s.telemetry.timers
    assert s._woke == {}
    # the callers' threads read the clock at their submissions alone
    assert all(len(clock.log[i]) == 4 for i in loop.idents)


def test_each_stage_counter_is_bumped_at_most_once_a_served_batch(clock):
    tel = Telemetry()
    calls = []
    bump = tel.bump

    def counted(name, n=1, labels=None):
        calls.append((name, labels))
        bump(name, n, labels)

    tel.bump = counted
    exe = Paced(0.03)
    s = _sched(exe, tel)
    loop = Loop(s, 3, 6)
    loop.run()
    mid = [name for name, _ in calls]
    s.close()
    batches = tel.counter_total("serve.batches")
    assert batches == len(exe.sizes) >= 6
    assert tel.counter_total("serve.requests") == 18
    for name in STAGES + WAY_BACK:
        assert mid.count(name) == batches, name
    # close() hands in what no later batch could: once more, the way back
    end = [name for name, _ in calls]
    for name in STAGES + WAY_BACK:
        assert end.count(name) == batches + (name in WAY_BACK), name
    # no request of its own in the registry: no label, no timer but the two
    # the scheduler had (the labelled queue wait, the batch's size)
    assert not [c for c in calls if c[1] is not None]
    assert {k.split("{")[0] for k in tel.timers} == {
        "serve.queue_wait_ms", "serve.batch_requests", "sched.account_ms",
        "sched.demux_ms", "sched.idle_ms", "sched.hold_ms"}


def test_an_answer_read_twice_wakes_once_and_a_group_returns_once(clock):
    """``search_memories_batch`` reads ``f.result()`` twice a future and
    waits on several futures of one ``submit_many``: every answer is one
    wake-up, the thread's next submission one return."""
    exe = Paced(PACE_S)
    s = _sched(exe, max_batch=2)
    try:
        for _ in range(2):
            futs = s.submit_many([_req(i) for i in range(4)])   # two batches
            got = [(f.result(timeout=10).ids, f.result(timeout=10).scores)
                   for f in futs]
            assert len(got) == 4
    finally:
        s.close()
    tel = s.telemetry
    assert tel.counter_total("serve.wakes") == 8
    assert tel.counter_total("serve.returns") == 1
    sub0, *rest = clock.log[threading.get_ident()]
    woke0, sub1, woke1 = rest[:4], rest[4], rest[5:]
    assert len(woke1) == 4
    felt = sum(woke0) - 4 * sub0 + sum(woke1) - 4 * sub1
    assert felt == 1000 * _us(tel, STAGES + ("serve.wake_us",))
    assert sub1 - woke0[-1] == 1000 * tel.counter_total("serve.return_us")


def test_the_scheduler_reads_no_clock_that_may_step(monkeypatch):
    """Enqueue and flush were stamped with ``time.time()`` beside spans on
    ``perf_counter``: the scheduler now reads monotonic clocks alone (the
    breaker keeps its own wall time)."""
    monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
        perf_counter=time.perf_counter, monotonic=time.monotonic,
        sleep=time.sleep))
    s = _sched(_echo_executor, dispatch_timeout_s=5.0)
    try:
        futs = s.submit_many([_req(i) for i in range(3)])
        s.flush(timeout=10)
        assert [f.result(timeout=10).ids for f in futs]
    finally:
        s.close()
    tel = s.telemetry
    waits = tel.timer_values("serve.queue_wait_ms")
    assert len(waits) == 3 and min(waits) >= 0.0
    assert tel.counter_total("serve.queue_wait_us") == pytest.approx(
        1e3 * sum(waits), abs=3.0)


def test_api_callers_wait_through_the_same_result(tmp_path):
    """``MemorySystem.search_memories`` blocks in ``_CallerFuture.result``:
    an application's threads are counted like the benchmark's clients."""
    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.memory_system import MemorySystem
    from tests.test_fused_ingest import ClusteredEmb, QueueLLM

    ms = MemorySystem(
        enable_async=False, db_dir=str(tmp_path / "db"), verbose=False,
        load_from_disk=False, llm_provider=QueueLLM(20),
        embedding_provider=ClusteredEmb(), auto_prune=False,
        config=MemoryConfig(auto_consolidate=False, enable_hierarchy=False))
    try:
        ms.switch_user("alice")
        ms.start_conversation()
        ms.add_to_short_term("a fact", "episodic", 0.7)
        ms.end_conversation()
        for _ in range(3):
            assert ms.search_memories("a fact")
        tel = ms.telemetry
        served = tel.counter_total("serve.requests")
        assert served >= 3
    finally:
        ms.close()
    assert tel.counter_total("serve.wakes") == served
    assert tel.counter_total("serve.wake_us") > 0
    assert tel.counter_total("serve.exec_us") > 0
