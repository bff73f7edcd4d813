"""A request's handle (ISSUE 42; tier-1, CPU, fake executors): what
``QueryScheduler.submit`` returns is ONE lock, taken when the request is
made and released by whoever resolves it — not a
``concurrent.futures.Future``. One parametrised test walks the protocol the
callers use (every case counts); then the races the scheduler runs by
design (the watchdog's thread against the worker, a registrant against the
resolver), the typed failures through a real scheduler, a COUNT of what the
hand-over constructs (the same on the CPU and the chip: no ``Condition``
and one lock a request), and the worker thread that died for real, which the
next submission replaces (the restart loop's belt and braces, which no test
drove)."""

import concurrent.futures
import logging
import sys
import threading
import time

import pytest

from lazzaro_tpu.reliability.errors import (DispatchTimeout, LoadShed,
                                            PlanInfeasible, WorkerCrashed)
from lazzaro_tpu.reliability.faults import INJECTOR
from lazzaro_tpu.serve import QueryScheduler
from lazzaro_tpu.serve import scheduler as sched_mod
from lazzaro_tpu.serve.scheduler import (_CallerFuture, _fail_future,
                                         _set_future)
from lazzaro_tpu.utils.telemetry import Telemetry
from tests.test_query_scheduler import Gate, _echo_executor, _req

ROUNDS = 1500                   # of each race


def _handle():
    return _CallerFuture(threading.get_ident(), None)


def _in_thread(fn, *args):
    """Run ``fn`` on another thread; ``join()`` gives what it returned or
    raised, as (value, error)."""
    box = []

    def run():
        try:
            box.append((fn(*args), None))
        except BaseException as e:      # noqa: BLE001 — handed to the test
            box.append((None, e))

    t = threading.Thread(target=run, daemon=True)
    t.start()

    def join():
        t.join(timeout=10)
        assert not t.is_alive()
        return box[0]
    return join


class NoLock:
    """Stands where a resolved handle's lock was: reading must not touch it."""

    def __getattr__(self, name):
        raise AssertionError(f"a resolved handle touched its lock: {name}")


# ---------------------------------------------------------------- protocol
def result_after_resolution():
    h = _handle()
    assert not h.done() and not h.cancelled() and not h.running()
    _set_future(h, "answer")
    assert h.done() and not h.cancelled() and not h.running()
    h._lock = NoLock()                  # resolved: no lock operation at all
    assert h.result() == "answer" == h.result(timeout=0)
    assert h.exception() is None is h.exception(timeout=0)


def result_blocks_until_resolved():
    h = _handle()
    join = _in_thread(h.result, 10)
    while not h.waiter:                 # the thread blocked in result()
        time.sleep(0.001)
    assert not h.done()
    _set_future(h, 7)
    assert join() == (7, None) and h.waiter == 0


def exception_blocks_until_resolved():
    h = _handle()
    join = _in_thread(h.exception, 10)
    time.sleep(0.02)
    err = LoadShed("full")
    _fail_future(h, err)
    assert join() == (err, None)


def timeout_raises_the_futures_timeout_and_leaves_the_handle_pending():
    h = _handle()
    for timeout in (0.02, 0, -1.0):     # Future: a negative wait is no wait
        t0 = time.monotonic()
        with pytest.raises(concurrent.futures.TimeoutError):
            h.result(timeout=timeout)
        with pytest.raises(concurrent.futures.TimeoutError):
            h.exception(timeout=timeout)
        assert time.monotonic() - t0 < 5
    assert not h.done() and h.waiter == 0
    _set_future(h, 1)
    assert h.result(timeout=0) == 1


def failure_surfaces_typed_at_result_and_is_returned_by_exception():
    h = _handle()
    err = DispatchTimeout("late")
    _fail_future(h, err)
    assert h.done() and not h.cancelled()
    for _ in range(2):                  # every read raises the same error
        with pytest.raises(DispatchTimeout) as got:
            h.result(timeout=1)
        assert got.value is err
    assert h.exception() is err
    _set_future(h, "late answer")       # the late dispatch: discarded
    assert h.exception() is err


def cancel_before_resolution():
    h = _handle()
    seen = []
    h.add_done_callback(seen.append)
    join = _in_thread(h.result, 10)
    while not h.waiter:
        time.sleep(0.001)
    assert h.cancel() is True and h.cancel() is True
    assert h.cancelled() and h.done() and seen == [h]
    value, err = join()                 # the blocked reader is let go
    assert type(err) is concurrent.futures.CancelledError
    for read in (h.result, h.exception):
        with pytest.raises(concurrent.futures.CancelledError):
            read(timeout=0)
    _set_future(h, "late")              # its answer, when it comes: dropped
    _fail_future(h, RuntimeError("late"))
    assert h.cancelled() and seen == [h]


def cancel_after_resolution_changes_nothing():
    for resolve, arg in ((_set_future, 3), (_fail_future, WorkerCrashed("x"))):
        h = _handle()
        resolve(h, arg)
        assert h.cancel() is False and not h.cancelled()
        assert h.exception() is (arg if resolve is _fail_future else None)


def callbacks_before_resolution_run_on_the_resolver_in_order():
    h = _handle()
    seen = []
    for i in range(3):
        h.add_done_callback(lambda f, i=i: seen.append(
            (i, f, threading.get_ident())))
    assert seen == []
    join = _in_thread(_set_future, h, "x")
    join()
    assert [(i, f) for i, f, _ in seen] == [(0, h), (1, h), (2, h)]
    assert {t for _, _, t in seen}.isdisjoint({threading.get_ident()})
    assert len({t for _, _, t in seen}) == 1


def callbacks_after_resolution_run_at_once_on_the_registering_thread():
    for resolve, arg in ((_set_future, 3), (_fail_future, LoadShed("x")),
                         (lambda h, _: h.cancel(), None)):
        h = _handle()
        resolve(h, arg)
        seen = []
        h.add_done_callback(lambda f: seen.append((1, threading.get_ident())))
        assert seen == [(1, threading.get_ident())]
        h.add_done_callback(lambda f: seen.append((2, f.done())))
        assert seen[1] == (2, True) and h._callbacks == []


def a_raising_callback_is_logged_and_the_others_still_run(caplog):
    h = _handle()
    seen = []
    h.add_done_callback(seen.append)
    h.add_done_callback(lambda f: 1 / 0)
    h.add_done_callback(seen.append)
    with caplog.at_level(logging.ERROR, logger="lazzaro_tpu.serve"):
        _set_future(h, "x")             # does not raise
        h.add_done_callback(lambda f: [].pop())     # nor does a late one
    assert seen == [h, h] and h.result() == "x"
    logged = [r for r in caplog.records if "callback" in r.getMessage()]
    assert [r.exc_info[0] for r in logged] == [ZeroDivisionError, IndexError]


def a_callback_reads_its_answer_and_a_failure_alike():
    got = []
    h = _handle()
    h.add_done_callback(lambda f: got.append(f.result()))
    _set_future(h, 5)
    h = _handle()
    h.add_done_callback(lambda f: got.append(f.exception()))
    err = PlanInfeasible("no split fits")
    _fail_future(h, err)
    assert got == [5, err]


def two_readers_of_one_handle_both_get_the_answer():
    h = _handle()
    joins = [_in_thread(h.result, 10) for _ in range(2)]
    time.sleep(0.05)                    # both blocked on the one lock
    _set_future(h, "both")
    assert [j() for j in joins] == [("both", None)] * 2
    assert h.result(timeout=0) == "both"            # and a third, later
    assert not h._lock.locked()         # left open: nobody blocks again


def free_attributes_are_the_caller_s_to_set():
    h = _handle()
    h.bench_i = 41                      # the harness's open loop sets one
    _set_future(h, 1)
    assert h.bench_i == 41 and vars(h)["bench_i"] == 41


def futures_wait_and_as_completed_refuse_it_at_once():
    # they reach into a Future's condition: typed, at once, never a hang
    pending, resolved = _handle(), _handle()
    _set_future(resolved, 1)
    for h in (pending, resolved):
        with pytest.raises(TypeError, match="not a concurrent.futures"):
            concurrent.futures.wait([h], timeout=5)
        with pytest.raises(TypeError, match="not a concurrent.futures"):
            next(concurrent.futures.as_completed([h], timeout=5))
    assert not isinstance(_handle(), concurrent.futures.Future)


PROTOCOL = [
    result_after_resolution, result_blocks_until_resolved,
    exception_blocks_until_resolved,
    timeout_raises_the_futures_timeout_and_leaves_the_handle_pending,
    failure_surfaces_typed_at_result_and_is_returned_by_exception,
    cancel_before_resolution, cancel_after_resolution_changes_nothing,
    callbacks_before_resolution_run_on_the_resolver_in_order,
    callbacks_after_resolution_run_at_once_on_the_registering_thread,
    a_raising_callback_is_logged_and_the_others_still_run,
    a_callback_reads_its_answer_and_a_failure_alike,
    two_readers_of_one_handle_both_get_the_answer,
    free_attributes_are_the_caller_s_to_set,
    futures_wait_and_as_completed_refuse_it_at_once,
]


@pytest.mark.parametrize("case", PROTOCOL, ids=lambda c: c.__name__)
def test_the_handle_keeps_the_callers_protocol(case, caplog):
    case(caplog) if case.__code__.co_argcount else case()


# ------------------------------------------------------------------- races
class Pair:
    """Two threads that spin until the next handle is there and then fall
    on it together, round after round, under a switch interval short
    enough to part any two bytecodes."""

    def __init__(self, first, second):
        self.interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        self.h = self.stopped = None
        self.seq = 0
        self.done = [0, 0]
        self.won = [None, None]
        self.threads = [threading.Thread(target=self._run, args=(i, fn),
                                         daemon=True)
                        for i, fn in enumerate((first, second))]
        for t in self.threads:
            t.start()

    def _run(self, i, fn):
        seen = 0
        while True:
            while self.seq == seen:     # spinning: no lock orders the two
                if self.stopped:
                    return
            seen = self.seq
            self.won[i] = fn(self.h)
            self.done[i] = seen

    def round(self, h):
        self.h = h
        self.seq += 1
        deadline = time.monotonic() + 10
        while self.done != [self.seq, self.seq]:
            assert time.monotonic() < deadline
        return tuple(self.won)

    def stop(self):
        sys.setswitchinterval(self.interval)
        self.stopped = True
        for t in self.threads:
            t.join(timeout=10)
            assert not t.is_alive()


def test_the_watchdog_and_the_worker_race_and_exactly_one_wins():
    """``_deadline`` on the watchdog's ``Timer`` thread fails a batch's
    handles while the worker's demux sets their answers: whoever is first
    owns the handle, the other changes nothing — whichever it is, every
    read and every callback sees that one outcome."""
    err = DispatchTimeout("watchdog")
    pair = Pair(lambda h: h._resolve("answer", None),
                lambda h: h._resolve(None, err))
    wins = [0, 0]
    try:
        for _ in range(ROUNDS):
            h = _handle()
            calls = []
            h.add_done_callback(lambda f: calls.append(f.exception()))
            worker, watchdog = pair.round(h)
            assert worker is not watchdog           # exactly one True
            wins[0] += worker
            wins[1] += watchdog
            if worker:
                assert h.result(timeout=0) == "answer" and calls == [None]
            else:
                assert h.exception(timeout=0) is err and calls == [err]
            # the loser's paths keep their meaning: tolerated, nothing moves
            _set_future(h, "late")
            _fail_future(h, RuntimeError("late"))
            assert h.cancel() is False and len(calls) == 1
            assert not h._lock.locked()
    finally:
        pair.stop()
    assert sum(wins) == ROUNDS


def test_a_callback_registered_while_the_answer_is_set_runs_exactly_once():
    runs = []
    pair = Pair(lambda h: h.add_done_callback(lambda f: runs.append(f)),
                lambda h: h._resolve(1, None))
    try:
        for _ in range(ROUNDS):
            h = _handle()
            del runs[:]
            pair.round(h)
            assert runs == [h] and h._callbacks == []
    finally:
        pair.stop()


# ------------------------------------------- typed failures, end to end
def _shed(s, gate):
    first = s.submit(_req(0))
    assert gate.wait_entered() == 0     # in flight: the queue fills behind
    s.submit_many([_req(i) for i in range(1, 3)])
    return s.submit(_req(9)), LoadShed


def _infeasible(s, gate):
    return s.submit(_req(13)), PlanInfeasible


def _timed_out(s, gate):
    fut = s.submit(_req(0))
    assert gate.wait_entered() == 0     # held past the deadline
    return fut, DispatchTimeout


def _crashed(s, gate):
    INJECTOR.arm("scheduler.worker", times=1)
    return s.submit(_req(0)), WorkerCrashed


def _refuse_13(reqs):
    if any(int(r.query[0]) == 13 for r in reqs):
        raise PlanInfeasible("no split fits")


@pytest.mark.parametrize("how,kw", [
    (_shed, {"shed_depth": 2}), (_infeasible, {"admission_check": _refuse_13}),
    (_timed_out, {"dispatch_timeout_s": 0.1}), (_crashed, {})],
    ids=["LoadShed", "PlanInfeasible", "DispatchTimeout", "WorkerCrashed"])
def test_typed_failures_surface_at_result_and_in_a_callback(how, kw):
    gate = Gate()
    s = QueryScheduler(gate, max_batch=4, telemetry=Telemetry(), **kw)
    try:
        fut, typed = how(s, gate)
        seen = []
        fut.add_done_callback(lambda f: seen.append(type(f.exception())))
        with pytest.raises(typed):
            fut.result(timeout=10)
        assert isinstance(fut.exception(timeout=0), typed)
        assert seen == [typed] and fut.done() and not fut.cancelled()
    finally:
        INJECTOR.disarm("scheduler.worker")
        gate.open(*range(8))
        s.close()


# ------------------------------------------------------------- the count
def test_the_hand_over_makes_no_condition_and_one_lock_a_request(monkeypatch):
    """1,000 requests from 16 waiting threads through a fake executor: what
    the hand-over constructs is a count, the same on the CPU and the chip.
    The parent made a ``Condition`` (an ``RLock``, a deque) a request and a
    lock a wait."""
    clients, each = 16, 63              # 1,008 requests
    tel = Telemetry()
    s = QueryScheduler(_echo_executor, max_batch=clients, telemetry=tel)
    start = threading.Barrier(clients + 1)
    errors = []

    def client(c):
        start.wait()
        try:
            for i in range(each):
                assert s.submit(_req(i, tenant=f"t{c}")).result(
                    timeout=30).ids == [f"t{c}:{i}"]
        except BaseException as e:      # noqa: BLE001 — handed to the test
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()                       # a Thread has Conditions of its own
    made = {"condition": 0, "handle_lock": 0, "wait_lock": 0}

    def counting(key, real):
        def make(*args, **kw):
            made[key] += 1
            return real(*args, **kw)
        return make

    monkeypatch.setattr(threading, "Condition",
                        counting("condition", threading.Condition))
    monkeypatch.setattr(sched_mod, "_allocate_lock",
                        counting("handle_lock", sched_mod._allocate_lock))
    # the lock a ``Condition.wait`` allocates, whoever waits: the parent's
    # callers made one a ``result()``; now only the worker's own waits do
    monkeypatch.setattr(threading, "_allocate_lock",
                        counting("wait_lock", threading._allocate_lock))
    start.wait()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    batches = s.batches_flushed
    monkeypatch.undo()
    s.close()                           # folds the last answers' wake-ups
    assert not errors
    n = clients * each
    assert tel.counter_total("serve.requests") == n
    # none a request: what is left is ``_account``'s one ``Event`` a BATCH
    # (the watchdog's flag), which this PR leaves byte for byte
    assert made["condition"] == batches < n / 4
    assert made["handle_lock"] == n     # one lock a request, and no other
    assert made["wait_lock"] <= 4 * batches < n
    assert tel.counter_total("serve.wakes") == n    # every answer waited for


# ------------------------------------------------- the worker that died
@pytest.mark.filterwarnings(      # the thread dies with its error: the point
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_worker_that_died_is_replaced_by_the_next_submission():
    """The restart loop survives every crash of ``_serve_loop``; a thread
    that ends all the same (here: the restart's own bookkeeping raises) is
    found dead by the next submission, which starts another."""
    tel = Telemetry()
    s = QueryScheduler(_echo_executor, max_batch=4, telemetry=tel)
    try:
        assert s.submit(_req(0)).result(timeout=10).ids == ["u:0"]
        first = s._workers[0]
        real = tel.bump

        def bump(name, *args, **kw):
            if name == "reliability.worker_restarts":
                raise RuntimeError("the registry is gone")
            return real(name, *args, **kw)

        tel.bump = bump
        INJECTOR.arm("scheduler.worker", times=1)
        with pytest.raises(WorkerCrashed):
            s.submit(_req(1)).result(timeout=10)
        first.join(timeout=10)
        assert not first.is_alive() and s._workers == [first]
        tel.bump = real
        assert s.submit(_req(2)).result(timeout=10).ids == ["u:2"]
        assert s._workers[0] is not first and s._workers[0].is_alive()
    finally:
        INJECTOR.disarm("scheduler.worker")
        s.close()
