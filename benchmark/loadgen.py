"""The one general load generator. A traffic mix is a data file; its ``loop``
field picks one of four loops (open, closed, conversations, mixed) and its
other fields are their parameters. Nothing here knows a cell's name.

Every seed draws from the same population: the multiset of tenants (Zipf by
largest remainder, tenant 0 hottest), the multiset of inter-arrival gaps
(the exponential's quantiles) and the target facts are fixed by the mix and
the sizes; the seed only orders them. So two seeds do the same work.

Inside a window the generator does the least it can: schedule, tenants and
query vectors are arrays made before it; the sender sleeps to the next due
time and submits; completion is stamped in the future's done-callback;
nothing is reduced, logged or allocated per request until the window closed.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np


def zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    """How many of ``n`` requests each tenant gets: n * p_t, rounded by
    largest remainder, p_t proportional to 1/(t+1)**s."""
    p = 1.0 / np.arange(1, tenants + 1, dtype=np.float64) ** s
    want = n * p / p.sum()
    base = np.floor(want).astype(np.int64)
    rest = n - int(base.sum())
    order = np.argsort(-(want - base), kind="stable")
    base[order[:rest]] += 1
    return base


def tenant_sequence(n: int, tenants: int, s: float,
                    rng: np.random.Generator) -> np.ndarray:
    seq = np.repeat(np.arange(tenants, dtype=np.int32),
                    zipf_counts(n, tenants, s))
    rng.shuffle(seq)
    return seq


def poisson_schedule(n: int, rate: float, rng: np.random.Generator
                     ) -> np.ndarray:
    """[n] due times in seconds from the window's start: the exponential
    distribution's n quantile gaps in seeded order, scaled so that the
    schedule spans exactly n / rate seconds."""
    gaps = -np.log1p(-(np.arange(n, dtype=np.float64) + 0.5) / n)
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    return due * ((n - 0.5) / rate / due[-1])


class Samples:
    """What a window leaves for the reduction. Times are perf_counter
    seconds; ``done`` stays NaN for a request that never came back, ``ok``
    is False for one that failed. ``answers`` holds the results of the
    requests flagged in ``keep`` only, by request number: the window keeps
    nothing else per request, so that the benchmark's own garbage does not
    stall the threads it measures."""

    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.request_of = np.arange(n)   # sample -> index into the plan
        self.answers: dict = {}
        self.t0 = self.t1 = 0.0          # window start / end


def _send(out: Samples, submit: Callable, requests: Sequence,
          due: np.ndarray, keep: np.ndarray, clock: Callable,
          sleep: Callable) -> threading.Event:
    """The open loop's sender: request i goes out when ``due[i]`` has passed,
    late or not; ``out`` takes its stamps. Returns the event that is set
    when the last answer is back."""
    n = len(requests)
    done, ok, answers = out.done, out.ok, out.answers
    returned = [0]                  # written by the one thread that answers
    all_back = threading.Event()

    def stamp(fut):          # one callable for all: no closure per request
        i = fut.bench_i
        done[i] = clock()
        if not fut.cancelled() and fut.exception() is None:
            ok[i] = True
            if keep[i]:
                answers[i] = fut.result()
        returned[0] += 1
        if returned[0] == n:
            all_back.set()

    sent = out.sent
    for i in range(n):
        wait = due[i] - clock()
        if wait > 0:
            sleep(wait)
        sent[i] = clock()
        fut = submit(requests[i])
        fut.bench_i = i
        fut.add_done_callback(stamp)
    out.due = due
    return all_back


def run_open(submit: Callable, requests: Sequence, due_rel: np.ndarray,
             keep: np.ndarray, drain_s: float, annotate: Callable,
             clock: Callable = time.perf_counter,
             sleep: Callable = time.sleep) -> Samples:
    """Open loop: request i is sent when ``due_rel[i]`` has passed, late or
    not, and its latency counts from the time it was DUE. (``clock`` and
    ``sleep`` are the host's; the tests put a clock of their own there.)"""
    out = Samples(len(requests))
    with annotate("bench.window"):
        out.t0 = clock()
        _send(out, submit, requests, due_rel + out.t0, keep, clock,
              sleep).wait(timeout=drain_s)
        out.t1 = clock()
    return out


def run_closed(submit: Callable, requests: Sequence, clients: int,
               keep: np.ndarray, seconds: float, drain_s: float,
               annotate: Callable) -> Samples:
    """Closed loop: ``clients`` threads, each sends its next request when
    its last returned; client c walks requests c, c + clients, ... (and
    wraps). Stops sending at the deadline; requests in flight then are
    awaited but complete outside the window."""
    n = len(requests)
    clock = time.perf_counter
    per: List[tuple] = [([], [], [], []) for _ in range(clients)]
    answers: dict = {}
    start = threading.Barrier(clients + 1)
    t_end = [0.0]

    def client(c: int):
        idx, sent, done, good = per[c]
        i = c
        start.wait()
        while True:
            t_sent = clock()
            if t_sent >= t_end[0]:
                return
            r = i % n
            try:
                res = submit(requests[r]).result(timeout=drain_s)
            except Exception:       # noqa: BLE001 — counted as failed
                res = None
            idx.append(r); sent.append(t_sent); done.append(clock())
            good.append(res is not None)
            if res is not None and keep[r]:
                answers[r] = res
            i += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    with annotate("bench.window"):
        t0 = clock()
        t_end[0] = t0 + seconds
        start.wait()
        time.sleep(seconds)
        t1 = clock()
    for t in threads:
        t.join(timeout=drain_s + 5)
    out = Samples(sum(len(p[0]) for p in per))
    out.request_of = np.concatenate([np.asarray(p[0], np.int64) for p in per])
    out.sent = out.due = np.concatenate([np.asarray(p[1], float) for p in per])
    out.done = np.concatenate([np.asarray(p[2], float) for p in per])
    out.ok = np.concatenate([np.asarray(p[3], bool) for p in per])
    out.answers, out.t0, out.t1 = answers, t0, t1
    return out


class ConversationLog:
    def __init__(self):
        self.tenants: List[int] = []
        self.starts: List[float] = []       # on the window's clock
        self.seconds: List[float] = []
        self.errors: List[str] = []
        self.t0 = self.t1 = 0.0
        self.ran_out = False        # the tenants ended before the seconds


def _converse(log: ConversationLog, converse: Callable,
              tenants: Sequence[int], t_end: float, annotate: Callable,
              clock: Callable) -> None:
    """The one writer: a conversation per tenant, in order and back to back,
    none started once ``t_end`` has passed; ``log`` takes each one's start
    and length."""
    for t in tenants:
        a = clock()
        if a >= t_end:
            break
        log.starts.append(a)
        with annotate("bench.conversation"):
            try:
                converse(int(t))
            except Exception as e:      # noqa: BLE001 — a failed write
                log.errors.append(f"tenant {t}: {e!r}")
                log.tenants.append(int(t))
                log.seconds.append(float("nan"))
                continue
        log.tenants.append(int(t))
        log.seconds.append(clock() - a)
    else:
        log.ran_out = True
    log.t1 = clock()


def run_conversations(converse: Callable, tenants: Sequence[int],
                      seconds: float, annotate: Callable,
                      clock: Callable = time.perf_counter) -> ConversationLog:
    """One writer: a conversation per tenant, in order, until ``seconds``
    have passed; the last one started is finished and counted, and the
    window runs to its end. ``tenants`` are all that the deployment's free
    rows hold: a writer fast enough to get through them closes the window
    early (``ran_out``), and the rate is still its work over its time."""
    log = ConversationLog()
    with annotate("bench.window"):
        log.t0 = clock()
        _converse(log, converse, tenants, log.t0 + seconds, annotate, clock)
    return log


def run_mixed(reads: Optional[tuple], writes: Optional[tuple], seconds: float,
              annotate: Callable, clock: Callable = time.perf_counter,
              sleep: Callable = time.sleep
              ) -> Tuple[Optional[Samples], Optional[ConversationLog]]:
    """Readers beside a writer, in ONE window from one ``t0``: ``reads`` is
    the open loop's ``(submit, requests, due_rel, keep, drain_s)``, sent from
    this thread as ``run_open`` sends them; ``writes`` is the writer's
    ``(converse, tenants)``, one thread of its own that walks its tenants as
    ``run_conversations`` does and starts none once ``seconds`` have passed
    (or the tenants have ended: ``ran_out`` closes the writer, never the
    readers). Either may be None. The window closes when the last answer is
    back and the last conversation started has ended; the writer's own time
    runs from ``t0`` to the end of its last conversation (``log.t1``)."""
    samples = log = thread = None
    with annotate("bench.window"):
        t0 = clock()
        if writes is not None:
            converse, tenants = writes
            log = ConversationLog()
            log.t0 = t0
            thread = threading.Thread(
                target=_converse, name="bench-writer", daemon=True,
                args=(log, converse, tenants, t0 + seconds, annotate, clock))
            thread.start()
        if reads is not None:
            submit, requests, due_rel, keep, drain_s = reads
            samples = Samples(len(requests))
            samples.t0 = t0
            _send(samples, submit, requests, due_rel + t0, keep, clock,
                  sleep).wait(timeout=drain_s)
            samples.t1 = clock()
        if thread is not None:
            thread.join()
    return samples, log
