"""Cross-request query batching for the fused retrieval kernel.

Serving millions of users means the unit of device work must be the BATCH,
not the request: every dispatch+readback costs the host one round trip
regardless of how many queries ride in it, and one arena scan serves a whole
batch for the HBM bytes of one query. The
``QueryScheduler`` here coalesces concurrent ``search_memories`` / ``chat``
retrievals — across callers, threads, and tenants — into padded mega-batches
the way Ragged Paged Attention coalesces ragged decode work on TPU:

- callers ``submit()`` a :class:`RetrievalRequest` and block on the returned
  handle (``result()`` / ``add_done_callback`` as on a
  ``concurrent.futures.Future``; since ISSUE 42 it is one lock and not a
  ``Future``: :class:`_CallerFuture`); the scheduler's workers own the
  device dispatch. One dispatch
  is in flight at a time — which keeps the donated state mutation
  single-writer — except that a FULL pending batch of pure reads is
  admitted over an in-flight batch of pure reads (at most two in flight,
  see :class:`QueryScheduler`);
- there is no flush timer: the dispatch in flight is the batching window.
  Pending requests are admitted, at most ``max_batch`` of them, the moment
  the worker is free, so a lone request on an idle scheduler ships at
  once and arrivals during a dispatch coalesce into the next — except
  that a free worker HOLDS a window that is not full while callers its
  last demux released are still expected back (ISSUE 32): callers that
  block in ``result()`` re-submit within a millisecond or two of
  their answers, and shipping the first of them alone costs the others a
  whole dispatch. The hold ends when the window fills, when everyone
  expected is back, or ``HOLD_FRACTION`` of one dispatch's time after
  that demux, whichever is first; everything it reads (how long a
  dispatch takes, who blocks, who comes back) the scheduler observes
  itself, so callers that do not wait are never held for and callers
  that do not return are held for once (see :class:`QueryScheduler`);
- the executor pads the popped batch to a linear granularity bucket before
  dispatch (``utils.batching.pad_to_bucket``), so the number of distinct
  jit specializations stays bounded no matter what batch sizes arrive;
- results demux back per request: the executor returns one
  :class:`RetrievalResult` per submitted request, in order, and per-request
  tenant ids ride INTO the kernel as a device column — tenant isolation is
  enforced by the same mask arithmetic as everywhere else, never by
  splitting batches.

The scheduler is deliberately generic over its ``executor`` callable:
``MemoryIndex`` plugs in the fused kernel (``search_fused_requests`` —
which routes to the exact dense, the quantized two-stage, or the IVF
coarse-prefilter program depending on ``int8_serving`` / a published IVF
build, and under a mesh to the DISTRIBUTED fused program,
``state.make_fused_sharded``, so int8, IVF, and pod modes all keep the
cross-request mega-batching, the one-dispatch turn, and zero-RTT
query-cache hits), while ``parallel.index.ShardedMemoryIndex`` plugs in
its own pod executor (``serve_requests``) — since ISSUE 5 the SAME full
chat-turn program as one distributed shard_map dispatch per mixed-tenant
mega-batch. Same coalescing, same policy, different device program.

Failure model (ISSUE 10) — a request future resolves with a RESULT or a
TYPED ERROR; it never blocks forever:

Every clause holds PER BATCH, also while two batches are in flight: each
has its own watchdog and its own futures, and the breaker and the degrade
rung see every batch once.

- an **executor exception** demuxes to every future of that batch (the
  PR 2 behavior) and counts a breaker failure; the other batch in flight
  is untouched;
- a **worker-thread death** anywhere outside the demuxed executor call
  fails the admitted batch's futures with :class:`WorkerCrashed` and the
  thread that died RESTARTS (``reliability.worker_restarts``) — pending
  requests stay queued and are served by the restarted worker, the other
  worker's batch is served;
- a **dispatch deadline** (``dispatch_timeout_s > 0``) arms a watchdog
  per dispatch: on expiry that batch's futures fail with
  :class:`DispatchTimeout` while the stuck dispatch is left to finish
  (its late results are discarded) and the breaker records the failure;
- **sustained pressure** opens the circuit breaker
  (``breaker_threshold`` consecutive failures/timeouts): for
  ``breaker_cooldown_s`` every batch is served DEGRADED — per-request
  ``nprobe``/``cap_take`` clamped to the cheap rung — then one
  half-open probe at full quality decides re-close vs re-open;
- **admission overload** (``shed_depth``/``shed_bytes`` exceeded) fails
  new submissions immediately with :class:`LoadShed`
  (``reliability.load_shed``) — the device never sees them;
- **memory-infeasible geometry** (ISSUE 11): with an ``admission_check``
  wired (the HBM planner's minimum-geometry probe), a submission whose
  geometry no split can fit fails immediately with the typed
  :class:`PlanInfeasible` — shed exactly like ``LoadShed``, before the
  queue, so a request that could never dispatch is never admitted. The
  executor raising ``PlanInfeasible`` mid-batch demuxes to the batch's
  futures like any other typed error (futures never hang either way).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from _thread import allocate_lock as _allocate_lock
from concurrent.futures import CancelledError
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field
from threading import get_ident as _get_ident
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import StepTraceAnnotation

from lazzaro_tpu.reliability import faults
from lazzaro_tpu.reliability.errors import (DispatchTimeout, LoadShed,
                                            PlanInfeasible, WorkerCrashed)
from lazzaro_tpu.reliability.watchdog import CircuitBreaker
from lazzaro_tpu.utils.hashing import tenant_home_group
from lazzaro_tpu.utils.telemetry import default_registry

logger = logging.getLogger("lazzaro_tpu.serve")


@dataclass
class RetrievalRequest:
    """One query's worth of the chat-turn retrieval sequence.

    ``boost=True`` asks the device to apply the access-salience boost to the
    returned top rows and the neighbor-salience boost to their CSR
    neighbors IN the same dispatch (the chat path); ``boost=False`` is a
    pure read (``search_memories``). ``gate_enabled`` switches the
    super-node top-1 gate evaluation on (the device skips boosts for
    queries whose gate fires — the host owns the hierarchy fast path)."""

    query: np.ndarray
    tenant: str
    k: int = 10
    gate_enabled: bool = False
    boost: bool = False
    super_filter: int = -1      # reserved; the fused kernel serves both tiers
    # Per-request knobs (ISSUE 7): ride into the fused kernel as int32
    # device columns, so one compiled kernel serves any mix. None =
    # the index's configured default (retrieval cap / build nprobe).
    cap_take: Optional[int] = None   # per-request boost/retrieval cap
    nprobe: Optional[int] = None     # per-request IVF probe width


@dataclass
class RetrievalResult:
    ids: List[str] = field(default_factory=list)
    scores: List[float] = field(default_factory=list)
    gate_id: Optional[str] = None
    gate_score: float = float("-inf")
    fast: bool = False          # device gate verdict (gate_enabled & > gate)
    boosted: bool = False       # device applied this query's boosts
    # Tiered memory (ISSUE 8): how many of this query's final top-k rows
    # were served from the host cold tier (0 on an all-hot turn — the
    # turn then cost exactly ONE dispatch).
    cold_hits: int = 0


Executor = Callable[[List[RetrievalRequest]], List[RetrievalResult]]


# The one clock of a request's stamps (ISSUE 41): the spans' own
# (``perf_counter``), in whole nanoseconds. Monotonic: wall time stays where
# a wall time is meant (the breaker's cooldown, a request's ``now``).
_clock_ns = time.perf_counter_ns


class _CallerFuture:
    """A request's handle: ONE lock, taken when the request is made and
    released by whoever resolves it (ISSUE 42). It is NOT a
    ``concurrent.futures.Future`` — no ``Condition``, no ``RLock``, no lock
    allocated per wait — because the 64 answers of a batch change hands
    under the one interpreter lock that paces a host-bound cell, and every
    bytecode of the hand-over is paid 64 times a cycle.

    What it keeps of the ``Future`` protocol, for the callers: ``result`` /
    ``exception`` (``timeout`` raises ``concurrent.futures.TimeoutError``,
    a cancelled handle ``CancelledError``, a failed one its typed error),
    ``done``, ``cancelled``, ``cancel`` (True on a pending handle, whose
    late answer is then discarded), ``running`` (never: the scheduler marks
    no request running, so a request can be cancelled until it is
    answered), ``add_done_callback`` (run with the handle on the thread
    that resolves it, in registration order; at once, on the registering
    thread, when it is resolved already; a raising callback is logged and
    the others still run) and free attributes. What it does not: there is
    no ``set_result`` / ``set_exception`` (only this module resolves a
    handle: ``_set_future`` / ``_fail_future``), and ``concurrent.futures
    .wait`` / ``as_completed``, which reach into a ``Future``'s condition,
    raise ``TypeError`` at once (nothing in the repo hands them one).

    Resolution is atomic, and first come first served: the worker's demux,
    a watchdog's ``Timer`` thread and a caller's ``cancel`` race as meant.
    Whoever deletes ``_open`` — one C call, so exactly one thread finds it —
    owns the handle: it stores the outcome, releases the lock and runs the
    callbacks; the others learn that they lost and change nothing. A
    pending ``result()`` is one blocking ``acquire`` in C and the
    ``release`` that lets a second reader through; a resolved one touches
    no lock.

    It knows its callers: the thread that submitted it, and the thread
    blocked on it. At demux the scheduler notes the threads that wait in
    ``result()`` — they are released by the answer and, in a closed loop,
    on their way back. A caller that took ``add_done_callback`` instead
    never counts.

    It also carries the request's last two stamps (ISSUE 41; class
    docstring of :class:`QueryScheduler`, "A request's stages"): the
    worker writes ``t_set`` just before it sets the answer, and the thread
    that waited in ``result()`` reads the clock when it runs again and
    leaves its wake-up in the scheduler's ledger (``_woke``: thread ident
    → its running ``(wake_ns, wakes, t_woke)``) — two dict operations and
    no lock on the caller's thread; the scheduler folds the entry at that
    thread's next submission. A done-callback that reads its answer runs
    on the worker, inside the demux: it was not woken, and counts nowhere."""

    waiter = 0              # ident of the thread blocked in result(), or 0
    t_set = 0               # when the worker set the answer; 0 = not stamped
    _outcome = None         # (result, error); error is CancelledError itself
                            # (the class) on a cancelled handle

    def __init__(self, caller: int, sched: "QueryScheduler"):
        self.caller = caller            # ident of the thread that submitted
        self.sched = sched
        self._open = True               # deleted by the one that resolves it
        self._callbacks = []
        lock = self._lock = _allocate_lock()
        lock.acquire()

    def _resolve(self, result, error) -> bool:
        """Claim → store → release → callbacks. False, and nothing
        changed, if somebody else resolved it first."""
        try:
            del self._open
        except AttributeError:
            return False
        self._outcome = (result, error)
        self._lock.release()
        callbacks = self._callbacks
        while callbacks:
            try:
                fn = callbacks.pop(0)
            except IndexError:      # its registrant took the last one back
                break
            self._call(fn)
        return True

    def _call(self, fn) -> None:
        try:
            fn(self)
        except Exception:       # noqa: BLE001 — as Future: logged
            logger.exception("exception calling callback for %r", self)

    def _wait(self, timeout):
        """Block until resolved: the outcome, or the futures' timeout."""
        if not self._lock.acquire(
                True, -1 if timeout is None else max(timeout, 0)):
            raise FutureTimeout()
        self._lock.release()            # the next reader's turn
        return self._outcome

    def result(self, timeout=None):
        ident = _get_ident()
        outcome = self._outcome
        if outcome is None:
            self.waiter = ident
            try:
                outcome = self._wait(timeout)
            finally:
                self.waiter = 0
        t_set = self.t_set
        if t_set:               # a served answer, read for the first time
            self.t_set = 0
            sched = self.sched
            if ident not in sched._worker_idents:
                now = _clock_ns()
                # pop, then store: whoever pops an entry owns it, so an
                # expiry on another thread in between neither loses nor
                # doubles a wake-up
                woke = sched._woke
                ns, n, _ = woke.pop(ident, None) or (0, 0, 0)
                woke[ident] = (ns + now - t_set, n + 1, now)
        res, err = outcome
        if err is None:
            return res
        try:
            raise err                   # the class, for a cancelled handle
        finally:
            self = outcome = err = None     # no cycle through the traceback

    def exception(self, timeout=None):
        err = (self._outcome or self._wait(timeout))[1]
        if err is CancelledError:
            raise CancelledError()
        return err

    def done(self) -> bool:
        return self._outcome is not None

    def cancelled(self) -> bool:
        outcome = self._outcome
        return outcome is not None and outcome[1] is CancelledError

    def running(self) -> bool:
        return False

    def cancel(self) -> bool:
        return self._resolve(None, CancelledError) or self.cancelled()

    def add_done_callback(self, fn) -> None:
        callbacks = self._callbacks
        callbacks.append(fn)
        if self._outcome is not None:
            # resolved before, or meanwhile: whoever takes ``fn`` out of
            # the list runs it — the resolver, or this thread, never both
            try:
                callbacks.remove(fn)
            except ValueError:
                return
            self._call(fn)

    @property
    def _condition(self):
        raise TypeError(
            "a request's handle is not a concurrent.futures.Future: wait "
            "on it with result() / exception() or add_done_callback()")


def _fail_future(fut: _CallerFuture, err: BaseException) -> None:
    """Set an exception, tolerating a handle that already resolved (the
    watchdog and the late dispatch race by design; a cancelled one stays
    cancelled)."""
    fut._resolve(None, err)


def _set_future(fut: _CallerFuture, res) -> None:
    # False: the watchdog already failed it — late result discarded
    fut._resolve(res, None)


_Item = Tuple[RetrievalRequest, _CallerFuture, int]     # (.., .., t_submit ns)

# The hold's bound, as a share of what a dispatch takes (the scheduler's own
# running estimate): nobody waits longer than this past the demux that
# released the callers being waited for. On the chip (fill.serve, PERF.md
# section 6, PR 32) the executor call takes 14.7 ms, the demux of 64 answers
# 1.1 ms, and the last of the 64 callers is back 3.6 ms after it (4.6 ms at
# the 95th percentile): half a dispatch, 7.4 ms, leaves that room, and a
# companion saves a whole dispatch, so the price is at most half of the gain.
HOLD_FRACTION = 0.5


@dataclass(eq=False)
class _Batch:
    """One admitted dispatch: its queue items, and what the admission
    decided about it."""
    items: List[_Item]
    overlapped: bool            # admitted while another was in flight
    seq: int                    # admission order: the profiler's step number
    held_s: float = 0.0         # how long a free worker held its window open

    @property
    def reqs(self) -> List[RetrievalRequest]:
        return [req for req, _, _ in self.items]


# _next_batch_locked: come back parked / come back through the hold
_PARK, _HOLD = object(), object()


class QueryScheduler:
    """Coalesce concurrent retrievals into dense device batches.

    Daemon worker threads pop pending requests and run ``executor`` on
    them; callers block on per-request futures. ``close()`` drains
    pending work before returning. The workers are crash-restarting and
    every failure path resolves futures with a typed error (see the
    module docstring's failure model).

    The admission rule, the whole of it: a batch admits when NOTHING is
    in flight — requests enter the NEXT dispatch the moment the worker is
    free, so the in-flight dispatch is the batching window: a lone
    request on an idle scheduler ships immediately (latency = dispatch
    time, there is no timer), and arrivals during a dispatch coalesce
    into the next one — unless (ISSUE 32) the window is NOT full and
    callers released by a recent demux are still expected back: then the
    free worker holds the window open (below) — or (ISSUE 30) when exactly
    ONE dispatch is in flight and the pending queue holds a FULL batch
    (what ``_select_locked`` picks has ``max_batch`` requests) and
    ``overlap_check`` says yes to both the batch in flight and the one to
    admit. A window that is full cannot grow: keeping it closed until the
    other dispatch returns only idles the device, so its host path (pack,
    stage, launch) runs while the other batch's pass is on the device. At
    most two dispatches are ever in flight — one running, one queued
    behind it: a third could not start sooner and would only take
    requests out of the window early. A window one short of full waits as
    before, so a closed loop of callers is never cut into more groups
    than it has today. ``overlap_check`` belongs to the executor's owner
    (``MemoryIndex.reads_may_overlap``: pure reads that share no serving
    state); without one the scheduler has ONE worker and never overlaps.

    The hold (ISSUE 32). Callers that block in ``result()`` come back
    together: a demux of N answers is followed, a millisecond or two
    later, by N submissions. Admitting the first s of them the instant
    they arrive cuts the loop into (s, N - s), and every such split is a
    stable cycle — when the N - s return, the s are in flight or already
    waiting, and nothing about the age of the oldest pending request or
    the time since the last arrival tells the two apart — so half of the
    dispatches carry a fraction of the callers. What does tell them apart
    is who was released and who is back, and that the scheduler sees:

    - T, what a dispatch takes: the executor call's wall time, an estimate
      that falls to a faster sample at once and rises by an eighth of a
      slower one's excess (a compile or a stall never sets the bound);
      the bound is ``HOLD_FRACTION`` * T past the last demux that released
      a waiting caller;
    - who waits: ``submit_many`` hands out :class:`_CallerFuture`, and at
      demux the threads blocked in ``result()`` on the batch's futures are
      the callers it releases — unless a thread has submitted other
      requests that are still pending or in flight (a
      ``search_memories_batch`` larger than a batch): that caller is not
      on its way back, it is waiting for the rest; callbacks never count;
    - who comes back: a released caller whose next submission arrives
      inside the bound is a returner from then on — learned whether or not
      a hold was in force, so there is no hold until returns have been
      seen — and one that is still out when the bound ends is not, until
      it returns inside a bound again: callers that have left, or that
      think for longer than the bound, are held for once. It is kept per
      caller and not as one share r of a batch, because a fleet is a mix:
      the prompt callers of a batch are waited for and the thinkers beside
      them are not (one r sits between the two, so it holds every window
      the thinkers' answers open, to the bound, and cuts the prompt
      callers' window short at r of them).

    Expected back are the returners among the callers the recent demuxes
    released that have not submitted since; nobody once the bound has
    passed. While somebody is expected, nothing is in flight and the
    window is not full, ``_try_admit_locked`` admits nothing and the free
    worker waits on the condition (``_hold_locked``: a timed wait; the
    submit path wakes it only when the window fills or the last expected
    caller is back) and then admits as ever. An EMPTY window is held the
    same way from the demux on when two or more callers are expected, so
    that the first one back does not ship alone. From any split the loop
    heals at the next demux: the released callers are expected, so the
    pending ones wait for them. A lone request on an idle scheduler meets
    no hold (nobody was released, or its own return is the one arrival
    that was expected, and one caller has nobody to wait for), and a
    fleet of more callers than a batch holds meets none either (two
    batches' worth keep one dispatch in flight and a full window behind
    it; where nothing is in flight for a moment, the window ships as it
    is and the others make the next: holding it would only idle the
    device); ``close()`` and ``flush()`` end a hold at once; a held
    request is a pending request like any other.

    A request's stages (ISSUE 41). Every served request is stamped on ONE
    monotonic clock (``_clock_ns``, the spans' ``perf_counter``) where it
    changes hands: ``t_submit`` (``submit_many``, before the lock),
    ``t_flush`` (the start of ``_account``), ``t_exec0`` / ``t_exec1``
    (around the executor call), ``t_set`` (just before its answer is set
    in the demux loop), ``t_woke`` (in ``_CallerFuture.result``, when the
    thread that waited runs again) and ``t_back`` (that thread's next
    ``submit_many``). What changes hands is the request's handle
    (:class:`_CallerFuture`; ISSUE 42): one lock, taken in ``submit_many``
    and released by the demux loop right after ``t_set`` is written — a
    waiting caller is one blocking ``acquire`` in C, woken by that release,
    with no ``Condition``, no ``RLock`` and no lock allocated per wait in
    between; the first thread to claim a handle (the worker, a watchdog, a
    ``cancel``) resolves it and the others change nothing. The stamps did
    not move. The differences are summed over ALL served requests
    into unlabelled counters, in microseconds, bumped once a served batch
    beside ``serve.requests``: ``serve.queue_wait_us`` (flush − submit),
    ``serve.account_us`` (exec0 − flush), ``serve.exec_us`` (exec1 −
    exec0; the first three are the batch's, times its requests),
    ``serve.demux_wait_us`` (set − exec1: the bookkeeping after the call
    and the callbacks of the requests ahead in the batch),
    ``serve.wake_us`` / ``serve.wakes`` (woke − set, and their number:
    only threads that waited in ``result()``; a callback counts nowhere)
    and ``serve.return_us`` / ``serve.returns`` (back − woke: the caller's
    own time, counted only while the caller's bound of ``_watched`` /
    ``_expected`` still runs). So for every request served through
    ``result()``, woke − submit = queue + account + exec + demux_wait +
    wake, and for a callback request set − submit is the same without the
    wake: what a user's own clock reads beyond that sum, no layer of the
    program explains. The last two pairs begin on one thread and end on
    another, so no span can hold them: the caller's thread leaves its
    wake-up in a ledger (``_woke``: its ident → ``(wake_ns, wakes,
    t_woke)``; two dict operations, no lock), the same thread's next
    submission folds it under the lock it takes anyway (an expiry folds
    the wake-up of a caller that did not come back, ``close()`` what is
    left), and the worker bumps what was folded with the next batch's
    counters. A failed or timed-out batch counts in no stage; with the
    registry switched off nothing is stamped or stored.

    Per-tenant admission control (``tenant_max_inflight``) caps how many
    of one tenant's requests enter a single dispatch, walking the queue
    oldest-first so over-cap requests keep their place for the next batch
    — one flooding tenant cannot monopolize the device.
    """

    def __init__(self, executor: Executor, max_batch: int = 64,
                 name: str = "lz-query-scheduler", telemetry=None,
                 tenant_max_inflight: int = 0,
                 dispatch_timeout_s: float = 0.0,
                 breaker_threshold: int = 5,
                 breaker_cooldown_s: float = 5.0,
                 shed_depth: int = 0, shed_bytes: int = 0,
                 degrade_cap_take: int = 1, degrade_nprobe: int = 1,
                 admission_check: Optional[Callable] = None,
                 overlap_check: Optional[Callable] = None):
        self._executor = executor
        # The executor owner's word on which batches may run while another
        # is in flight (ISSUE 30): called with a batch's requests, under
        # the scheduler's lock, only when a full batch waits behind one
        # dispatch. None = never overlap.
        self.overlap_check = overlap_check
        # Memory-safe admission (ISSUE 11): an optional callable invoked
        # with the submitted request group BEFORE it queues; raising
        # PlanInfeasible fails the group's futures typed right here —
        # shed like LoadShed, the device never sees them.
        self.admission_check = admission_check
        # Serving telemetry (ISSUE 6): every request records its
        # enqueue→flush queue wait (per-tenant label), every flushed batch
        # one batch-size sample — N coalesced requests therefore yield N
        # queue-wait samples and the executor's ONE dispatch sample.
        self.telemetry = telemetry if telemetry is not None \
            else default_registry()
        self.max_batch = max(1, int(max_batch))
        self.tenant_max_inflight = max(0, int(tenant_max_inflight))
        # Reliability knobs (ISSUE 10)
        self.dispatch_timeout_s = max(0.0, float(dispatch_timeout_s))
        self.shed_depth = max(0, int(shed_depth))
        self.shed_bytes = max(0, int(shed_bytes))
        self.degrade_cap_take = max(1, int(degrade_cap_take))
        self.degrade_nprobe = max(1, int(degrade_nprobe))
        self.breaker: Optional[CircuitBreaker] = (
            CircuitBreaker(breaker_threshold, breaker_cooldown_s,
                           telemetry=self.telemetry, name=name)
            if breaker_threshold > 0 else None)
        self._cond = threading.Condition()
        # where a worker waits that has nothing to admit while a dispatch is
        # in flight: woken only when a full batch waits behind ONE dispatch
        # (and on close), so a submit costs it nothing otherwise
        self._parked = threading.Condition(self._cond)
        # the one free worker is in its idle wait / in a hold: the other
        # parks, and a submit wakes a holding worker only to end the hold
        self._idle_held = False
        self._holding = False
        # what the hold reads (ISSUE 32; class docstring), all observed
        self._dispatch_s = 0.0               # T: what a dispatch takes
        # callers (thread idents) whose last release was followed by their
        # next request inside the bound: the ones worth holding a window for
        self._returners: set = set()
        # callers the recent demuxes released that are not back yet -> when
        # each one's bound ends (monotonic; oldest first): the returners
        # among them (expected back), and the others (watched)
        self._expected: Dict[int, float] = {}
        self._watched: Dict[int, float] = {}
        self._held_s = 0.0                   # held since the last admission
        # a request's way back (ISSUE 41; class docstring, "A request's
        # stages"): the ledger the callers' threads write their wake-ups
        # into, and what submissions (and expiries) have folded out of it
        # since the last served batch — [wake ns, wakes, return ns, returns]
        self._woke: Dict[int, Tuple[int, int, int]] = {}
        self._way_back = [0, 0, 0, 0]
        self._worker_idents: set = set()     # their reads are no wake-ups
        self._flushing = 0                   # flush() callers: they end a hold
        self._pending: List[_Item] = []
        self._pending_bytes = 0
        self._inflight_batches: List[_Batch] = []    # never more than two
        self._dispatch_seq = 0
        self._closed = False
        self.batches_flushed = 0
        self.requests_served = 0
        self.requests_deferred = 0           # tenant-cap admission defers
        self.requests_shed = 0               # admission-control rejections
        self.worker_restarts = 0
        self.watchdog_timeouts = 0
        self.batch_sizes: List[int] = []     # observability (bench reads it)
        self._name = name
        # the second worker exists for the overlapped batch alone
        self._workers: List[Optional[threading.Thread]] = \
            [None] * (2 if overlap_check is not None else 1)
        with self._cond:
            self._ensure_workers_locked()

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def _inflight(self) -> int:
        return len(self._inflight_batches)

    # ------------------------------------------------------------- submit
    def submit(self, request: RetrievalRequest) -> _CallerFuture:
        return self.submit_many([request])[0]

    def submit_many(self, requests: Sequence[RetrievalRequest]
                    ) -> List[_CallerFuture]:
        """Enqueue a group atomically (a ``search_memories_batch`` fleet
        stays contiguous, so it lands in as few flushes as possible).
        Under admission overload the whole group's futures fail
        immediately with :class:`LoadShed` — the futures API is uniform,
        so callers see the typed error at ``.result()`` like any other
        failure."""
        caller = threading.get_ident()
        futures = [_CallerFuture(caller, self) for _ in requests]
        now = _clock_ns()
        if self.admission_check is not None and requests:
            try:
                self.admission_check(list(requests))
            except PlanInfeasible as err:
                # memory-infeasible geometry: shed typed, like LoadShed —
                # the futures resolve immediately, the queue never grows
                self.requests_shed += len(requests)
                self.telemetry.bump("plan.infeasible_shed", len(requests))
                for fut in futures:
                    _fail_future(fut, err)
                return futures
        nbytes = (sum(np.asarray(r.query).nbytes for r in requests)
                  if self.shed_bytes else 0)
        with self._cond:
            if self._closed:
                raise RuntimeError("QueryScheduler is closed")
            over_depth = (self.shed_depth and
                          len(self._pending) + len(requests)
                          > self.shed_depth)
            over_bytes = (self.shed_bytes and
                          self._pending_bytes + nbytes > self.shed_bytes)
            if over_depth or over_bytes:
                self.requests_shed += len(requests)
                self.telemetry.bump("reliability.load_shed", len(requests))
                reason = "depth" if over_depth else "bytes"
                err = LoadShed(
                    f"admission queue over {reason} budget "
                    f"({len(self._pending)} pending); retry with backoff")
                for fut in futures:
                    _fail_future(fut, err)
                return futures
            for req, fut in zip(requests, futures):
                self._pending.append((req, fut, now))
            self._pending_bytes += nbytes
            self._ensure_workers_locked()
            self._fold_locked(caller, now, self._note_return_locked(caller))
            # a worker that holds its window open is woken only when the
            # hold is over: a wake-up per submission would hand it the
            # interpreter after the first caller is back
            if not (self._holding and self._hold_left_locked() > 0):
                self._cond.notify()
            if (self._inflight == 1
                    and len(self._pending) >= self.max_batch):
                self._parked.notify()
        return futures

    def _ensure_workers_locked(self) -> None:
        """Spawn the workers, and respawn one that is gone (belt-and-
        braces: the restart loop already survives crashes, but a dead
        thread must never let a future sit unserved)."""
        if self._closed:
            return
        for i, worker in enumerate(self._workers):
            if worker is None or not worker.is_alive():
                self._workers[i] = threading.Thread(
                    target=self._run, daemon=True,
                    name=self._name + ("-%d" % (i + 1) if i else ""))
                self._workers[i].start()

    # ------------------------------------------------------------- worker
    def _run(self) -> None:
        """Crash-restarting wrapper: a worker death fails the admitted
        batch's futures (inside ``_serve_loop``) and restarts the loop —
        pending requests stay queued and are served after the restart.
        Only a clean close exits."""
        self._worker_idents.add(threading.get_ident())
        while True:
            try:
                self._serve_loop()
                return
            except BaseException:       # noqa: BLE001 — must not die silent
                logger.exception("query-scheduler worker crashed; "
                                 "restarting")
                self.worker_restarts += 1
                self.telemetry.bump("reliability.worker_restarts",
                                    labels={"actor": "query_scheduler"})
                with self._cond:
                    if self._closed and not self._pending:
                        return
                time.sleep(0.005)       # never spin on a persistent fault

    def _serve_loop(self) -> None:
        while True:
            # a hold comes first, and is a span of its own: what the worker
            # waits out for callers on their way back is not its idle time
            with self._cond:
                self._hold_locked()
            # the wait of a worker that would admit whatever came, up to
            # the batch being admitted: the window less these spans is the
            # time a worker was busy. A worker with nothing to admit while
            # a dispatch is in flight parks OUTSIDE the span.
            with self.telemetry.span("sched.idle"), self._cond:
                batch = self._next_batch_locked(parked=False)
            if batch is _HOLD:
                continue
            if batch is _PARK:
                with self._cond:
                    batch = self._next_batch_locked(parked=True)
            if batch is None:
                return
            try:
                # Fault point "scheduler.worker" (ISSUE 10): a raise here
                # models the worker dying OUTSIDE the demuxed executor
                # call — the pre-ISSUE-10 scheduler would strand these
                # futures forever.
                try:
                    faults.fire("scheduler.worker", batch=len(batch.items))
                    self._execute(batch)
                except BaseException as e:
                    err = WorkerCrashed(
                        f"query-scheduler worker died mid-batch: {e!r}")
                    for _, fut, _ in batch.items:
                        _fail_future(fut, err)
                    raise
            finally:
                with self._cond:
                    self._inflight_batches.remove(batch)
                    self._cond.notify_all()
                    if self._closed:
                        self._parked.notify_all()

    def _next_batch_locked(self, parked: bool):
        """Wait (caller holds the lock) until the admission rule lets this
        worker take a batch; None on a clean close. A worker in its idle
        wait (``parked=False``) that finds a dispatch in flight and
        nothing it may admit over it — or the other worker free already —
        returns ``_PARK`` and comes back parked: it then sleeps until a
        full batch waits behind one dispatch. The worker that finishes a
        dispatch always looks at the queue itself, so a parked one is
        never needed for anything else. One that finds what is pending
        refused by the hold returns ``_HOLD`` and comes back through
        ``_hold_locked``."""
        while True:
            batch = self._try_admit_locked()
            if batch is not None:
                return batch
            if self._closed and not self._pending:
                return None
            if parked:
                self._parked.wait()
                continue
            if self._inflight or self._idle_held or self._holding:
                return _PARK
            if self._pending:       # nothing in flight: only the hold refuses
                return _HOLD
            self._idle_held = True
            try:
                self._cond.wait()
            finally:
                self._idle_held = False

    def _try_admit_locked(self) -> Optional["_Batch"]:
        """The admission rule (class docstring; caller holds the lock):
        the batch admitted now, or None."""
        if not self._pending:
            return None
        overlapped = False
        if self._inflight == 0:
            # nothing in flight IS the flush signal: pending work admits
            # immediately, a lone request never waits on a timer — unless
            # the window can still grow by callers known to be coming back
            if self._hold_left_locked() > 0:
                return None
            picked = self._select_locked()
        else:
            # one dispatch in flight: only a window that cannot grow, and
            # only if the executor's owner lets both batches run together
            if (self._inflight != 1 or self.overlap_check is None
                    or len(self._pending) < self.max_batch):
                return None
            picked = self._select_locked()
            if (len(picked[0]) < self.max_batch
                    or not self._may_overlap(self._inflight_batches[0].reqs)
                    or not self._may_overlap(
                        [req for req, _, _ in picked[0]])):
                return None
            overlapped = True
        return self._admit_locked(*picked, overlapped)

    def _hold_left_locked(self) -> float:
        """The hold, as a predicate (class docstring, "The hold"; caller
        holds the lock; it changes nothing but to forget callers whose
        bound is over): for how many seconds more a free worker keeps its
        window open, 0.0 = admit. It stands while nothing is in flight and
        somebody is still expected back (so the bound past the last demux
        that released such a caller still runs) whom the hold can bring
        together with another request — an empty window is held for two
        callers or more, so the first one back does not ship alone, but
        not for ONE, who has nobody to wait for — and all of whom the
        window ``_select_locked`` would pick still has room for: where it
        has not (the window is full, or more callers wait than a batch
        holds) the rest make the next window whatever this one does, and
        waiting would only idle the device."""
        if self._inflight or self._closed or self._flushing:
            return 0.0
        self._expire_locked()
        expected = len(self._expected)
        if not expected:
            return 0.0
        window = len(self._select_locked()[0])
        if (window + expected > self.max_batch
                or expected < max(1, 2 - window)):
            return 0.0
        return next(reversed(self._expected.values())) - time.monotonic()

    def _hold_locked(self) -> None:
        """The hold itself (caller holds the lock): while it stands, wait
        on the condition — timed, so the bound ends it; a submission wakes
        this worker only when it ends it sooner (``submit_many``), as
        ``close()`` and ``flush()`` do. Opens its span, and counts as held
        time, only if there is something to wait for."""
        left = self._hold_left_locked()
        if left <= 0 or self._idle_held or self._holding:
            return
        began = time.perf_counter()
        self._holding = True
        try:
            with self.telemetry.span("sched.hold"):
                while left > 0:
                    self._cond.wait(left)
                    left = self._hold_left_locked()
        finally:
            self._holding = False
            self._held_s += time.perf_counter() - began

    def _note_return_locked(self, caller: int) -> bool:
        """A submission by the thread ``caller``: if a recent demux
        released it and its bound still runs, it is back (True), and known
        to come back."""
        self._expire_locked()
        back = self._expected.pop(caller, None) is not None
        if self._watched.pop(caller, None) is not None:
            self._returners.add(caller)
            back = True
        return back

    def _fold_locked(self, caller: int, now: int = 0,
                     back: bool = False) -> None:
        """Take the thread ``caller``'s wake-ups out of the ledger: their
        time and number and, if it is ``back`` inside its bound at ``now``,
        its return — from its last wake-up to this submission."""
        entry = self._woke.pop(caller, None)
        if entry is None:
            return
        wake_ns, wakes, t_woke = entry
        tally = self._way_back
        tally[0] += wake_ns
        tally[1] += wakes
        if back:
            tally[2] += now - t_woke
            tally[3] += 1

    def _expire_locked(self) -> None:
        """A caller still out when its bound has run out did not come
        back inside it: no window is held for that caller again until it
        has. (Each caller has its own bound's end, so a scheduler that is
        never quiet — a demux every few milliseconds — forgets the callers
        that left all the same.)"""
        now = time.monotonic()
        for out in (self._expected, self._watched):
            while out:
                caller = next(iter(out))
                if out[caller] > now:
                    break
                del out[caller]
                self._returners.discard(caller)
                self._fold_locked(caller)   # it woke; it is not on its way back

    def _note_demux_locked(self, batch: "_Batch", took_s: float) -> None:
        """A dispatch came back and its answers are about to be handed
        out: ``took_s`` is T's next sample, and the threads blocked on the
        batch's futures are released now — those that are not waiting for
        more: a thread with requests of its own still pending or in the
        other dispatch goes on waiting, it is not on its way back."""
        t = self._dispatch_s
        self._dispatch_s = (took_s if not t or took_s < t
                            else t + (min(took_s, 2 * t) - t) / 8)
        released = {fut.waiter for _, fut, _ in batch.items} - {0}
        if not released:
            return
        released -= {fut.caller for _, fut, _ in self._pending}
        for other in self._inflight_batches:
            if other is not batch:
                released -= {fut.caller for _, fut, _ in other.items}
        until = time.monotonic() + HOLD_FRACTION * self._dispatch_s
        for caller in released:
            out = (self._expected if caller in self._returners
                   else self._watched)
            out.pop(caller, None)       # released again: last in line
            out[caller] = until

    def _may_overlap(self, reqs) -> bool:
        try:
            return bool(self.overlap_check(reqs))
        except Exception:       # noqa: BLE001 — a broken predicate says no
            logger.exception("overlap_check raised; serving serially")
            return False

    def _select_locked(self):
        """What the next dispatch would take from the pending queue, and
        nothing changed yet (caller holds the lock): (batch, queue left
        behind, requests deferred by the tenant cap). Oldest-first; at
        most ``max_batch``; with a tenant cap, at most
        ``tenant_max_inflight`` requests per tenant — over-cap requests
        KEEP their queue position (fairness: the deferred oldest request
        is first in line next dispatch)."""
        limit = self.max_batch
        cap = self.tenant_max_inflight
        if not cap:
            return self._pending[:limit], self._pending[limit:], 0
        batch: List[_Item] = []
        kept: List[_Item] = []
        counts: dict = {}
        deferred = 0
        for item in self._pending:
            tenant = item[0].tenant
            if len(batch) < limit and counts.get(tenant, 0) < cap:
                batch.append(item)
                counts[tenant] = counts.get(tenant, 0) + 1
            else:
                kept.append(item)
                if len(batch) < limit:
                    deferred += 1        # capped out, not batch-full
        return batch, kept, deferred

    def _admit_locked(self, items, kept, deferred, overlapped) -> _Batch:
        """Pop what ``_select_locked`` picked: it is in flight now."""
        self._pending = kept
        self._note_admitted_locked(items)
        if deferred:
            self.requests_deferred += deferred
            self.telemetry.bump("serve.admission_deferred", deferred)
        self._dispatch_seq += 1
        batch = _Batch(items, overlapped, self._dispatch_seq, self._held_s)
        self._held_s = 0.0
        self._inflight_batches.append(batch)
        return batch

    def _note_admitted_locked(self, batch) -> None:
        if self.shed_bytes and batch:
            self._pending_bytes = max(
                0, self._pending_bytes
                - sum(np.asarray(req.query).nbytes for req, _, _ in batch))

    def _degrade(self, req: RetrievalRequest) -> RetrievalRequest:
        """The breaker's cheap rung: clamp the per-request knobs the
        serving kernels read as device data (fewer IVF probes, smaller
        boost/retrieval cap) — same k results, less device work. The
        request object is copied, never mutated (the caller may retry it
        at full quality)."""
        cap = (self.degrade_cap_take if req.cap_take is None
               else min(req.cap_take, self.degrade_cap_take))
        npr = (self.degrade_nprobe if req.nprobe is None
               else min(req.nprobe, self.degrade_nprobe))
        return dataclasses.replace(req, cap_take=cap, nprobe=npr)

    def _account(self, batch: _Batch):
        """(requests to dispatch, ``t_flush`` and the requests' summed queue
        wait up to it in nanoseconds, the armed watchdog timer or None, its
        timed-out flag)."""
        items = batch.items
        reqs = batch.reqs
        t_flush = _clock_ns()
        waited_ns = 0
        for req, _, t_submit in items:
            waited_ns += t_flush - t_submit
            # a ring of ``window`` samples PER TENANT series (256 tenants,
            # then "~other"): a series that overflows keeps its newest
            # samples only, so percentiles of this timer lean to the
            # window's end — serve.queue_wait_us is the whole sum
            self.telemetry.record("serve.queue_wait_ms",
                                  (t_flush - t_submit) / 1e6,
                                  labels={"tenant": req.tenant})
        if self.breaker is not None and self.breaker.degraded():
            reqs = [self._degrade(r) for r in reqs]
            self.telemetry.bump("reliability.degraded_requests", len(reqs))
        timer = None
        timed_out = threading.Event()
        if self.dispatch_timeout_s > 0:
            def _deadline():
                timed_out.set()
                self.watchdog_timeouts += 1
                self.telemetry.bump("reliability.watchdog_timeouts")
                if self.breaker is not None:
                    self.breaker.record_failure()
                err = DispatchTimeout(
                    f"dispatch exceeded the {self.dispatch_timeout_s:.3f}s "
                    f"watchdog deadline (batch of {len(items)})")
                for _, fut, _ in items:
                    _fail_future(fut, err)
            timer = threading.Timer(self.dispatch_timeout_s, _deadline)
            timer.daemon = True
            timer.start()
        return reqs, t_flush, waited_ns, timer, timed_out

    def _execute(self, batch: _Batch) -> None:
        items = batch.items
        # the worker's own bookkeeping before the dispatch: one labelled
        # queue-wait sample per request, the breaker, the watchdog
        with self.telemetry.span("sched.account"):
            reqs, t_flush, waited_ns, timer, timed_out = self._account(batch)
        try:
            # one mega-batch == one profiler step, so TPU captures line up
            # with the host spans batch-for-batch. ONE annotation around
            # the whole executor call, on the thread that runs it: every
            # such span contains at least its own pass over the arena.
            with StepTraceAnnotation("lz.serve.batch", step_num=batch.seq):
                t_exec0 = _clock_ns()
                results = self._executor(reqs)
                t_exec1 = _clock_ns()
        except Exception as e:                      # noqa: BLE001 — demuxed
            if timer is not None:
                timer.cancel()
            if self.breaker is not None:
                self.breaker.record_failure()
            for _, fut, _ in items:
                _fail_future(fut, e)
            return
        if timer is not None:
            timer.cancel()
        if timed_out.is_set():
            # The dispatch came back AFTER the watchdog failed its
            # futures: discard the late results (the callers have moved
            # on) but leave state/telemetry consistent.
            return
        if self.breaker is not None:
            self.breaker.record_success()
        with self._cond:        # two workers may get here together
            self.batches_flushed += 1
            self.requests_served += len(items)
            self.batch_sizes.append(len(items))
            if len(self.batch_sizes) > 1024:
                del self.batch_sizes[:512]
            self._note_demux_locked(batch, (t_exec1 - t_exec0) / 1e9)
            way_back, self._way_back = self._way_back, [0, 0, 0, 0]
        n = len(items)
        self.telemetry.bump("serve.requests", n)
        self.telemetry.bump("serve.batches")
        # a request's stages, each summed over the served requests, so it
        # divides by serve.requests (class docstring, "A request's stages")
        self.telemetry.bump("serve.queue_wait_us", waited_ns // 1000)
        self.telemetry.bump("serve.account_us", n * (t_exec0 - t_flush) // 1000)
        self.telemetry.bump("serve.exec_us", n * (t_exec1 - t_exec0) // 1000)
        # the way back of the requests served BEFORE this batch, as the
        # callers' submissions folded it since the last one
        self._bump_way_back(way_back)
        if len(items) == 1:
            # a whole dispatch (a full arena pass) for one answer
            self.telemetry.bump("serve.lone_batches")
        # a batch admitted while another was in flight (a bump of 0 is
        # dropped: a run that never overlaps has no such entry)
        self.telemetry.bump("serve.overlapped_batches", int(batch.overlapped))
        # a batch admitted after a free worker held its window open for
        # callers on their way back, and for how long (the same: no hold
        # anywhere, no entry)
        self.telemetry.bump("serve.held_batches", int(batch.held_s > 0))
        self.telemetry.bump("serve.hold_us", int(batch.held_s * 1e6))
        self.telemetry.record("serve.batch_requests", len(items))
        # the callers' done-callbacks run here, on the worker thread: an
        # answer waits for the callbacks of those ahead of it in the batch
        stamped = self.telemetry.enabled
        demux_wait_ns = 0
        with self.telemetry.span("sched.demux"):
            for (_, fut, _), res in zip(items, results):
                if stamped:
                    fut.t_set = t_set = _clock_ns()
                    demux_wait_ns += t_set - t_exec1
                _set_future(fut, res)
        self.telemetry.bump("serve.demux_wait_us", demux_wait_ns // 1000)

    def _bump_way_back(self, tally) -> None:
        wake_ns, wakes, return_ns, returns = tally
        self.telemetry.bump("serve.wake_us", wake_ns // 1000)
        self.telemetry.bump("serve.wakes", wakes)
        self.telemetry.bump("serve.return_us", return_ns // 1000)
        self.telemetry.bump("serve.returns", returns)

    def load(self) -> int:
        """Instantaneous queue depth + in-flight dispatches — the
        least-loaded routing signal :class:`ReplicaRouter` reads."""
        with self._cond:
            return len(self._pending) + self._inflight

    # ----------------------------------------------------------- lifecycle
    def flush(self, timeout: float = 30.0) -> None:
        """Block until everything submitted so far has been executed."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._flushing += 1         # ends a hold, and lets none begin
            self._cond.notify_all()
            try:
                while self._pending or self._inflight:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError("QueryScheduler.flush timed out")
                    self._cond.wait(min(remaining, 0.05))
            finally:
                self._flushing -= 1

    def close(self) -> None:
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
            self._parked.notify_all()
            workers = [w for w in self._workers if w is not None]
        for worker in workers:
            worker.join(timeout=30.0)
        # the wake-ups no later submission folded: the last batches' callers
        with self._cond:
            for caller in list(self._woke):
                self._fold_locked(caller)
            way_back, self._way_back = self._way_back, [0, 0, 0, 0]
        self._bump_way_back(way_back)

    def stats(self) -> dict:
        with self._cond:
            sizes = list(self.batch_sizes)
            return {
                "batches_flushed": self.batches_flushed,
                "requests_served": self.requests_served,
                "requests_deferred": self.requests_deferred,
                "requests_shed": self.requests_shed,
                "worker_restarts": self.worker_restarts,
                "watchdog_timeouts": self.watchdog_timeouts,
                "breaker": (self.breaker.stats()
                            if self.breaker is not None else None),
                "pending": len(self._pending),
                "mean_batch": (round(float(np.mean(sizes)), 2)
                               if sizes else None),
                "max_batch_seen": max(sizes) if sizes else None,
            }


class ReplicaRouter:
    """Group-aware routing in front of per-group :class:`QueryScheduler`s
    (replica-group serving, ISSUE 18).

    One scheduler per replica group — each with its OWN worker thread,
    admission queue, and circuit breaker, so a sick group degrades (or
    sheds) alone while the others keep serving at full quality — and a
    routing policy in front that assigns every submitted request to
    exactly one group:

    - **tenant-affine**: tenants named in ``affine_tenants`` (the
      placement layer registers every overlay tenant it ingests) always
      route to their stable home group (``utils.hashing``'s CRC32-based
      ``tenant_home_group``, the same assignment the write side uses) —
      their private rows exist ONLY on that home group, and the pinning
      also buys read-your-writes for shared-tier tenants that opt in;
    - **least-loaded**: everything else routes to the group whose
      scheduler reports the smallest queue depth + in-flight count
      (:meth:`QueryScheduler.load`), ties broken round-robin so an idle
      fleet still spreads.

    Because routing happens at submission, each group's scheduler
    coalesces ITS stream into mega-batches independently — every flushed
    mega-batch lands on exactly one group as ONE distributed dispatch +
    ONE packed readback, which is what makes aggregate QPS scale with
    group count instead of every dispatch sweeping every chip."""

    def __init__(self, executors: Sequence[Executor],
                 affine_tenants: Optional[set] = None,
                 telemetry=None, name: str = "lz-replica-router", **sched_kw):
        if not executors:
            raise ValueError("ReplicaRouter needs at least one executor")
        self.telemetry = telemetry if telemetry is not None \
            else default_registry()
        # a set passed in is kept BY REFERENCE: the placement layer shares
        # its live overlay-tenant set, so tenants that turn overlay after
        # router construction pin immediately
        self.affine_tenants = (affine_tenants if isinstance(affine_tenants,
                                                            set)
                               else set(affine_tenants or ()))
        self.schedulers = [
            QueryScheduler(ex, name=f"{name}-g{g}",
                           telemetry=self.telemetry, **sched_kw)
            for g, ex in enumerate(executors)]
        self._rr = 0
        self._rr_lock = threading.Lock()

    @property
    def n_groups(self) -> int:
        return len(self.schedulers)

    def pin_tenant(self, tenant: str) -> int:
        """Register a tenant as overlay/affine; returns its home group."""
        self.affine_tenants.add(tenant)
        return self.group_for_tenant(tenant)

    def group_for_tenant(self, tenant: str) -> int:
        """The tenant's home group (process-stable hash — the same
        assignment the write-side placement uses, so affine reads land
        where the tenant's overlay rows live, across restarts too)."""
        return tenant_home_group(tenant, len(self.schedulers))

    def route(self, request: RetrievalRequest) -> int:
        if request.tenant in self.affine_tenants:
            g = self.group_for_tenant(request.tenant)
            self.telemetry.bump("serve.replica_affine_routed",
                                labels={"group": str(g)})
            return g
        loads = [s.load() for s in self.schedulers]
        lo = min(loads)
        candidates = [g for g, v in enumerate(loads) if v == lo]
        with self._rr_lock:
            g = candidates[self._rr % len(candidates)]
            self._rr += 1
        self.telemetry.bump("serve.replica_routed",
                            labels={"group": str(g)})
        return g

    def submit(self, request: RetrievalRequest) -> _CallerFuture:
        return self.schedulers[self.route(request)].submit(request)

    def submit_many(self, requests: Sequence[RetrievalRequest]
                    ) -> List[_CallerFuture]:
        """Route a group of requests; each sub-group stays contiguous on
        its scheduler (the atomic-group property per group)."""
        by_group: Dict[int, List[int]] = {}
        for i, req in enumerate(requests):
            by_group.setdefault(self.route(req), []).append(i)
        futures: List[Optional[_CallerFuture]] = [None] * len(requests)
        for g, idxs in by_group.items():
            got = self.schedulers[g].submit_many(
                [requests[i] for i in idxs])
            for i, fut in zip(idxs, got):
                futures[i] = fut
        return futures

    def flush(self, timeout: float = 30.0) -> None:
        for s in self.schedulers:
            s.flush(timeout)

    def close(self) -> None:
        for s in self.schedulers:
            s.close()

    def stats(self) -> dict:
        return {"n_groups": len(self.schedulers),
                "affine_tenants": len(self.affine_tenants),
                "groups": [s.stats() for s in self.schedulers]}
