"""Cross-request query batching for the fused retrieval kernel.

Serving millions of users means the unit of device work must be the BATCH,
not the request: every dispatch+readback costs the host one round trip
regardless of how many queries ride in it, and one arena scan serves a whole
batch for the HBM bytes of one query. The
``QueryScheduler`` here coalesces concurrent ``search_memories`` / ``chat``
retrievals — across callers, threads, and tenants — into padded mega-batches
the way Ragged Paged Attention coalesces ragged decode work on TPU:

- callers ``submit()`` a :class:`RetrievalRequest` and block on the returned
  future; the scheduler's workers own the device dispatch. One dispatch
  is in flight at a time — which keeps the donated state mutation
  single-writer — except that a FULL pending batch of pure reads is
  admitted over an in-flight batch of pure reads (at most two in flight,
  see :class:`QueryScheduler`);
- there is no flush timer: the dispatch in flight is the batching window.
  Pending requests are admitted, at most ``max_batch`` of them, the moment
  the worker is free, so a lone request on an idle scheduler ships at
  once and arrivals during a dispatch coalesce into the next;
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
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
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


def _fail_future(fut: Future, err: BaseException) -> None:
    """Set an exception, tolerating a future that already resolved (the
    watchdog and the late dispatch race by design)."""
    if fut.cancelled():
        return
    try:
        fut.set_exception(err)
    except InvalidStateError:
        pass


def _set_future(fut: Future, res) -> None:
    if fut.cancelled():
        return
    try:
        fut.set_result(res)
    except InvalidStateError:
        pass            # watchdog already failed it — late result discarded


_Item = Tuple[RetrievalRequest, Future, float]      # (request, future, enqueued)


@dataclass(eq=False)
class _Batch:
    """One admitted dispatch: its queue items, and what the admission
    decided about it."""
    items: List[_Item]
    overlapped: bool            # admitted while another was in flight
    seq: int                    # admission order: the profiler's step number

    @property
    def reqs(self) -> List[RetrievalRequest]:
        return [req for req, _, _ in self.items]


_PARK = object()        # _next_batch_locked: come back as a parked worker


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
    into the next one — or (ISSUE 30) when exactly ONE
    dispatch is in flight and the pending queue holds a FULL batch (what
    ``_select_locked`` picks has ``max_batch`` requests) and
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
        self._idle_held = False              # a worker is in its idle wait
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
    def submit(self, request: RetrievalRequest) -> "Future[RetrievalResult]":
        return self.submit_many([request])[0]

    def submit_many(self, requests: Sequence[RetrievalRequest]
                    ) -> List["Future[RetrievalResult]"]:
        """Enqueue a group atomically (a ``search_memories_batch`` fleet
        stays contiguous, so it lands in as few flushes as possible).
        Under admission overload the whole group's futures fail
        immediately with :class:`LoadShed` — the futures API is uniform,
        so callers see the typed error at ``.result()`` like any other
        failure."""
        futures = [Future() for _ in requests]
        now = time.time()
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
            # the wait of a worker that would admit whatever came, up to
            # the batch being admitted: the window less these spans is the
            # time a worker was busy. A worker with nothing to admit while
            # a dispatch is in flight parks OUTSIDE the span.
            with self.telemetry.span("sched.idle"), self._cond:
                batch = self._next_batch_locked(parked=False)
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
        nothing it may admit over it — or the other worker idle already —
        returns ``_PARK`` and comes back parked: it then sleeps until a
        full batch waits behind one dispatch. The worker that finishes a
        dispatch always looks at the queue itself, so a parked one is
        never needed for anything else."""
        while True:
            batch = self._try_admit_locked()
            if batch is not None:
                return batch
            if self._closed and not self._pending:
                return None
            if parked:
                self._parked.wait()
                continue
            if self._inflight or self._idle_held:
                return _PARK
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
            # immediately, a lone request never waits on a timer
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
        batch = _Batch(items, overlapped, self._dispatch_seq)
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
        """(requests to dispatch, their summed queue wait in seconds, the
        armed watchdog timer or None, its timed-out flag)."""
        items = batch.items
        reqs = batch.reqs
        flush_t = time.time()
        waited_s = 0.0
        for req, _, enq in items:
            waited_s += flush_t - enq
            # a ring of ``window`` samples PER TENANT series (256 tenants,
            # then "~other"): a series that overflows keeps its newest
            # samples only, so percentiles of this timer lean to the
            # window's end — serve.queue_wait_us is the whole sum
            self.telemetry.record("serve.queue_wait_ms",
                                  (flush_t - enq) * 1e3,
                                  labels={"tenant": req.tenant})
        if self.breaker is not None and self.breaker.degraded(flush_t):
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
        return reqs, waited_s, timer, timed_out

    def _execute(self, batch: _Batch) -> None:
        items = batch.items
        # the worker's own bookkeeping before the dispatch: one labelled
        # queue-wait sample per request, the breaker, the watchdog
        with self.telemetry.span("sched.account"):
            reqs, waited_s, timer, timed_out = self._account(batch)
        try:
            # one mega-batch == one profiler step, so TPU captures line up
            # with the host spans batch-for-batch. ONE annotation around
            # the whole executor call, on the thread that runs it: every
            # such span contains at least its own pass over the arena.
            with StepTraceAnnotation("lz.serve.batch", step_num=batch.seq):
                results = self._executor(reqs)
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
        self.telemetry.bump("serve.requests", len(items))
        self.telemetry.bump("serve.batches")
        # summed over the served requests, so it divides by serve.requests
        self.telemetry.bump("serve.queue_wait_us", int(waited_s * 1e6))
        if len(items) == 1:
            # a whole dispatch (a full arena pass) for one answer
            self.telemetry.bump("serve.lone_batches")
        # a batch admitted while another was in flight (a bump of 0 is
        # dropped: a run that never overlaps has no such entry)
        self.telemetry.bump("serve.overlapped_batches", int(batch.overlapped))
        self.telemetry.record("serve.batch_requests", len(items))
        # the callers' done-callbacks run here, on the worker thread
        with self.telemetry.span("sched.demux"):
            for (_, fut, _), res in zip(items, results):
                _set_future(fut, res)

    def load(self) -> int:
        """Instantaneous queue depth + in-flight dispatches — the
        least-loaded routing signal :class:`ReplicaRouter` reads."""
        with self._cond:
            return len(self._pending) + self._inflight

    # ----------------------------------------------------------- lifecycle
    def flush(self, timeout: float = 30.0) -> None:
        """Block until everything submitted so far has been executed."""
        deadline = time.time() + timeout
        with self._cond:
            self._cond.notify()
            while self._pending or self._inflight:
                remaining = deadline - time.time()
                if remaining <= 0:
                    raise TimeoutError("QueryScheduler.flush timed out")
                self._cond.wait(min(remaining, 0.05))

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

    def submit(self, request: RetrievalRequest) -> "Future[RetrievalResult]":
        return self.schedulers[self.route(request)].submit(request)

    def submit_many(self, requests: Sequence[RetrievalRequest]
                    ) -> List["Future[RetrievalResult]"]:
        """Route a group of requests; each sub-group stays contiguous on
        its scheduler (the atomic-group property per group)."""
        by_group: Dict[int, List[int]] = {}
        for i, req in enumerate(requests):
            by_group.setdefault(self.route(req), []).append(i)
        futures: List[Optional[Future]] = [None] * len(requests)
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
