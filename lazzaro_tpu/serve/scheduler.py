"""Cross-request query batching for the fused retrieval kernel.

Serving millions of users means the unit of device work must be the BATCH,
not the request: every dispatch+readback costs the host one round trip
regardless of how many queries ride in it, and one arena scan serves a whole
batch for the HBM bytes of one query. The
``QueryScheduler`` here coalesces concurrent ``search_memories`` / ``chat``
retrievals — across callers, threads, and tenants — into padded mega-batches
the way Ragged Paged Attention coalesces ragged decode work on TPU:

- callers ``submit()`` a :class:`RetrievalRequest` and block on the returned
  future; a single worker thread owns the device dispatch (which also keeps
  the donated state mutation single-writer);
- the flush decision is the shared time/size policy (``utils.batching.
  FlushPolicy``): a full ``max_batch`` flushes immediately, a lone trickle
  request waits at most ``max_wait_us`` before it ships;
- the executor pads the popped batch to a power-of-two bucket before
  dispatch (``utils.batching.pad_to_pow2``), so the number of distinct jit
  specializations stays bounded no matter what batch sizes arrive;
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

- an **executor exception** demuxes to every future of that batch (the
  PR 2 behavior) and counts a breaker failure;
- a **worker-thread death** anywhere outside the demuxed executor call
  fails the admitted batch's futures with :class:`WorkerCrashed` and the
  worker RESTARTS (``reliability.worker_restarts``) — pending requests
  stay queued and are served by the restarted worker;
- a **dispatch deadline** (``dispatch_timeout_s > 0``) arms a watchdog
  per dispatch: on expiry the batch's futures fail with
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
from lazzaro_tpu.utils.batching import FlushPolicy
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
    # Ragged per-request knobs (ISSUE 7): ride into the fused kernel as
    # int32 sidecar data, so one compiled kernel serves any mix. None =
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


class QueryScheduler:
    """Coalesce concurrent retrievals into dense device batches.

    One daemon worker thread pops pending requests and runs ``executor``
    on them; callers block on per-request futures. ``close()`` drains
    pending work before returning. The worker is crash-restarting and
    every failure path resolves futures with a typed error (see the
    module docstring's failure model).

    Two batching disciplines (ISSUE 7):

    - **continuous** (default): requests admit into the NEXT dispatch the
      moment the worker is free — the in-flight dispatch is the batching
      window. A lone request on an idle scheduler ships immediately
      (latency = dispatch time, never the flush timeout), and arrivals
      during a dispatch coalesce into the next one without any timer.
      Per-tenant admission control (``tenant_max_inflight``) caps how
      many of one tenant's requests enter a single dispatch, walking the
      queue oldest-first so over-cap requests keep their place for the
      next batch — one flooding tenant cannot monopolize the device.
    - **flush-boundary** (``continuous=False``, the PR 2–6 policy): a
      batch ships when it holds ``max_batch`` requests or its oldest has
      waited ``max_wait_us`` (default 2 ms). Kept for A/B and fallback.
    """

    def __init__(self, executor: Executor, max_batch: int = 64,
                 max_wait_us: int = 2000, name: str = "lz-query-scheduler",
                 telemetry=None, continuous: bool = True,
                 tenant_max_inflight: int = 0,
                 dispatch_timeout_s: float = 0.0,
                 breaker_threshold: int = 5,
                 breaker_cooldown_s: float = 5.0,
                 shed_depth: int = 0, shed_bytes: int = 0,
                 degrade_cap_take: int = 1, degrade_nprobe: int = 1,
                 admission_check: Optional[Callable] = None):
        self._executor = executor
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
        self.policy = FlushPolicy(max_batch, max_wait_us / 1e6)
        self.continuous = bool(continuous)
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
        self._pending: List[Tuple[RetrievalRequest, Future, float]] = []
        self._pending_bytes = 0
        self._inflight = 0
        self._closed = False
        self.batches_flushed = 0
        self.requests_served = 0
        self.requests_deferred = 0           # tenant-cap admission defers
        self.requests_shed = 0               # admission-control rejections
        self.worker_restarts = 0
        self.watchdog_timeouts = 0
        self.batch_sizes: List[int] = []     # observability (bench reads it)
        self._name = name
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._worker.start()

    @property
    def closed(self) -> bool:
        return self._closed

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
            self._ensure_worker_locked()
            self._cond.notify()
        return futures

    def _ensure_worker_locked(self) -> None:
        """Respawn the worker if it is gone (belt-and-braces: the restart
        loop already survives crashes, but a dead thread must never let a
        future sit unserved)."""
        if self._closed or self._worker.is_alive():
            return
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name=self._name)
        self._worker.start()

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
            # the worker's wait for work, up to the batch being admitted:
            # the window less these spans is the time the worker was busy
            with self.telemetry.span("sched.idle"), self._cond:
                while True:
                    now = time.time()
                    oldest = self._pending[0][2] if self._pending else None
                    if self._pending and (
                            self._closed or self.continuous
                            or self.policy.should_flush(len(self._pending),
                                                        now, oldest)):
                        # continuous mode: the worker being free IS the
                        # flush signal — pending work admits immediately
                        # (ISSUE 7 lone-request fix: no serve_flush_us
                        # wait on an idle scheduler).
                        break
                    if self._closed:
                        return
                    timeout = (self.policy.wait_remaining(now, oldest)
                               if self._pending else None)
                    self._cond.wait(timeout)
                batch = self._admit_locked()
                self._inflight += 1
            try:
                # Fault point "scheduler.worker" (ISSUE 10): a raise here
                # models the worker dying OUTSIDE the demuxed executor
                # call — the pre-ISSUE-10 scheduler would strand these
                # futures forever.
                try:
                    faults.fire("scheduler.worker", batch=len(batch))
                    self._execute(batch)
                except BaseException as e:
                    err = WorkerCrashed(
                        f"query-scheduler worker died mid-batch: {e!r}")
                    for _, fut, _ in batch:
                        _fail_future(fut, err)
                    raise
            finally:
                with self._cond:
                    self._inflight -= 1
                    self._cond.notify_all()

    def _admit_locked(self) -> List[Tuple[RetrievalRequest, Future, float]]:
        """Pop the next dispatch's batch from the pending queue (caller
        holds the lock). Oldest-first; at most ``max_batch``; with a
        tenant cap, at most ``tenant_max_inflight`` requests per tenant
        admit — over-cap requests KEEP their queue position (fairness:
        the deferred oldest request is first in line next dispatch)."""
        limit = self.policy.max_items
        cap = self.tenant_max_inflight
        if not cap:
            batch = self._pending[:limit]
            del self._pending[:len(batch)]
            self._note_admitted_locked(batch)
            return batch
        batch: List[Tuple[RetrievalRequest, Future, float]] = []
        kept: List[Tuple[RetrievalRequest, Future, float]] = []
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
        self._pending = kept
        self._note_admitted_locked(batch)
        if deferred:
            self.requests_deferred += deferred
            self.telemetry.bump("serve.admission_deferred", deferred)
        return batch

    def _note_admitted_locked(self, batch) -> None:
        if self.shed_bytes and batch:
            self._pending_bytes = max(
                0, self._pending_bytes
                - sum(np.asarray(req.query).nbytes for req, _, _ in batch))

    def _degrade(self, req: RetrievalRequest) -> RetrievalRequest:
        """The breaker's cheap rung: clamp the per-request knobs the
        ragged kernels read as device data (fewer IVF probes, smaller
        boost/retrieval cap) — same k results, less device work. The
        request object is copied, never mutated (the caller may retry it
        at full quality)."""
        cap = (self.degrade_cap_take if req.cap_take is None
               else min(req.cap_take, self.degrade_cap_take))
        npr = (self.degrade_nprobe if req.nprobe is None
               else min(req.nprobe, self.degrade_nprobe))
        return dataclasses.replace(req, cap_take=cap, nprobe=npr)

    def _account(self, batch):
        """(requests to dispatch, their summed queue wait in seconds, the
        armed watchdog timer or None, its timed-out flag)."""
        reqs = [req for req, _, _ in batch]
        flush_t = time.time()
        waited_s = 0.0
        for req, _, enq in batch:
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
                    f"watchdog deadline (batch of {len(batch)})")
                for _, fut, _ in batch:
                    _fail_future(fut, err)
            timer = threading.Timer(self.dispatch_timeout_s, _deadline)
            timer.daemon = True
            timer.start()
        return reqs, waited_s, timer, timed_out

    def _execute(self, batch) -> None:
        # the worker's own bookkeeping before the dispatch: one labelled
        # queue-wait sample per request, the breaker, the watchdog
        with self.telemetry.span("sched.account"):
            reqs, waited_s, timer, timed_out = self._account(batch)
        try:
            # one mega-batch == one profiler step, so TPU captures line up
            # with the host spans batch-for-batch
            with StepTraceAnnotation("lz.serve.batch",
                                     step_num=self.batches_flushed):
                results = self._executor(reqs)
        except Exception as e:                      # noqa: BLE001 — demuxed
            if timer is not None:
                timer.cancel()
            if self.breaker is not None:
                self.breaker.record_failure()
            for _, fut, _ in batch:
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
        self.batches_flushed += 1
        self.requests_served += len(batch)
        self.telemetry.bump("serve.requests", len(batch))
        self.telemetry.bump("serve.batches")
        # summed over the served requests, so it divides by serve.requests
        self.telemetry.bump("serve.queue_wait_us", int(waited_s * 1e6))
        if len(batch) == 1:
            # a whole dispatch (a full arena pass) for one answer
            self.telemetry.bump("serve.lone_batches")
        self.telemetry.record("serve.batch_requests", len(batch))
        self.batch_sizes.append(len(batch))
        if len(self.batch_sizes) > 1024:
            del self.batch_sizes[:512]
        # the callers' done-callbacks run here, on the worker thread
        with self.telemetry.span("sched.demux"):
            for (_, fut, _), res in zip(batch, results):
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
        self._worker.join(timeout=30.0)

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
                "continuous": self.continuous,
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
