"""Fault-injection recovery matrix (ISSUE 10).

Every named injection point — failed donated dispatch, worker-thread
death, pump crash mid-chunk, torn checkpoint write, cold-store read
error — is driven deterministically through ``reliability.faults`` and
must end in STATE PARITY with an uninjected run: same results, bit-equal
arena/edge columns (and int8 shadow where maintained), zero hung
futures, zero lost journaled facts. The dispatch-level cells run across
{exact, quant, ivf, tiered, 2-way mesh}; actor-level cells (scheduler,
ingest worker, pump, checkpoint, cold store) run on the modes they
apply to. A jit-counter test pins that the fault-FREE path still costs
exactly ONE dispatch per serve — the guards add retries, never
dispatches.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

from lazzaro_tpu.core import checkpoint as C
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.reliability import (ArenaPoisoned, CheckpointCorrupt,
                                     CircuitBreaker, ColdReadError,
                                     DeviceOom, DispatchTimeout,
                                     IngestJournal, LoadShed,
                                     WorkerCrashed)
from lazzaro_tpu.reliability.faults import (INJECTOR, InjectedFault,
                                            oom_error, poison_states_hook,
                                            torn_write_hook)
from lazzaro_tpu.serve.scheduler import (QueryScheduler, RetrievalRequest,
                                         RetrievalResult)
from lazzaro_tpu.utils.telemetry import Telemetry

D = 32
EPOCH = 1000.0          # shared by every index so parity covers timestamps
KW = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
          nbr_boost=0.02, now=1234.5)
MODES = ["exact", "quant", "ivf", "tiered", "mesh2"]

_ARENA_COLS = ("emb", "salience", "timestamp", "last_accessed",
               "access_count", "type_id", "shard_id", "tenant_id", "alive",
               "is_super")
_EDGE_COLS = ("src", "tgt", "weight", "co", "last_updated", "alive",
              "tenant_id")


@pytest.fixture(autouse=True)
def _clean_faults():
    INJECTOR.clear()
    yield
    INJECTOR.clear()


def _vecs(n, seed):
    r = np.random.default_rng(seed)
    nz = r.standard_normal((n, D)).astype(np.float32)
    return nz / np.linalg.norm(nz, axis=1, keepdims=True)


def _fill(idx, n=200, seed=0):
    emb = _vecs(n, seed)
    ids = [f"n{i}" for i in range(n)]
    sup = [i % 29 == 0 for i in range(n)]
    idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
            ["default"] * n, "u0", is_super=sup)
    # now= pinned so two builds are bit-identical regardless of the f32
    # relative-timestamp quantum the wall clock happens to land in
    idx.add_edges([(f"n{i}", f"n{i + 1}", 0.7) for i in range(n - 1)],
                  "u0", now=EPOCH)
    return emb


def _reqs(emb, nq=8, k=10, boost=True, seed=9):
    r = np.random.default_rng(seed)
    q = emb[:nq] + 0.01 * r.standard_normal((nq, D)).astype(np.float32)
    return [RetrievalRequest(query=q[i], tenant="u0", k=k,
                             gate_enabled=True, boost=boost)
            for i in range(nq)]


def _build_mode(mode, **extra):
    """One (index, emb) fixture per matrix column, deterministic and
    epoch-pinned so two builds are bit-identical. ``extra`` forwards
    ctor kwargs (the replan cells pass an HBM-planner budget)."""
    if mode == "ivf":
        n = 4500
        idx = MemoryIndex(dim=D, capacity=5000, int8_serving=True,
                          coarse_slack=5001, ivf_nprobe=4096, epoch=EPOCH,
                          telemetry=Telemetry(), **extra)
        emb = _vecs(n, 0)
        ids = [f"n{i}" for i in range(n)]
        idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
                ["default"] * n, "u0")
        idx.add_edges([(f"n{j}", f"n{j + 1}", 0.7) for j in range(200)],
                      "u0", now=EPOCH)
        assert idx.ivf_maintenance(iters=2)
        return idx, emb
    mesh = None
    if mode == "mesh2":
        from lazzaro_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    idx = MemoryIndex(dim=D, capacity=255, epoch=EPOCH, mesh=mesh,
                      int8_serving=(mode in ("quant", "tiered", "mesh2")),
                      coarse_slack=(8 if mode == "exact" else 512),
                      telemetry=Telemetry(), **extra)
    emb = _fill(idx)
    if mode == "tiered":
        tm = idx.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
        tm.demote_rows([idx.id_to_row[f"n{i}"] for i in range(100, 200)])
        assert tm.cold_count > 90
    return idx, emb


def _assert_results_equal(a_list, b_list):
    for a, b in zip(a_list, b_list):
        assert a.ids == b.ids
        assert np.allclose(a.scores, b.scores, atol=2e-6)
        assert a.fast == b.fast
        assert a.gate_id == b.gate_id


def _assert_state_parity(ia, ib):
    """Bit-parity of every arena/edge column (+ int8 shadow when both
    sides maintain one) — the matrix's recovery contract."""
    for col in _ARENA_COLS:
        a = np.asarray(getattr(ia.state, col))
        b = np.asarray(getattr(ib.state, col))
        assert np.array_equal(a, b), f"arena.{col} diverged"
    for col in _EDGE_COLS:
        a = np.asarray(getattr(ia.edge_state, col))
        b = np.asarray(getattr(ib.edge_state, col))
        assert np.array_equal(a, b), f"edges.{col} diverged"
    sa, sb = ia._int8_shadow, ib._int8_shadow
    if (sa is not None and sb is not None
            and not ia._int8_dirty and not ib._int8_dirty):
        assert np.array_equal(np.asarray(sa[0]), np.asarray(sb[0]))
        assert np.array_equal(np.asarray(sa[1]), np.asarray(sb[1]))


# =====================================================================
# dispatch faults: transient raise → copy-twin retry → parity
# =====================================================================
@pytest.mark.parametrize("mode", MODES)
def test_dispatch_raise_recovers_to_parity(mode):
    """A donated serving dispatch that fails WITHOUT consuming its input
    retries through the non-donating twin: the caller sees a normal
    result, the retry is counted, and the post-recovery state is
    bit-identical to a fault-free run."""
    idx_f, emb = _build_mode(mode)
    idx_c, _ = _build_mode(mode)
    INJECTOR.arm("index.dispatch", times=1)
    r_f = idx_f.search_fused_requests(_reqs(emb), **KW)
    r_c = idx_c.search_fused_requests(_reqs(emb), **KW)
    assert INJECTOR.fired("index.dispatch") == 1
    assert idx_f.telemetry.counter_total("serve.dispatch_retries") >= 1
    _assert_results_equal(r_f, r_c)
    _assert_state_parity(idx_f, idx_c)


def test_dispatch_raise_on_ingest_recovers_to_parity():
    """The fused ingest dispatch under the same guard: one injected
    failure, transparent copy-twin retry, node/edge/shadow parity."""
    idx_f, _ = _build_mode("quant")
    idx_c, _ = _build_mode("quant")
    new = _vecs(8, 7)
    args = (["m%d" % i for i in range(8)], new, [0.5] * 8, [0.0] * 8,
            ["semantic"] * 8, ["default"] * 8, "u0")
    INJECTOR.arm("index.dispatch", times=1)
    idx_f.ingest_batch(*args, chain_pairs=[("m0", "m1")], now=1200.0)
    idx_c.ingest_batch(*args, chain_pairs=[("m0", "m1")], now=1200.0)
    assert INJECTOR.fired("index.dispatch") == 1
    assert idx_f.telemetry.counter_total("serve.dispatch_retries") >= 1
    _assert_state_parity(idx_f, idx_c)


def test_mutation_dispatch_raise_recovers():
    idx_f, _ = _build_mode("exact")
    idx_c, _ = _build_mode("exact")
    INJECTOR.arm("index.dispatch", times=1)
    idx_f.update_access(["n0", "n3"], now=2000.0)
    idx_c.update_access(["n0", "n3"], now=2000.0)
    _assert_state_parity(idx_f, idx_c)


# =====================================================================
# typed OOM (ISSUE 11): non-transient classification + replan recovery
# =====================================================================
def test_oom_dispatch_not_retried_as_transient():
    """REPRO (ISSUE 11 satellite): the guard used to retry
    RESOURCE_EXHAUSTED with backoff as if transient — re-failing
    identically until the retry budget burned. It now reclassifies the
    FIRST allocation failure into the typed DeviceOom (routing it to the
    planner), so the armed fault fires exactly once and no copy-twin
    retry ever launches."""
    idx, emb = _build_mode("exact")
    INJECTOR.arm("index.dispatch", times=3, exc=oom_error)
    with pytest.raises(DeviceOom):
        idx.update_access(["n0"], now=2000.0)
    assert INJECTOR.fired("index.dispatch") == 1   # ONE attempt, no burn
    assert idx.telemetry.counter_total("serve.dispatch_retries") == 0
    assert idx.telemetry.counter_total("reliability.oom") == 1


@pytest.mark.parametrize("mode", MODES)
def test_plan_oom_replan_recovers_to_parity(mode):
    """The replan-recovery matrix cells (ISSUE 11): an injected
    RESOURCE_EXHAUSTED at the fused dispatch on a planner-active index
    recovers by ONE replan into split sub-dispatches through the copy
    twins — results and state bit-identical to an uninjected unsplit
    run, across every serving mode."""
    idx_f, emb = _build_mode(mode, hbm_budget_bytes=1 << 34)
    idx_c, _ = _build_mode(mode)
    INJECTOR.arm("plan.oom", times=1, exc=oom_error)
    r_f = idx_f.search_fused_requests(_reqs(emb, boost=False), **KW)
    r_c = idx_c.search_fused_requests(_reqs(emb, boost=False), **KW)
    assert INJECTOR.fired("plan.oom") == 1
    assert idx_f.telemetry.counter_total("plan.oom_replans") == 1
    assert idx_f.telemetry.counter_total("plan.split_dispatches") >= 2
    _assert_results_equal(r_f, r_c)
    _assert_state_parity(idx_f, idx_c)


def test_plan_oom_without_planner_stays_typed():
    """With no planner budget configured there is nothing to replan
    with: the reclassified DeviceOom surfaces typed (never a backoff
    retry loop, never a hang)."""
    idx, emb = _build_mode("exact")
    INJECTOR.arm("plan.oom", times=1, exc=oom_error)
    with pytest.raises(DeviceOom):
        idx.search_fused_requests(_reqs(emb, boost=False), **KW)
    r = idx.search_fused_requests(_reqs(emb, boost=False), **KW)
    assert all(x.ids for x in r)                   # next serve is clean


# =====================================================================
# dispatch faults: poisoned arena → typed error, checkpoint recovery
# =====================================================================
def test_poisoned_arena_raises_typed_and_fast():
    """A donated dispatch that CONSUMED its input before failing leaves
    nothing to retry with: the index raises the typed ArenaPoisoned —
    immediately on the failing call and on every later touch — instead
    of surfacing XLA's 'Array has been deleted' from a random depth."""
    idx, emb = _build_mode("exact")
    INJECTOR.arm("index.dispatch", times=1, hook=poison_states_hook)
    with pytest.raises(ArenaPoisoned):
        idx.update_access(["n0"], now=2000.0)
    assert idx.poisoned
    with pytest.raises(ArenaPoisoned):
        idx.update_access(["n1"], now=2001.0)
    with pytest.raises(ArenaPoisoned):
        idx.search_fused_requests(_reqs(emb, nq=2), **KW)
    assert idx.telemetry.counter_total("reliability.poisoned") == 1


def test_poisoned_arena_recovers_via_checkpoint(tmp_path):
    """The poisoned-arena recovery path: restore the last checkpoint →
    bit-parity with a never-poisoned twin, serving works."""
    idx, emb = _build_mode("quant")
    ck = str(tmp_path / "ck")
    C.save_index(idx, ck)
    INJECTOR.arm("index.dispatch", times=1, hook=poison_states_hook)
    with pytest.raises(ArenaPoisoned):
        idx.update_access(["n0"], now=2000.0)
    restored = C.load_index(ck, int8_serving=True, coarse_slack=512)
    control, _ = _build_mode("quant")
    _assert_state_parity(restored, control)
    r_r = restored.search_fused_requests(_reqs(emb), **KW)
    r_c = control.search_fused_requests(_reqs(emb), **KW)
    _assert_results_equal(r_r, r_c)


# =====================================================================
# scheduler worker death: typed futures, restart, parity
# =====================================================================
@pytest.mark.parametrize("mode", MODES)
def test_worker_death_fails_futures_and_restarts(mode):
    """Pre-ISSUE-10, a worker-thread exception outside the demuxed
    executor stranded every pending future FOREVER. Now the admitted
    batch fails with the typed WorkerCrashed, the worker restarts, and
    the next submit serves normally — state parity with a run that only
    saw the successful batch (the dead batch never touched the device)."""
    idx_f, emb = _build_mode(mode)
    idx_c, _ = _build_mode(mode)
    tel = Telemetry()
    sched = QueryScheduler(
        lambda rs: idx_f.search_fused_requests(rs, **KW), telemetry=tel)
    INJECTOR.arm("scheduler.worker", times=1)
    futs = sched.submit_many(_reqs(emb, nq=4))
    for f in futs:
        with pytest.raises(WorkerCrashed):
            f.result(timeout=30)            # typed, never a hang
    futs2 = sched.submit_many(_reqs(emb, nq=4))
    res_f = [f.result(timeout=30) for f in futs2]
    sched.close()
    assert tel.counter_total("reliability.worker_restarts") >= 1
    res_c = idx_c.search_fused_requests(_reqs(emb, nq=4), **KW)
    _assert_results_equal(res_f, res_c)
    _assert_state_parity(idx_f, idx_c)


def test_executor_exception_still_demuxes_typed():
    """The PR 2 contract preserved: an executor exception resolves every
    future of that batch with the error itself."""
    def boom(reqs):
        raise ValueError("executor exploded")

    sched = QueryScheduler(boom, telemetry=Telemetry())
    f = sched.submit(RetrievalRequest(query=np.zeros(D, np.float32),
                                      tenant="t"))
    with pytest.raises(ValueError):
        f.result(timeout=30)
    sched.close()


# =====================================================================
# watchdog deadline, circuit breaker, load shedding
# =====================================================================
def _req():
    return RetrievalRequest(query=np.zeros(D, np.float32), tenant="t")


def test_watchdog_deadline_fails_futures_typed():
    def slow(reqs):
        time.sleep(0.3)
        return [RetrievalResult() for _ in reqs]

    tel = Telemetry()
    sched = QueryScheduler(slow, telemetry=tel, dispatch_timeout_s=0.05)
    f = sched.submit(_req())
    with pytest.raises(DispatchTimeout):
        f.result(timeout=30)
    sched.close()
    assert tel.counter_total("reliability.watchdog_timeouts") == 1
    assert sched.breaker.stats()["consecutive_failures"] >= 0


def test_breaker_opens_degrades_then_recovers():
    seen = []
    fail = {"n": 2}

    def ex(reqs):
        seen.append([(r.cap_take, r.nprobe) for r in reqs])
        if fail["n"] > 0:
            fail["n"] -= 1
            raise RuntimeError("device unhappy")
        return [RetrievalResult() for _ in reqs]

    tel = Telemetry()
    sched = QueryScheduler(ex, telemetry=tel, breaker_threshold=2,
                           breaker_cooldown_s=30.0)
    for _ in range(2):
        with pytest.raises(RuntimeError):
            sched.submit(_req()).result(timeout=30)
    assert sched.breaker.state == "open"
    sched.submit(_req()).result(timeout=30)       # served DEGRADED
    assert seen[-1] == [(1, 1)]                   # nprobe/cap_take clamped
    assert tel.counter_total("reliability.degraded_requests") == 1
    # cooldown elapses → half-open probe at full quality → re-close
    sched.breaker._opened_at -= 60.0
    sched.submit(_req()).result(timeout=30)
    assert seen[-1] == [(None, None)]             # full quality again
    assert sched.breaker.state == "closed"
    sched.close()


def test_load_shed_typed_and_bounded():
    gate = threading.Event()

    def ex(reqs):
        gate.wait(10)
        return [RetrievalResult() for _ in reqs]

    tel = Telemetry()
    sched = QueryScheduler(ex, telemetry=tel, shed_depth=2)
    f1 = sched.submit(_req())         # admitted by the worker, blocks
    for _ in range(200):
        with sched._cond:
            if sched._inflight == 1 and not sched._pending:
                break
        time.sleep(0.005)
    f23 = sched.submit_many([_req(), _req()])     # queue == depth: admitted
    f4 = sched.submit(_req())                     # over budget: shed
    with pytest.raises(LoadShed):
        f4.result(timeout=30)
    gate.set()
    assert isinstance(f1.result(timeout=30), RetrievalResult)
    for f in f23:
        assert isinstance(f.result(timeout=30), RetrievalResult)
    sched.close()
    assert tel.counter_total("reliability.load_shed") == 1
    assert sched.requests_shed == 1


def test_breaker_unit_transitions():
    br = CircuitBreaker(threshold=2, cooldown_s=0.01)
    assert br.state == "closed" and not br.degraded(now=0.0)
    br.record_failure(now=0.0)
    assert br.state == "closed"
    br.record_failure(now=0.0)
    assert br.state == "open" and br.opens == 1
    assert br.degraded(now=0.005)                 # inside cooldown
    assert not br.degraded(now=0.02)              # → half-open probe
    assert br.state == "half_open"
    br.record_failure(now=0.03)                   # probe failed → re-open
    assert br.state == "open" and br.opens == 2
    assert not br.degraded(now=1.0)
    br.record_success()
    assert br.state == "closed"


# =====================================================================
# durable ingest journal
# =====================================================================
def test_ingest_journal_append_commit_replay(tmp_path):
    p = str(tmp_path / "ing.wal")
    j = IngestJournal(p)
    s1 = j.append([{"content": "a"}])
    s2 = j.append([{"content": "b"}, {"content": "c"}])
    assert (s1, s2) == (1, 2)
    j2 = IngestJournal(p)                         # crash + reopen
    assert [s for s, _ in j2.pending()] == [1, 2]
    j2.commit(s1)
    j3 = IngestJournal(p)
    assert [f for _, f in j3.pending()] == [[{"content": "b"},
                                             {"content": "c"}]]
    j3.commit(j3.last_seq)                        # retires all → compacts
    assert os.path.getsize(p) == 0
    # sequence numbers keep advancing after compaction
    j4 = IngestJournal(p)
    s3 = j4.append([{"content": "d"}])
    with open(p, "ab") as f:
        f.write(b"\x31WZL\x99garbage")            # torn tail record
    j5 = IngestJournal(p)
    assert [s for s, _ in j5.pending()] == [s3]


def _system_ms(tmp, llm=None):
    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.memory_system import MemorySystem
    from tests.test_fused_ingest import ClusteredEmb, QueueLLM

    return MemorySystem(
        enable_async=False, db_dir=tmp, verbose=False, load_from_disk=False,
        llm_provider=llm or QueueLLM(4), embedding_provider=ClusteredEmb(),
        auto_prune=False, max_buffer_size=10_000,
        config=MemoryConfig(journal=True, auto_consolidate=False,
                            decay_rate=0.0))


def _count_facts(ms, content):
    return sum(1 for shard in ms.shards.values()
               for n in shard.nodes.values() if n.content == content)


def test_ingest_worker_death_zero_lost_facts(tmp_db):
    """Worker dies between extraction and ingest: the facts are already
    journaled, so a 'crashed' process replays them on startup through
    the normal ingest path — zero lost facts."""
    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.memory_system import MemorySystem
    from tests.test_fused_ingest import ClusteredEmb, QueueLLM

    ms = _system_ms(tmp_db)
    ms.start_conversation()
    ms.add_to_short_term("turn one", "semantic", 0.6)
    INJECTOR.arm("ingest.worker", times=1)
    ms.end_conversation()                 # extraction ok, worker "dies"
    assert INJECTOR.fired("ingest.worker") == 1
    assert ms._ingest_journal.pending_count == 1
    assert _count_facts(ms, "fact 0 body") == 0   # nothing ingested yet
    # simulated crash: no close(). A fresh process on the same db_dir:
    ms2 = MemorySystem(
        enable_async=False, db_dir=tmp_db, load_from_disk=True,
        verbose=False, llm_provider=QueueLLM(4),
        embedding_provider=ClusteredEmb(),
        config=MemoryConfig(journal=True, auto_consolidate=False,
                            decay_rate=0.0))
    assert ms2._ingest_journal.pending_count == 0     # replayed + committed
    assert ms2.telemetry.counter_total("reliability.journal_replayed") == 4
    assert _count_facts(ms2, "fact 0 body") == 1
    ms2.close()


def test_journal_replay_is_idempotent(tmp_db):
    """Crash AFTER the dispatch but BEFORE the commit: replay re-ingests
    facts that already landed — the in-dispatch dedup probe collapses
    them into merges, so the corpus holds each fact exactly once."""
    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.core.memory_system import MemorySystem
    from tests.test_fused_ingest import ClusteredEmb, QueueLLM

    ms = _system_ms(tmp_db)
    ms.start_conversation()
    ms.add_to_short_term("turn one", "semantic", 0.6)
    ms.end_conversation()                 # clean ingest, journal committed
    assert _count_facts(ms, "fact 0 body") == 1
    # re-append the same facts = "crashed before commit"
    facts = [{"content": f"fact {i} body", "type": "semantic",
              "salience": 0.6, "topic": "work"} for i in range(4)]
    ms._ingest_journal.append(facts)
    ms._save_to_persistence()
    ms2 = MemorySystem(
        enable_async=False, db_dir=tmp_db, load_from_disk=True,
        verbose=False, llm_provider=QueueLLM(4),
        embedding_provider=ClusteredEmb(),
        config=MemoryConfig(journal=True, auto_consolidate=False,
                            decay_rate=0.0))
    assert ms2._ingest_journal.pending_count == 0
    assert _count_facts(ms2, "fact 0 body") == 1      # merged, not doubled
    ms2.close()


def test_ingest_dispatch_failure_requeues_and_retries(tmp_db):
    """The fused ingest dispatch fails past its retry budget: the facts
    go back to the coalescer front + stay journaled, the worker survives,
    and the next consolidation lands them exactly once."""
    ms = _system_ms(tmp_db)
    ms.start_conversation()
    ms.add_to_short_term("turn one", "semantic", 0.6)
    # 1 initial attempt + dispatch_retry_max(2) retries = 3 fires exhausts
    # the guard for the ONE ingest dispatch; decay afterwards runs clean.
    INJECTOR.arm("index.dispatch", times=3)
    ms.end_conversation()
    assert len(ms._ingest_coalescer) == 4         # facts requeued
    assert ms._ingest_journal.pending_count == 1
    assert ms.telemetry.counter_total("reliability.ingest_failures") == 1
    INJECTOR.clear()
    ms.start_conversation()
    ms.add_to_short_term("turn two", "semantic", 0.6)
    ms.end_conversation()                 # drains requeued + new facts
    assert _count_facts(ms, "fact 0 body") == 1
    assert _count_facts(ms, "fact 4 body") == 1   # second extraction's
    assert ms._ingest_journal.pending_count == 0  # all committed
    ms.close()


# =====================================================================
# tier pump: commit-then-zero, crash mid-chunk, cold-store read errors
# =====================================================================
def test_pump_mid_chunk_crash_leaves_rows_hot(tmp_path):
    """The pump dies between the cold-store commit and the hot
    zero-scatter: commit-then-zero means the master row was NOT zeroed —
    the rows stay hot, the cold residue is dropped, and the next pass
    demotes cleanly."""
    idx_f, emb = _build_mode("quant")
    idx_c, _ = _build_mode("quant")
    tm = idx_f.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0,
                              cold_dir=str(tmp_path / "cold"))
    # n116 / n145 are super rows (pinned hot): 48 of the 50 are demotable
    rows = [idx_f.id_to_row[f"n{i}"] for i in range(100, 150)]
    demotable = [r for r in rows if r not in idx_f._super_rows]
    INJECTOR.arm("pump.mid_chunk", times=1)
    with pytest.raises(InjectedFault):
        tm.demote_rows(rows)
    assert tm.cold_count == 0 and not tm.cold_np.any()
    _assert_state_parity(idx_f, idx_c)            # master untouched
    assert tm.demote_rows(rows) == len(demotable)  # clean retry next pass
    assert tm.cold_count == len(demotable)
    emb_now = np.asarray(idx_f.state.emb)
    assert not emb_now[demotable].any()           # now demoted for real


def test_pump_thread_survives_injected_crash():
    from lazzaro_tpu.tier import TierPump

    idx, _ = _build_mode("quant")
    tm = idx.enable_tiering(hot_budget_rows=32, hysteresis_s=0.0)
    INJECTOR.arm("pump.mid_chunk", times=1)
    pump = TierPump(tm, interval_s=0.02).start()
    deadline = time.time() + 30
    while time.time() < deadline and tm.cold_count == 0:
        time.sleep(0.02)
    assert INJECTOR.fired("pump.mid_chunk") == 1  # the crash happened
    assert tm.cold_count > 0                      # and a later pass won
    assert pump.running                           # pump never died
    pump.stop()
    assert idx.telemetry.counter_total("reliability.worker_restarts") >= 1


def test_coldstore_read_error_typed_and_recovers():
    """An injected cold-tier read error surfaces typed from the serving
    path (read-only turn: no partial boosts), and the next serve returns
    bit-parity with an uninjected index."""
    idx_f, emb = _build_mode("tiered")
    idx_c, _ = _build_mode("tiered")
    INJECTOR.arm("coldstore.read", times=1, exc=ColdReadError)
    with pytest.raises(ColdReadError):
        idx_f.search_fused_requests(_reqs(emb, boost=False), **KW)
    r_f = idx_f.search_fused_requests(_reqs(emb, boost=False), **KW)
    r_c = idx_c.search_fused_requests(_reqs(emb, boost=False), **KW)
    _assert_results_equal(r_f, r_c)
    _assert_state_parity(idx_f, idx_c)


def test_coldstore_read_error_on_promote_recovers():
    idx, _ = _build_mode("tiered")
    tm = idx.tiering
    cold_rows = sorted(np.flatnonzero(tm.cold_np).tolist())[:8]
    INJECTOR.arm("coldstore.read", times=1, exc=ColdReadError)
    with pytest.raises(ColdReadError):
        tm.promote_rows(cold_rows)
    assert tm.cold_np[cold_rows].all()            # still cold, consistent
    assert tm.promote_rows(cold_rows) == 8        # clean retry
    assert not tm.cold_np[cold_rows].any()


# =====================================================================
# torn checkpoint
# =====================================================================
def test_torn_checkpoint_raises_typed_and_resave_recovers(tmp_path):
    """A torn checkpoint write (payload corrupted after the CURRENT
    flip) must fail its checksum with the typed CheckpointCorrupt —
    never deserialize garbage — and a re-save from the live index
    restores full parity, including the tier residency + cold payload."""
    idx, emb = _build_mode("tiered")
    ck = str(tmp_path / "ck")
    INJECTOR.arm("checkpoint.torn", times=1, exc=None,
                 hook=torn_write_hook())
    C.save_index(idx, ck)                 # "succeeds" — silently torn
    with pytest.raises(CheckpointCorrupt):
        C.load_index(ck, int8_serving=True, coarse_slack=512)
    C.save_index(idx, ck)                 # recovery: re-save, no fault
    restored = C.load_index(ck, int8_serving=True, coarse_slack=512)
    _assert_state_parity(restored, idx)
    assert restored.tiering is not None
    assert restored.tiering.cold_count == idx.tiering.cold_count
    r_r = restored.search_fused_requests(_reqs(emb, boost=False), **KW)
    r_o = idx.search_fused_requests(_reqs(emb, boost=False), **KW)
    _assert_results_equal(r_r, r_o)


def test_checkpoint_checksum_catches_bit_rot(tmp_path):
    idx, _ = _build_mode("exact")
    ck = str(tmp_path / "ck")
    C.save_index(idx, ck)
    cur = open(os.path.join(ck, "CURRENT")).read().strip()
    npz = os.path.join(ck, cur, "arrays.npz")
    with open(npz, "r+b") as f:           # flip bytes mid-file
        f.seek(os.path.getsize(npz) // 2)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(CheckpointCorrupt):
        C.load_index(ck)


# =====================================================================
# fault-free path: the guards add ZERO dispatches
# =====================================================================
def test_fault_free_serve_still_one_dispatch(monkeypatch):
    """dispatches_per_turn == 1 is preserved with the reliability layer
    on: the guard wraps the same single donated dispatch — no probe, no
    shadow dispatch, no retry on the healthy path."""
    counted = ("search_fused_ragged", "search_fused_ragged_copy",
               "search_fused_ragged_read", "arena_search")
    calls = {name: 0 for name in counted}
    for name in counted:
        orig = getattr(S, name)

        def wrapped(*a, __orig=orig, __name=name, **kw):
            calls[__name] += 1
            return __orig(*a, **kw)

        monkeypatch.setattr(S, name, wrapped)
    idx, emb = _build_mode("exact")
    idx.search_fused_requests(_reqs(emb, nq=4), **KW)
    assert calls["search_fused_ragged"] == 1      # ONE donated dispatch
    for name in counted:
        if name != "search_fused_ragged":
            assert calls[name] == 0, (name, calls)
    assert idx.telemetry.counter_total("serve.dispatch_retries") == 0
