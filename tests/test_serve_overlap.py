"""Answers under overlapped dispatches (ISSUE 30; tier-1, CPU, debug
geometry): a closed loop of 2 x ``serve_batch_max`` client threads against
``MemorySystem`` — on one device and on four of conftest's host devices, as
``tests/test_pod_deployment.py`` stands the pod up — keeps one full batch in
flight and one full batch waiting, so the scheduler admits the second over
the first. What comes back is what a scheduler with ONE worker returns (the
same ids, scores to the last bit of an f32 sum) and what the benchmark's
plain reference gives; a boosting request among them is a barrier (it
donates: no copy, its boosts land once)."""

import os
import sys
import threading

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402  (the benchmark's plain top-k)
from lazzaro_tpu.config import MemoryConfig  # noqa: E402
from lazzaro_tpu.core import state as S  # noqa: E402
from lazzaro_tpu.core.memory_system import MemorySystem  # noqa: E402
from lazzaro_tpu.parallel.mesh import make_mesh  # noqa: E402
from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest  # noqa: E402
from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

N = 4
D = 32
SHARD = S.TOPK_BLOCK                             # 4,096 rows a chip
CAPACITY = N * SHARD - 1
TENANT_ROWS = 1000
TENANTS = 12                                     # three straddle a shard
BATCH = 16
CLIENTS = 2 * BATCH
ROUNDS = 6
K = 5
LIMITS = {"score_gap": 2e-4, "rank_errors": 0, "foreign_ids": 0,
          "count_errors": 0, "unanswered": 0, "swallowed": 0}


def _rows(t):
    rng = np.random.default_rng([30, t])
    return rng.standard_normal((TENANT_ROWS, D)).astype(np.float32)


@pytest.fixture(scope="module", params=["one_device", "mesh"])
def system(request, tmp_path_factory):
    mesh = (make_mesh(("data",), (N,), devices=jax.devices()[:N])
            if request.param == "mesh" else None)
    ms = MemorySystem(
        config=MemoryConfig(
            embed_dim=D, dtype="bfloat16", initial_capacity=CAPACITY,
            max_edges=1023, serve_batch_max=BATCH, max_buffer_size=4 * N * SHARD,
            enable_async=False, enable_hierarchy=False, auto_consolidate=False,
            load_from_disk=False,
            db_dir=str(tmp_path_factory.mktemp("overlap") / "db")),
        verbose=False, mesh=mesh)
    for t in range(TENANTS):
        ms.index.add([f"t{t}:f{j}" for j in range(TENANT_ROWS)], _rows(t),
                     [0.6] * TENANT_ROWS, [0.0] * TENANT_ROWS,
                     ["semantic"] * TENANT_ROWS, ["default"] * TENANT_ROWS,
                     f"t{t}")
    # both twins compiled before any client starts
    sched = ms._ensure_scheduler()
    for boost in (False, True):
        sched.submit(RetrievalRequest(query=_rows(0)[0], tenant="t0", k=K,
                                      boost=boost)).result(timeout=300)
    yield ms
    ms.close()


def _work(seed):
    """Per client: its tenant, and ROUNDS (fact, query) pairs."""
    out = []
    for c in range(CLIENTS):
        t = c % TENANTS
        rng = np.random.default_rng([seed, c])
        facts = rng.integers(0, TENANT_ROWS, ROUNDS)
        q = (_rows(t)[facts]
             + 0.05 * rng.standard_normal((ROUNDS, D)).astype(np.float32))
        out.append((t, facts, q))
    return out


def _closed_loop(ms, work, beside=None):
    """Every client sends its next query when its last returned; ``beside``
    runs on a thread of its own meanwhile. Returns results[c][round]."""
    sched = ms._ensure_scheduler()
    results = [[None] * ROUNDS for _ in work]
    errors = []
    start = threading.Barrier(len(work))

    def client(c):
        t, _, q = work[c]
        try:
            start.wait(timeout=60)
            for r in range(ROUNDS):
                results[c][r] = sched.submit(RetrievalRequest(
                    query=q[r], tenant=f"t{t}", k=K)).result(timeout=300)
        except BaseException as e:          # noqa: BLE001 — shown below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(len(work))]
    if beside is not None:
        threads.append(threading.Thread(target=beside))
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    assert not errors, errors[0]
    return results


def test_overlapped_answers_equal_the_serial_ones_and_the_reference(system):
    tel = system.telemetry
    work = _work(301)
    before = tel.counter_total("serve.overlapped_batches")
    got = _closed_loop(system, work)
    assert tel.counter_total("serve.overlapped_batches") > before
    assert tel.counter_total("serve.copy_dispatches") == 0
    # the same queries through ONE worker that overlaps nothing
    serial = QueryScheduler(system._serve_requests, max_batch=BATCH,
                            telemetry=Telemetry())
    try:
        for c, (t, _, q) in enumerate(work):
            futs = serial.submit_many([RetrievalRequest(
                query=v, tenant=f"t{t}", k=K) for v in q])
            for r, fut in enumerate(futs):
                one = fut.result(timeout=300)
                assert got[c][r].ids == one.ids, (c, r)
                # another padded batch, another program: the last bit of
                # an f32 sum may differ, nothing else
                assert got[c][r].scores == pytest.approx(one.scores,
                                                         abs=1e-6), (c, r)
        assert "serve.overlapped_batches" not in serial.telemetry.counters
    finally:
        serial.close()
    # and the plain reference, tenant by tenant
    cmp = reference.Comparison(LIMITS)
    live = np.ones(TENANT_ROWS, bool)
    for c, (t, facts, q) in enumerate(work):
        rows = reference.stored(_rows(t), "bfloat16")
        variants = reference.query_variants(rows, live, q, K, "bfloat16")
        for r, res in enumerate(got[c]):
            who = [nid.partition(":") for nid in res.ids]
            assert all(name == f"t{t}" for name, _, _ in who)
            cmp.answer(f"client {c} round {r}",
                       [int(f[1:]) for _, _, f in who], list(res.scores),
                       [tuple(v[r] for v in var) for var in variants], live)
    assert cmp.answers == CLIENTS * ROUNDS
    assert cmp.correct, cmp.first_fault


def test_boosting_request_among_overlapped_reads_donates_and_lands_once(
        system):
    idx, tel = system.index, system.telemetry
    cap = system.config.retrieval_cap
    sched = system._ensure_scheduler()
    before = np.asarray(idx.state.access_count).copy()
    overlapped = tel.counter_total("serve.overlapped_batches")
    facts = [11, 222, 333, 444, 555, 666]
    chats = []

    def chat():
        for f in facts:             # a chat turn: boosts its top rows
            chats.append(sched.submit(RetrievalRequest(
                query=_rows(1)[f], tenant="t1", k=cap, boost=True)
            ).result(timeout=300))

    _closed_loop(system, _work(302), beside=chat)
    sched.flush(timeout=60)
    assert tel.counter_total("serve.overlapped_batches") > overlapped
    assert tel.counter_total("serve.copy_dispatches") == 0
    assert [r.ids[0] for r in chats] == [f"t1:f{f}" for f in facts]
    assert all(r.boosted for r in chats)
    expect = before.copy()
    for r in chats:
        for nid in r.ids[:cap]:
            expect[idx.id_to_row[nid]] += 1
    after = np.asarray(idx.state.access_count)
    np.testing.assert_array_equal(after, expect)
    assert int((after - before).sum()) == len(facts) * cap


def test_index_vouches_for_pure_reads_only(system):
    idx = system.index
    read = [RetrievalRequest(query=_rows(0)[0], tenant="t0", k=K)]
    assert idx.reads_may_overlap(read) is True
    assert idx.reads_may_overlap(read + [RetrievalRequest(
        query=_rows(0)[1], tenant="t0", k=K, boost=True)]) is False
    idx.planner.budget_bytes, kept = 1 << 40, idx.planner.budget_bytes
    try:
        assert idx.reads_may_overlap(read) is False     # one dispatch's model
    finally:
        idx.planner.budget_bytes = kept
    idx._poisoned = True
    try:
        assert idx.reads_may_overlap(read) is False
    finally:
        idx._poisoned = False
    assert idx.reads_may_overlap(read) is True
