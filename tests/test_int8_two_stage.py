"""The int8 deployment against its plain reference (ISSUE 36; tier-1, CPU,
small sizes, seeded random rows): ``MemorySystem(int8_serving=True)`` through
the ``QueryScheduler`` against ``benchmark/reference_two_stage_q8.py`` — ids,
ranks and scores inside the cell's limits, several tenants, ragged k — on one
device and with the arena sharded over 2 and 4 CPU devices; and the ways the
deployment's promise can be cut, each of which has to FAIL the comparison:
the rescore skipped (coarse scores served), the rescore made from the codes
instead of the master, the slack cut to 0, another tenant's rows admitted.

The corpus is BUILT TO NEED THE TWO STAGES: each tenant's rows climb a ladder
of exact scores 4e-4 apart (twice the score limit) and carry a spike that
makes their int8 scores ~1e-3 wrong, so the coarse ranking is the exact one
shuffled by a few places — the exact top-k lies inside the coarse top-(k +
slack) and not inside the coarse top-k. The serving ceiling is k = 16 with the
default slack 8, so the coarse fetch is 24 and every step is cheap on a CPU.
No number read here is a device number."""

import os
import sys

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:0] = [ROOT]

from benchmark import files  # noqa: E402
from lazzaro_tpu import MemorySystem  # noqa: E402
from lazzaro_tpu.config import MemoryConfig  # noqa: E402
from lazzaro_tpu.parallel.mesh import make_mesh  # noqa: E402
from lazzaro_tpu.serve.scheduler import RetrievalRequest  # noqa: E402

D, K_MAX, SLACK = 32, 16, 8
SIZES = (96, 64, 128, 80)           # rows a tenant
LIMITS = {"score_gap": 2e-4, "rank_errors": 0, "foreign_ids": 0,
          "count_errors": 0, "unanswered": 0, "swallowed": 0}
KS = (16, 3, 16, 7, 1, 16, 12, 5)   # the ragged k of a tenant's requests


@pytest.fixture()
def ref(monkeypatch):
    """The deployment's reference, its coarse fetch set to this geometry's
    (the configuration's file states 136 for the published one)."""
    mod = files.load_module("benchmark/reference_two_stage_q8.py")
    assert mod.coarse_fetch() == 136
    monkeypatch.setattr(mod, "coarse_fetch", lambda: K_MAX + SLACK)
    return mod


def ladder(rng, n, u):
    """[n, D] bf16-exact f32 rows around the unit direction ``u``: cosines
    0.95, 0.9496, ... and one spiked component each."""
    cos = 0.95 - 4e-4 * np.arange(n)
    w = 0.25 * rng.standard_normal((n, D))
    w[np.arange(n), rng.integers(0, D, n)] += rng.choice([-1.0, 1.0], n)
    w -= (w @ u)[:, None] * u[None, :]
    w /= np.linalg.norm(w, axis=1, keepdims=True)
    v = cos[:, None] * u[None, :] + np.sqrt(1 - cos ** 2)[:, None] * w
    return rng.permutation(v.astype(np.float32)).astype(
        ml_dtypes.bfloat16).astype(np.float32)


def corpus(seed):
    rng = np.random.default_rng(seed)
    dirs = [v / np.linalg.norm(v) for v in rng.standard_normal((len(SIZES), D))]
    rows = [ladder(rng, n, u) for n, u in zip(SIZES, dirs)]
    queries = [(u[None, :] + 0.03 * rng.standard_normal((len(KS), D))
                ).astype(np.float32) for u in dirs]
    return rows, queries


def build(tmp_path, rows, mesh=None, **cfg):
    """A system whose arena holds exactly ``rows`` (tenant t's row j is
    ``t{t}:{j}``), installed as the benchmark installs its rows: beside
    ``add``'s bookkeeping, so that no second normalisation touches them."""
    ms = MemorySystem(
        config=MemoryConfig(
            embed_dim=D, dtype="bfloat16", int8_serving=True,
            serve_k_max=K_MAX, serve_batch_max=8, enable_async=False,
            enable_hierarchy=False, auto_consolidate=False,
            load_from_disk=False, journal=False,
            initial_capacity=1000 if mesh is None else 4095,
            db_dir=str(tmp_path / "db"), **cfg),
        verbose=False, mesh=mesh)
    idx = ms.index
    for t, mine in enumerate(rows):
        n = len(mine)
        idx.add([f"t{t}:{j}" for j in range(n)], mine, [0.5] * n, [0.0] * n,
                ["semantic"] * n, ["default"] * n, f"t{t}")
    at = np.asarray([idx.id_to_row[f"t{t}:{j}"] for t, mine in enumerate(rows)
                     for j in range(len(mine))], np.int32)
    st = idx.state
    idx.state = st.replace(emb=st.emb.at[jnp.asarray(at)].set(
        jnp.asarray(np.concatenate(rows), st.emb.dtype)))
    idx._int8_dirty = True
    ms._ensure_scheduler()
    # one request alone first: the shadow is built once, by one worker
    ms.query_scheduler.submit(RetrievalRequest(
        query=np.ones((D,), np.float32), tenant="t0", k=1)).result(timeout=120)
    return ms


def serve(ms, queries):
    """Every tenant's requests through the scheduler, mixed in one stream."""
    reqs = [RetrievalRequest(query=q[i], tenant=f"t{t}", k=KS[i])
            for i in range(len(KS)) for t, q in enumerate(queries)]
    futs = ms.query_scheduler.submit_many(reqs)
    return reqs, [f.result(timeout=120) for f in futs]


def compare(ref, rows, queries, reqs, results):
    cmp = ref.Comparison(LIMITS)
    by_tenant = {}
    for r, res in zip(reqs, results):
        by_tenant.setdefault(int(r.tenant[1:]), []).append((r, res))
    for t, pairs in sorted(by_tenant.items()):
        live = np.ones(len(rows[t]), bool)
        for r, res in pairs:
            variants = ref.query_variants(rows[t], live, r.query[None, :],
                                          r.k, "bfloat16")
            idx, sc = [], []
            for nid, score in zip(res.ids, res.scores):
                who, _, j = nid.partition(":")
                if who != r.tenant:
                    cmp.foreign(f"{r.tenant} k={r.k}", repr(nid))
                else:
                    idx.append(int(j))
                    sc.append(score)
            cmp.answer(f"{r.tenant} k={r.k}", idx, sc,
                       [tuple(v[0] for v in var) for var in variants], live)
    return cmp


def test_the_corpus_needs_both_stages(ref):
    """What the reference says of this corpus, before any program runs: the
    two-stage answer is the exact one, and the coarse top-k alone is not."""
    exact = files.load_module("benchmark/reference.py")
    rows, queries = corpus(7)
    short = agree = 0
    for mine, q in zip(rows, queries):
        live = np.ones(len(mine), bool)
        want = exact.topk_exact(mine, live, ref.unit(q), K_MAX)[1]
        two = ref.topk_two_stage(mine, live, ref.unit(q), ref.unit(q), K_MAX,
                                 K_MAX + SLACK)[1]
        bare = ref.topk_two_stage(mine, live, ref.unit(q), ref.unit(q), K_MAX,
                                  K_MAX)[1]
        agree += int((want == two).all())
        short += int((np.sort(want, 1) != np.sort(bare, 1)).any(1).sum())
    assert agree == len(rows) and short >= len(rows) * len(KS) // 2


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_system_through_the_scheduler_agrees_with_the_reference(
        tmp_path, ref, seed):
    rows, queries = corpus(seed)
    ms = build(tmp_path, rows)
    try:
        reqs, results = serve(ms, queries)
        tel = ms.telemetry
        assert tel.counters.get('serve.dispatches{mode="quant"}', 0) >= 1
        assert tel.counter_total("serve.dispatches") == \
            tel.counters['serve.dispatches{mode="quant"}']
        assert tel.counter_total("index.shadow_builds") == 1
    finally:
        ms.close()
    assert [len(r.ids) for r in results] == [q.k for q in reqs]
    cmp = compare(ref, rows, queries, reqs, results)
    assert cmp.answers == len(reqs) and cmp.correct, (cmp.numbers(),
                                                      cmp.first_fault)
    assert cmp.score_gap <= 1e-6            # bf16 products are exact in f32


@pytest.mark.parametrize("chips", [2, 4])
def test_sharded_system_agrees_with_the_reference(tmp_path, ref, chips):
    """The arena row-sharded over 2 and 4 CPU devices: every chip fetches
    its own coarse top-(k + slack), so the merged answer rests on a SUPERSET
    of the one-chip coarse set — on this corpus, where the one-chip answer is
    already the exact one, the same answer."""
    rows, queries = corpus(7)
    mesh = make_mesh(("data",), (chips,), devices=jax.devices()[:chips])
    ms = build(tmp_path, rows, mesh=mesh)
    try:
        assert len(ms.index.state.emb.sharding.device_set) == chips
        reqs, results = serve(ms, queries)
        modes = {k for k in ms.telemetry.counters
                 if k.startswith("serve.dispatches")}
        assert modes == {'serve.dispatches{mode="sharded_quant"}'}
        shadow = ms.index._int8_shadow
        assert len(shadow[0].sharding.device_set) == chips
    finally:
        ms.close()
    cmp = compare(ref, rows, queries, reqs, results)
    assert cmp.answers == len(reqs) and cmp.correct, (cmp.numbers(),
                                                      cmp.first_fault)


# ------------------------------------------------- the promise, cut four ways

def _coarse_scores_served(ms):
    """No rescore: the coarse stage's order and its int8 scores go out."""
    real = ms.index.search_fused_requests
    from lazzaro_tpu.ops.quant import quantize_rows

    def coarse(reqs, **kw):
        out = real(reqs, **kw)
        q8, scale = ms.index._int8_shadow
        for r, res in zip(reqs, out):
            at = jnp.asarray([ms.index.id_to_row[i] for i in res.ids])
            qq, qs = quantize_rows(jnp.asarray(r.query / np.linalg.norm(r.query))[None])
            dots = (q8[at].astype(jnp.int32) @ qq[0].astype(jnp.int32))
            res.scores = [float(x) for x in
                          dots.astype(jnp.float32) * qs[0] * scale[at]]
        return out
    ms.index.search_fused_requests = coarse


def _rescore_from_the_codes(ms):
    """The master overwritten by what the codes say of it: every rescore
    then reads code precision."""
    idx = ms.index
    q8, scale = idx._int8_shadow_for(idx.state)
    st = idx.state
    idx.state = st.replace(emb=(q8.astype(jnp.float32)
                                * scale[:, None]).astype(st.emb.dtype))
    idx._int8_dirty = False             # the shadow stays as it was


def _slack_cut_to_zero(ms):
    ms.index.coarse_slack = 0


def _wrong_tenants_rows(ms):
    st = ms.index.state
    ms.index.state = st.replace(tenant_id=jnp.roll(st.tenant_id, SIZES[0]))
    ms.index._int8_dirty = False


@pytest.mark.parametrize("fault,number", [
    (_coarse_scores_served, "score_gap"),
    (_rescore_from_the_codes, "score_gap"),
    (_slack_cut_to_zero, "rank_errors"),
    (_wrong_tenants_rows, "foreign_ids"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_cut_promise_fails_the_comparison(tmp_path, ref, fault, number):
    rows, queries = corpus(7)
    ms = build(tmp_path, rows)
    try:
        fault(ms)
        reqs, results = serve(ms, queries)
    finally:
        ms.close()
    cmp = compare(ref, rows, queries, reqs, results)
    v = cmp.numbers()[number]
    assert not cmp.correct and v["value"] > v["limit"], cmp.numbers()
