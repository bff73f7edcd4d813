"""Kernel phases are named for the device trace (PR 25; tier-1, CPU): the
exact ragged serving core and the fused dedup ingest carry ``jax.named_scope``
names in their operations' metadata — compile-time only, the results are the
program's own."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.utils.batching import RequestCarrier

D, Q = 16, 8


def _index():
    idx = MemoryIndex(dim=D, capacity=64, edge_capacity=255)
    rng = np.random.default_rng(3)
    emb = rng.standard_normal((12, D)).astype(np.float32)
    idx.add([f"n{i}" for i in range(12)], emb, [0.5] * 12, [0.0] * 12,
            ["semantic"] * 12, ["default"] * 12, "a")
    idx.add_edges([("n0", "n1", 0.7), ("n0", "n2", 0.7)], "a")
    return idx


def _serve_args(idx, boost=False):
    """State, CSR and the request carrier (ISSUE 37) of Q live queries of
    tenant "a": k = cap = 5, the gate off."""
    st = idx.state
    indptr, nbr = idx._csr_for(st)
    n = np.ones((Q,), np.int32)
    car = RequestCarrier.of(
        np.ones((Q, D), np.float32), valid=n, tenant=n * idx._tenants["a"],
        boost_on=n * boost, k=5 * n, cap=5 * n, now=1.0, super_gate=0.4,
        acc_boost=0.05, nbr_boost=0.02)
    return st, indptr, nbr, jnp.asarray(car.buf)


def _scopes(hlo_text):
    return set(re.findall(r'op_name="[^"]*?(lz\.[a-z]+)', hlo_text))


def test_read_core_names_its_phases():
    idx = _index()
    args = _serve_args(idx)
    low = S.search_fused_ragged_read.lower(*args, k=8, cap_take=5,
                                           max_nbr=4)
    # the read twin drops the neighbour gather (nothing reads it)
    assert _scopes(low.compile().as_text()) == {
        "lz.unpack", "lz.norms", "lz.scan", "lz.topk", "lz.gate", "lz.pack"}


def test_boosting_twin_of_the_same_core_names_the_csr_gather_too():
    idx = _index()
    args = _serve_args(idx, boost=True)
    low = S.search_fused_ragged_copy.lower(*args, k=8, cap_take=5,
                                           max_nbr=4)
    assert _scopes(low.compile().as_text()) >= {
        "lz.norms", "lz.scan", "lz.topk", "lz.gate", "lz.csr", "lz.pack"}


def test_fused_dedup_ingest_names_its_phases(monkeypatch):
    idx = _index()
    seen = {}
    real = idx._apply_dedup_fused

    def spy(*dev_args, **kw):
        fn = S.ingest_dedup_fused_copy
        low = fn.lower(idx.state, idx.edge_state, None, None, None, None,
                       *dev_args, k=kw["k"], shard_modes=kw["shard_modes"])
        seen["scopes"] = _scopes(low.compile().as_text())
        return real(*dev_args, **kw)

    monkeypatch.setattr(idx, "_apply_dedup_fused", spy)
    rng = np.random.default_rng(5)
    emb = rng.standard_normal((6, D)).astype(np.float32)
    pending = idx.ingest_batch_dedup(
        emb, [0.5] * 6, [0.0] * 6, ["semantic"] * 6, ["default"] * 6,
        tenant="a", dedup_gate=0.95, chain_weight=0.5, link_k=3,
        link_gate=0.5, link_scale=1.0, shard_modes=(1, 0), now=0.0)
    assert pending is not None
    assert seen["scopes"] >= {"lz.link", "lz.dedup", "lz.scatter"}


def test_scopes_leave_the_results_alone():
    """Same inputs, same packed readback as a scan written without scopes."""
    idx = _index()
    st, indptr, nbr, reqs = _serve_args(idx)
    packed = np.asarray(S.search_fused_ragged_read(
        st, indptr, nbr, reqs, k=8, cap_take=5, max_nbr=4))
    qn = S.normalize(jnp.ones((Q, D), jnp.float32)).astype(st.emb.dtype)
    scores = np.array(S.nt_dot(qn, st.emb), np.float32)
    live = (np.asarray(st.alive)
            & (np.asarray(st.tenant_id) == idx._tenants["a"]))
    scores[:, ~live] = -np.inf
    want = np.sort(scores[0])[::-1][:5]
    got = packed[0, 2:2 + 5].view(np.float32)
    np.testing.assert_array_equal(got, want)


def test_int8_read_core_names_the_coarse_scan_and_the_rescore():
    """ISSUE 36: the int8 family's program carries ``lz.scan_q8`` on the
    coarse scan's operations and ``lz.rescore`` on the survivors' gather,
    dot and top-k, beside the shared phases."""
    from lazzaro_tpu.ops.quant import quantize_rows
    idx = _index()
    st, *rest = _serve_args(idx)
    q8, scale = quantize_rows(st.emb)
    low = S.search_fused_quant_ragged_read.lower(
        st, q8, scale, *rest, k=8, slack=8, cap_take=5, max_nbr=4)
    scopes = set(re.findall(r'op_name="[^"]*?(lz\.[a-z0-9_]+)',
                            low.compile().as_text()))
    assert scopes == {"lz.unpack", "lz.norms", "lz.scan_q8", "lz.topk",
                      "lz.rescore", "lz.gate", "lz.pack"}


@pytest.mark.parametrize("cap,core", [(3 * 512 - 1, "blocked"),
                                      (64, "whole_pool")])
def test_ingest_select_counter_names_the_core(cap, core):
    """``ingest.select{core}`` (ISSUE 45), once a fused ingest dispatch —
    the dedup program and the plain one: ``blocked`` where a block tiles the
    pool, ``whole_pool`` where the pool is one block; in ``prometheus()``."""
    from lazzaro_tpu.utils.telemetry import Telemetry

    idx = MemoryIndex(dim=D, capacity=cap, edge_capacity=255,
                      telemetry=Telemetry())
    rng = np.random.default_rng(5)
    emb = rng.standard_normal((12, D)).astype(np.float32)
    pending = idx.ingest_batch_dedup(
        emb[:6], [0.5] * 6, [0.0] * 6, ["semantic"] * 6, ["default"] * 6,
        tenant="a", dedup_gate=0.95, now=0.0)
    idx.commit_ingest_dedup(pending, [f"n{i}" for i in range(6)])
    idx.ingest_batch([f"m{i}" for i in range(6)], emb[6:], [0.5] * 6,
                     [0.0] * 6, ["semantic"] * 6, ["default"] * 6, "a",
                     now=0.0)
    tel = idx.telemetry
    assert tel.counter_total("ingest.select") == 2
    lines = [ln for ln in tel.prometheus().splitlines()
             if ln.startswith("lazzaro_ingest_select_total")]
    assert lines == [f'lazzaro_ingest_select_total{{core="{core}"}} 2']


def test_classic_link_scan_is_the_same_core_without_its_probe():
    """``arena_link_candidates_multi`` (the plain fused ingest's post-add
    scan, and the classic path's) at a pool three blocks tile: no
    ``[facts, rows]`` buffer in the compiled program, the new rows excluded,
    and with one shard both modes give the same lists."""
    idx = MemoryIndex(dim=D, capacity=3 * 512 - 1, edge_capacity=255)
    rng = np.random.default_rng(3)
    idx.add([f"n{i}" for i in range(12)],
            rng.standard_normal((12, D)).astype(np.float32), [0.5] * 12,
            [0.0] * 12, ["semantic"] * 12, ["default"] * 12, "a")
    st = idx.state
    rows = jnp.asarray(S.pad_rows(np.asarray([0, 1], np.int32), st.capacity))
    args = (st, rows, rows, jnp.int32(idx._tenants["a"]), 3, (1, 0))
    text = S.arena_link_candidates_multi.lower(*args).compile().as_text()
    assert f"[{len(rows)},512]" in text                # one block's tile
    assert f"[{len(rows)},{st.emb.shape[0]}]" not in text
    s1, r1, s0, r0 = (np.asarray(a)
                      for a in S.arena_link_candidates_multi(*args))
    assert ((r1[:2] >= 2) & (r1[:2] < 12)).all()
    assert (s1[:2] > S.NEG_INF / 2).all()
    np.testing.assert_array_equal(r1[:2], r0[:2])
