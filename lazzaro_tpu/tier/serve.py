"""The host half of tiered serving: decode dispatch 1, finish cold hits.

Dispatch 1 (``state.search_fused_tiered*``) scanned the FULL corpus
through the int8 shadow and returned each query's k+slack candidate
window — exact scores for hot rows, coarse scores for cold rows, boosts
applied in-kernel for queries whose window is all-hot. This module:

1. decodes hot-only queries straight from the packed readback (their
   scores are final — ONE dispatch total);
2. for cold-hit queries, gathers the cold candidates' exact rows from the
   host :class:`~lazzaro_tpu.tier.ColdStore` and runs ONE bounded second
   dispatch — ``state.tier_cold_finish`` (exact rescore + final re-rank +
   the deferred gate/CSR/boost tail) when any of them asked for boosts,
   else the read-only ``state.tier_cold_rescore`` — never a full-arena
   fault-in;
3. feeds the tier telemetry (cold-hit rate, promotion hit counters).

Shared by ``core.index.MemoryIndex`` (single chip AND mesh — the finish
kernel is plain jnp under jit, so GSPMD partitions it against the
row-sharded arena with a replicated flat CSR) and
``parallel.index.ShardedMemoryIndex``.
"""

from __future__ import annotations

import sys
from typing import List

import numpy as np

NEG_INF = -1e30


def _packed_k(host: np.ndarray) -> int:
    """Candidate width of a packed retrieval readback: the layout is
    [gate_s, gate_r, k·ann_s, k·ann_r, fast, 5 counters]."""
    return (host.shape[1] - 8) // 2


def tiered_decode_and_finish(index, tm, reqs, results, valid, boost_on,
                             q_np, tenants, host, *, k_bucket: int,
                             cap_take: int, max_nbr: int, acc_boost: float,
                             nbr_boost: float, now_rel: float,
                             cap_arr: np.ndarray, tel) -> List:
    """Decode a tiered dispatch-1 readback and finish cold-hit queries
    with at most ONE more bounded dispatch. Mutates ``results`` in place
    and returns it."""
    import jax.numpy as jnp

    from lazzaro_tpu.core import state as S
    from lazzaro_tpu.utils.batching import (decode_topk, pad_to_bucket,
                                            unpack_retrieval)

    nq = len(reqs)
    cap = len(tm.cold_np) - 1
    k_unpack = _packed_k(host)
    gate_s, gate_r, ann_s, ann_r, fast, counters = unpack_retrieval(
        host[:nq], k_unpack)
    live = ann_s > NEG_INF / 2
    coldf = tm.is_cold_rows(ann_r) & live
    coldq = coldf.any(axis=1) & valid[:nq]

    # ---- hot-only queries: dispatch 1's scores are final ----------------
    for i, r in enumerate(reqs):
        if not valid[i] or coldq[i]:
            continue
        res = results[i]
        ids, scores = decode_topk(ann_s[i:i + 1], ann_r[i:i + 1],
                                  index.row_to_id, NEG_INF,
                                  limit=min(int(r.k), cap),
                                  lengths=counters[i:i + 1, 0])[0]
        res.ids, res.scores = ids, scores
        if gate_s[i] > NEG_INF / 2:
            res.gate_id = index.row_to_id.get(int(gate_r[i]))
            res.gate_score = float(gate_s[i])
        res.fast = bool(fast[i])
        res.boosted = bool(boost_on[i] and not fast[i])

    cidx = np.nonzero(coldq)[0]
    tm.note_turns(int(valid[:nq].sum()), len(cidx))
    if len(cidx) == 0:
        return results

    # ---- cold-hit queries: ONE bounded finish dispatch ------------------
    c2 = len(cidx)
    dim = q_np.shape[1]
    arena_dt = tm.stores[0].dtype
    gran = getattr(index, "serve_pad_granularity", 8)
    pad_c = len(pad_to_bucket(np.zeros((c2, 1)), gran))
    rows2 = np.full((pad_c, k_unpack), cap, np.int32)
    s2 = np.full((pad_c, k_unpack), NEG_INF, np.float32)
    m2 = np.zeros((pad_c, k_unpack), bool)
    q2 = np.zeros((pad_c, dim), np.float32)
    ten2 = np.full((pad_c,), -1, np.int32)
    gs2 = np.full((pad_c,), NEG_INF, np.float32)
    gr2 = np.full((pad_c,), cap, np.int32)
    fast2 = np.zeros((pad_c,), bool)
    boost2 = np.zeros((pad_c,), bool)
    capq2 = np.zeros((pad_c,), np.int32)
    for j, i in enumerate(cidx):
        rows2[j] = ann_r[i]
        s2[j] = ann_s[i]
        m2[j] = coldf[i]
        q2[j] = q_np[i]
        ten2[j] = tenants[i]
        gs2[j] = gate_s[i]
        gr2[j] = gate_r[i]
        fast2[j] = fast[i]
        boost2[j] = boost_on[i]
        capq2[j] = int(cap_arr[i])
    vecs2 = np.zeros((pad_c, k_unpack, dim), arena_dt)
    flat = np.nonzero(m2)
    if len(flat[0]):
        vecs2[flat] = tm.gather_cold(rows2[flat].tolist())

    k_dec = min(int(k_bucket), k_unpack)
    any_boost = bool(boost2.any())
    dev = lambda a: jnp.asarray(a)       # noqa: E731
    with tel.span("serve.tiered_cold", timer="serve.dispatch_ms",
                  labels={"mode": "tiered_cold"}):
        if any_boost:
            indptr_f, nbr_f = index._flat_csr_for()
            with index._state_lock:
                cur = index.state
                sole = sys.getrefcount(cur) <= index._SOLE_REFS
                new_state, packed2 = index._guarded(
                    lambda fn: fn(
                        cur, indptr_f, nbr_f, dev(q2), dev(ten2), dev(rows2),
                        dev(s2), dev(m2), dev(vecs2), dev(gs2), dev(gr2),
                        dev(fast2), dev(boost2), dev(capq2),
                        jnp.float32(now_rel), jnp.float32(acc_boost),
                        jnp.float32(nbr_boost), k=k_dec, cap_take=cap_take,
                        max_nbr=max_nbr),
                    S.tier_cold_finish, S.tier_cold_finish_copy, sole, (cur,),
                    "serve_tiered_cold")
                del cur
                index.state = new_state
        else:
            packed2 = S.tier_cold_rescore(
                dev(q2), dev(rows2), dev(s2), dev(m2), dev(vecs2), dev(gs2),
                dev(gr2), dev(fast2), k=k_dec, sentinel=cap)
        host2 = np.asarray(packed2)          # the ONE finish readback
    tel.bump("serve.dispatches", labels={"mode": "tiered_cold"})
    _, _, ann_s2, ann_r2, _, counters2 = unpack_retrieval(host2[:c2],
                                                          k_dec)
    hit_rows: List[int] = []
    for j, i in enumerate(cidx):
        r = reqs[i]
        res = results[i]
        ids, scores = decode_topk(ann_s2[j:j + 1], ann_r2[j:j + 1],
                                  index.row_to_id, NEG_INF,
                                  limit=min(int(r.k), cap))[0]
        res.ids, res.scores = ids, scores
        if gs2[j] > NEG_INF / 2:
            res.gate_id = index.row_to_id.get(int(gr2[j]))
            res.gate_score = float(gs2[j])
        res.fast = bool(fast2[j])
        res.boosted = bool(boost2[j] and not fast2[j])
        kq = min(int(r.k), k_dec)
        final = ann_r2[j][:kq][ann_s2[j][:kq] > NEG_INF / 2]
        cold_final = [int(x) for x in final if tm.cold_np[int(x)]]
        res.cold_hits = len(cold_final)
        hit_rows.extend(cold_final)
    if hit_rows:
        tm.note_cold_hits(hit_rows)
    return results
