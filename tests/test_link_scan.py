"""The write path's select-while-scanning core (ISSUE 45; tier-1, CPU).

``ops/pallas_topk.blocked_link_scan`` — per fact the dedup probe's top-1 and
one top-``k`` per link mode (same shard, any shard, another shard), selected
while the pool streams in blocks — against a NumPy f32 oracle written here:
same scores (bf16 rows × the arena-dtype fact, f32 accumulate), ties to the
lowest pool row in every tier, ``(NEG, sentinel)`` in a slot no candidate
fills. The Pallas vehicle runs in interpret mode at small shapes only. The
whole dispatch (``ingest_batch_dedup`` over a seeded arena) is held to what
the tree's dense ``[facts, rows]`` scan gave before it was deleted:
``tests/data/ingest_dedup_golden.json``, frozen from commit d42b5f3 by
``python tests/test_link_scan.py <file>``."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.ops import pallas_topk as PT

NEG = np.float32(S.NEG_INF)
GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "ingest_dedup_golden.json")


def _unit(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


# ------------------------------------------------------ the core and its oracle

BLK = PT.SELECT_BLOCK


def _arena(rows, d=32, tenants=3, shards=3, seed=0, dead=0.1, supers=0.05,
           paged=0, dtype=jnp.bfloat16):
    """An ArenaState of ``rows`` pool rows built column by column (the last
    row is the dead sentinel). ``paged`` > 0: that many LOGICAL rows behind
    a shuffled ``row_map`` with free pool slots."""
    rng = np.random.default_rng(seed)
    emb = _unit(rng, rows, d)
    emb[-1] = 0.0
    n = paged or rows
    alive = rng.random(n) >= dead
    alive[-1] = False
    st = S.init_arena(n - 1, d, dtype).replace(
        emb=jnp.asarray(emb, dtype),
        tenant_id=jnp.asarray(rng.integers(0, tenants, n), jnp.int32),
        shard_id=jnp.asarray(rng.integers(0, shards, n), jnp.int32),
        alive=jnp.asarray(alive),
        is_super=jnp.asarray(rng.random(n) < supers))
    if paged:
        slots = rng.permutation(rows - 1)[:n - 1].astype(np.int32)
        row_map = np.append(slots, rows - 1).astype(np.int32)
        inv = np.full((rows,), -1, np.int32)
        inv[row_map] = np.arange(n)
        st = st.replace(row_map=jnp.asarray(row_map),
                        inv_map=jnp.asarray(inv))
    return st


def _batch(st, n, seed, shards=3):
    """``n`` facts: normalized arena-dtype embeddings, their shards, and the
    logical rows they would take (excluded from the link tiers)."""
    rng = np.random.default_rng(seed)
    qd = jnp.asarray(_unit(rng, n, st.dim), st.emb.dtype)
    q_shard = rng.integers(0, shards, n).astype(np.int32)
    rows = rng.choice(st.capacity, size=n, replace=False).astype(np.int32)
    return qd, q_shard, rows


def _oracle(st, qd, q_shard, tenant, rows, k, modes, with_probe=True):
    """NumPy f32 over what the arena stores; ties by POOL row; a slot no
    candidate fills is ``(NEG, capacity)``. The flat tuple of
    ``S._ingest_scan_core``."""
    emb = np.asarray(st.emb.astype(jnp.float32))
    scores = np.asarray(qd.astype(jnp.float32)) @ emb.T
    pool_n, cap = emb.shape[0], st.capacity
    cols = [np.asarray(c) for c in (st.alive, st.tenant_id, st.is_super,
                                    st.shard_id)]
    excl = np.zeros((cap + 1,), bool)
    excl[rows] = True
    excl[cap] = True
    sent = np.arange(cap + 1) == cap
    if st.row_map is not None:
        inv = np.asarray(st.inv_map)
        bound, at = inv >= 0, np.maximum(inv, 0)
        alive, ten, sup, shard = (c[at] for c in cols)
        alive, excl, sent = alive & bound, excl[at], sent[at]
        logical = np.where(bound, inv, cap)
    else:
        alive, ten, sup, shard = cols
        logical = np.arange(pool_n)
    pmask = alive & (ten == tenant) & ~sup & ~sent
    lmask = pmask & ~excl
    b = scores.shape[0]

    def top(mask_of, width):
        out_s = np.full((b, width), NEG)
        out_r = np.full((b, width), cap)
        for i in range(b):
            at = np.flatnonzero(mask_of(i))
            order = at[np.lexsort((at, -scores[i, at]))][:width]
            out_s[i, :len(order)] = scores[i, order]
            out_r[i, :len(order)] = logical[order]
        return out_s, out_r

    flat = list(top(lambda i: pmask, 1)) if with_probe else []
    for sm in modes:
        flat.extend(top(
            lambda i: lmask & {0: True, 1: shard == q_shard[i]}.get(
                sm, shard != q_shard[i]), k))
    return flat


def _check(got, want):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        if i % 2:
            np.testing.assert_array_equal(np.asarray(g), w)
        else:
            np.testing.assert_allclose(np.asarray(g), w, atol=2e-6, rtol=0)


def _core(st, qd, q_shard, tenant, rows, k, modes, with_probe=True):
    cap = st.capacity
    probe_excl = jnp.arange(cap + 1) == cap
    link_excl = jnp.zeros((cap + 1,), bool).at[rows].set(True) | probe_excl
    return jax.jit(S._ingest_scan_core, static_argnums=(6, 7, 8))(
        st, qd, jnp.asarray(q_shard), probe_excl, link_excl,
        jnp.int32(tenant), k, modes, with_probe)


CASES = {
    # pools: one block, several blocks, a row count the block does not divide
    "one_block": dict(rows=300, batch=8, modes=(1, 0)),
    "several_blocks": dict(rows=3 * BLK, batch=24, modes=(1, 0)),
    "indivisible": dict(rows=2 * BLK + 100, batch=8, modes=(1, 0)),
    "half_block_pool": dict(rows=3 * 1024, batch=16, modes=(1, 0)),
    # the other-shard mode, alone and beside the others
    "other_shard_blocks": dict(rows=2 * BLK, batch=8, modes=(2,)),
    "other_shard_one_block": dict(rows=500, batch=8, modes=(2,)),
    "three_modes": dict(rows=5 * 512, batch=8, modes=(1, 0, 2)),
    # the non-dedup program's scan: the link tiers alone
    "no_probe_blocks": dict(rows=2 * BLK, batch=8, modes=(1, 0),
                            with_probe=False),
    "no_probe_one_block": dict(rows=700, batch=8, modes=(0,),
                               with_probe=False),
    # a tenant with fewer rows than k in a shard; an f32 arena; k = 1
    "short_tenants": dict(rows=4 * 512, batch=8, modes=(1, 0), tenants=150),
    "f32_arena": dict(rows=4 * 512, batch=8, modes=(1, 0),
                      dtype=jnp.float32),
    "k1": dict(rows=4 * 512, batch=8, modes=(1, 0), k=1),
    # a paged pool scans in pool space
    "paged_blocks": dict(rows=2 * BLK, batch=8, modes=(1, 0), paged=5000),
    "paged_one_block": dict(rows=600, batch=8, modes=(2,), paged=400),
    # a batch the scan takes in pieces
    "batch_in_pieces": dict(rows=3 * 512, batch=PT._MAX_QUERIES + 40,
                            modes=(1, 0)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_core_matches_numpy_oracle(name):
    case = dict(CASES[name])
    tenants = case.get("tenants", 3)
    st = _arena(case["rows"], tenants=tenants, seed=len(name),
                paged=case.get("paged", 0),
                dtype=case.get("dtype", jnp.bfloat16))
    qd, q_shard, rows = _batch(st, case["batch"], seed=7)
    k, modes = case.get("k", 3), case["modes"]
    with_probe = case.get("with_probe", True)
    got = _core(st, qd, q_shard, 1, rows, k, modes, with_probe)
    _check(got, _oracle(st, qd, q_shard, 1, rows, k, modes, with_probe))


def _columns(st, tenant, rows):
    """The two key columns ``S._ingest_scan_core`` builds, in NumPy (a
    dense arena)."""
    cap = st.capacity
    pmask = (np.asarray(st.alive) & (np.asarray(st.tenant_id) == tenant)
             & ~np.asarray(st.is_super) & (np.arange(cap + 1) < cap))
    lmask = pmask.copy()
    lmask[rows] = False
    return (jnp.asarray(np.where(pmask, 0, PT.ROW_DEAD), jnp.int32),
            jnp.asarray(np.where(lmask, np.asarray(st.shard_id),
                                 PT.ROW_DEAD), jnp.int32))


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_ties_across_a_block_boundary_go_to_the_lowest_row(impl):
    """The same vector at the last row of one block, the first of the
    next and deep in a third: equal scores, ascending rows, in the probe
    and in every link tier — and a copy in another shard shows only where
    the mode lets it."""
    blk, d = 512, 32
    rng = np.random.default_rng(2)
    n = 5 * blk
    emb = _unit(rng, n, d)
    twins = [blk - 1, blk, 2 * blk + 7, 3 * blk + 1]
    emb[twins] = emb[twins[0]]
    shard = np.zeros((n,), np.int32)
    shard[twins[3]] = 1
    shard[-1] = PT.ROW_DEAD
    probe = np.where(shard == PT.ROW_DEAD, PT.ROW_DEAD, 0)
    q = jnp.asarray(np.tile(emb[twins[0]], (8, 1)), jnp.bfloat16)
    p_s, p_r, s1, r1, s0, r0, s2, r2 = (np.asarray(a) for a in (
        PT.blocked_link_scan(
            jnp.asarray(emb, jnp.bfloat16), q, jnp.asarray(shard),
            jnp.zeros((8,), jnp.int32), 4, (1, 0, 2),
            jnp.asarray(probe, jnp.int32), impl=impl)))
    assert (p_r[:, 0] == twins[0]).all() and (p_s[:, 0] == s1[:, 0]).all()
    assert r1[0, :3].tolist() == twins[:3] and twins[3] not in r1[0]
    assert s1[0, 0] == s1[0, 1] == s1[0, 2] > s1[0, 3]
    assert r0[0].tolist() == twins
    assert s0[0, 0] == s0[0, 3]
    assert r2[0, 0] == twins[3] and (s2[0, 1:] == NEG).all()
    assert (r2[0, 1:] == n - 1).all()


@pytest.mark.parametrize("impl", ["jax", "pallas"])
def test_short_and_pad_facts_return_the_sentinel(impl):
    """A fact whose shard holds two candidate rows fills two slots of its
    same-shard list and leaves ``(NEG, sentinel)`` in the third; a fact of
    a shard nobody holds, and the pad facts the kernel's tile adds (they
    ask nothing: k 0), leave it in every slot of every tier."""
    blk, d, c, k = 512, 32, 5, 3
    n = 3 * blk
    rng = np.random.default_rng(8)
    emb = jnp.asarray(_unit(rng, n, d), jnp.bfloat16)
    shard = rng.integers(0, 2, n).astype(np.int32)
    shard[[40, blk + 3]] = 7                       # two rows of shard 7
    shard[-1] = PT.ROW_DEAD
    row_link = jnp.asarray(shard)
    row_probe = jnp.where(row_link == PT.ROW_DEAD, PT.ROW_DEAD, 0)
    qn = jnp.asarray(_unit(rng, c, d), jnp.bfloat16)
    q_shard = jnp.asarray([0, 1, 7, 9, 0], jnp.int32)
    flat = [np.asarray(a) for a in PT.blocked_link_scan(
        emb, qn, row_link, q_shard, k, (1, 0), row_probe, impl=impl)]
    s1, r1 = flat[2], flat[3]
    assert sorted(r1[2, :2].tolist()) == [40, blk + 3]
    assert s1[2, 2] == NEG and r1[2, 2] == n - 1
    assert (s1[3] == NEG).all() and (r1[3] == n - 1).all()
    assert (flat[4][3] > NEG / 2).all()            # any shard: it has rows
    # the vehicle itself, padded as the entry point pads it: 5 facts -> 16
    cp, kp = (16, 128) if impl == "pallas" else (8, k)
    pad = cp - c
    args = (emb, jnp.pad(qn, ((0, pad), (0, 0))), row_probe, row_link,
            jnp.pad(q_shard, (0, pad))[:, None],
            jnp.pad(jnp.full((c,), k, jnp.int32), (0, pad))[:, None],
            k, (1, 0, 2), kp, blk, n - 1)
    raw = (PT._link_scan_pallas(*args, interpret=True) if impl == "pallas"
           else PT._link_scan_jax(*args))
    for i, a in enumerate(raw):
        tail = np.asarray(a)[c:]
        assert (tail == (n - 1 if i % 2 else NEG)).all(), i


@pytest.mark.parametrize("batch,modes,with_probe", [
    (16, (1, 0), True), (100, (1, 0), True), (128, (1, 0), True),
    (PT._MAX_QUERIES + 12, (1, 0), True), (24, (2,), True),
    (24, (1, 0, 2), False)])
def test_pallas_vehicle_in_interpret_mode_matches_the_loop(batch, modes,
                                                           with_probe):
    """Both vehicles run the same steps: same rows, and scores that differ
    by the CPU gemm's rounding at most (the kernel pads 100 facts to 112),
    at a shape small enough for interpret mode (3 blocks of 512)."""
    st = _arena(3 * 512, seed=batch)
    qd, q_shard, rows = _batch(st, batch, seed=batch + 1)
    row_probe, row_link = _columns(st, 1, rows)
    outs = [PT.blocked_link_scan(
        st.emb, qd, row_link, jnp.asarray(q_shard), 3, modes,
        row_probe if with_probe else None, impl=impl)
        for impl in ("jax", "pallas")]
    assert len(outs[0]) == 2 * len(modes) + 2 * with_probe
    _check(outs[1], [np.asarray(a) for a in outs[0]])
    want = _oracle(st, qd, q_shard, 1, rows, 3, modes, with_probe)
    _check(outs[0], want)


def test_pallas_vehicle_refuses_a_pool_no_block_tiles():
    with pytest.raises(ValueError, match="no block tiles"):
        PT.blocked_link_scan(jnp.zeros((700, 32), jnp.bfloat16),
                             jnp.zeros((8, 32), jnp.bfloat16),
                             jnp.zeros((700,), jnp.int32),
                             jnp.zeros((8,), jnp.int32), 3, (1, 0),
                             jnp.zeros((700,), jnp.int32), impl="pallas")


def test_planner_mirrors_the_piece_size():
    from lazzaro_tpu.plan import model
    assert model.LINK_SCAN_FACTS == PT._MAX_QUERIES


def test_a_block_without_candidates_is_skipped_not_scored():
    """The per-block flag: rows of the scanned tenant in ONE block of five;
    NaN embeddings everywhere else would poison any score computed from
    them — the lists come back clean, and equal to a scan of that block's
    rows alone."""
    blk, d = 512, 32
    n = 5 * blk
    rng = np.random.default_rng(3)
    emb = np.full((n, d), np.nan, np.float32)
    lo = 2 * blk
    emb[lo:lo + blk] = _unit(rng, blk, d)
    shard = np.full((n,), PT.ROW_DEAD, np.int32)
    shard[lo + 5:lo + 200] = rng.integers(0, 3, 195)
    row_link = jnp.asarray(shard)
    row_probe = jnp.where(row_link == PT.ROW_DEAD, PT.ROW_DEAD, 0)
    qn = jnp.asarray(_unit(rng, 8, d), jnp.bfloat16)
    q_shard = jnp.asarray(rng.integers(0, 3, 8), jnp.int32)
    for impl in ("jax", "pallas"):
        flat = [np.asarray(a) for a in PT.blocked_link_scan(
            jnp.asarray(emb, jnp.bfloat16), qn, row_link, q_shard, 3,
            (1, 0), row_probe, impl=impl)]
        assert all(np.isfinite(a).all() for a in flat[::2])
        assert all(((a >= lo + 5) & (a < lo + 200) | (a == n - 1)).all()
                   for a in flat[1::2])
        assert (flat[0] > NEG / 2).all()


def test_shard_local_call_under_shard_map():
    """Each chip of a 2-way mesh runs the core on its own slice (two
    blocks each) with localized exclusion masks and returns LOCAL rows;
    both slices match the oracle."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    parts, local = 2, 2 * 512
    st = _arena(parts * local, seed=9)
    mesh = Mesh(np.asarray(jax.devices()[:parts]), ("data",))
    qd, q_shard, _ = _batch(st, 8, seed=4)
    rows = np.asarray([3, 700, local + 1, local + 900, 5, 6, local + 7, 8],
                      np.int32)

    def local_core(arena, q_c, s_c, rows_c):
        n_l = arena.emb.shape[0]
        rows_l = rows_c - jax.lax.axis_index("data") * n_l
        rows_l = jnp.where((rows_l >= 0) & (rows_l < n_l), rows_l, n_l)
        probe_excl = jnp.arange(n_l) == n_l - 1
        link_excl = jnp.zeros((n_l,), bool).at[rows_l].set(True) | probe_excl
        outs = S._ingest_scan_core(arena, q_c, s_c, probe_excl, link_excl,
                                   jnp.int32(1), 3, (1, 0))
        return tuple(o[None] for o in outs)

    row = jax.tree_util.tree_map(
        lambda a: P("data", None) if a.ndim == 2 else P("data"), st)
    got = jax.jit(shard_map(
        local_core, mesh=mesh,
        in_specs=(row, P(None, None), P(None), P(None)),
        out_specs=tuple(P("data", None, None) for _ in range(6)),
        check_vma=False))(st, qd, jnp.asarray(q_shard), jnp.asarray(rows))
    for p in range(parts):
        sl = slice(p * local, (p + 1) * local)
        part = jax.tree_util.tree_map(lambda a: a[sl], st)
        mine = rows[(rows >= p * local) & (rows < (p + 1) * local)] \
            - p * local
        # a slice's last row is its own sentinel: what "no row" reads as
        _check([np.asarray(o)[p] for o in got],
               _oracle(part, qd, q_shard, 1, mine, 3, (1, 0)))


# ------------------------------------------------ the whole dispatch, frozen

_D = 32
_SHARDS = ("work", "personal", "learning")


def _clustered(rng, dirs, which):
    v = 0.85 * dirs[which] + 0.55 * _unit(rng, len(which), _D)
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.float32)


def _dispatches(paged: bool):
    """Three fused dedup ingests over a seeded three-block arena (two
    tenants, three shards, super rows, deleted rows): a tenant's second
    session (restated facts the probe decides, intra-batch duplicates, links
    in modes (1, 0)), a batch of the other tenant in mode (2,), and a tenant
    the arena has never seen. Returns what the device decided, as lists."""
    rng = np.random.default_rng(45)
    dirs = _unit(rng, 8, _D)
    idx = MemoryIndex(dim=_D, capacity=3 * 512 - 1, edge_capacity=4095,
                      dtype=jnp.bfloat16, paged=paged, page_rows=512)
    stock = {}
    for t in ("a", "b"):
        for part in range(4):               # interleaved: both span blocks
            n = 150
            which = rng.integers(0, 8, n)
            emb = _clustered(rng, dirs, which)
            ids = [f"{t}{part}n{i}" for i in range(n)]
            idx.add(ids, emb, [0.5] * n, [0.0] * n, ["semantic"] * n,
                    [_SHARDS[i % 3] for i in range(n)], t,
                    is_super=[i % 37 == 0 for i in range(n)])
            stock.update(zip(ids, emb))
    idx.delete([f"a1n{i}" for i in range(0, 150, 5)])
    out = []
    for tenant, modes, n, restate in (("a", (1, 0), 20, 6),
                                      ("b", (2,), 12, 0),
                                      ("c", (1, 0), 9, 0)):
        which = rng.integers(0, 8, n)
        emb = _clustered(rng, dirs, which)
        for j in range(restate):            # an earlier session's facts
            emb[3 * j] = stock[f"a2n{7 * j + 1}"]
        if restate:
            emb[n - 1] = emb[n - 2]         # a duplicate inside the batch
        pend = idx.ingest_batch_dedup(
            emb, [0.6] * n, [1.0] * n, ["semantic"] * n,
            [_SHARDS[i % 3] for i in range(n)], tenant=tenant,
            dedup_gate=0.95, chain_weight=0.5, link_k=3, link_gate=0.5,
            link_scale=0.8, shard_modes=modes, now=2.0)
        host = pend["link_host"]
        rec = {"dup": pend["dup"].astype(int).tolist(),
               "target": np.where(pend["dup"], pend["target_rows"],
                                  -1).tolist(),
               "chain_src": pend["chain_src"].tolist(), "modes": []}
        for mi in range(len(modes)):
            sc, cd, ps = (np.asarray(a)[:n] for a in host[3 * mi:3 * mi + 3])
            found = sc > NEG / 2
            rec["modes"].append({
                "score": np.where(found, sc, 0.0).astype(float).tolist(),
                "cand": np.where(found, cd, -1).tolist(),
                "pos": ps.tolist()})
        ids = [None if d else f"{tenant}x{len(out)}n{i}"
               for i, d in enumerate(pend["dup"])]
        idx.commit_ingest_dedup(pend, ids)
        out.append(rec)
    es = idx.edge_state
    alive = np.asarray(es.alive)
    edges = sorted(zip(np.asarray(es.src)[alive].tolist(),
                       np.asarray(es.tgt)[alive].tolist(),
                       np.asarray(es.weight)[alive].astype(float).tolist()))
    return {"dispatches": out, "edges": [list(e) for e in edges]}


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_whole_dispatch_gives_what_the_dense_scan_gave(layout):
    """Verdicts, targets, chain sources, every found candidate with its
    score, every pool position and the edge arena after three dispatches:
    the dense scan's, but for ``(NEG, sentinel)`` in the slots it left a
    masked row in."""
    with open(GOLDEN) as f:
        want = json.load(f)[layout]
    got = _dispatches(layout == "paged")
    for g, w in zip(got["dispatches"], want["dispatches"], strict=True):
        assert g["dup"] == w["dup"] and sum(w["dup"]) in (0, 7)
        assert g["target"] == w["target"]
        assert g["chain_src"] == w["chain_src"]
        for gm, wm in zip(g["modes"], w["modes"], strict=True):
            assert gm["cand"] == wm["cand"] and gm["pos"] == wm["pos"]
            np.testing.assert_allclose(gm["score"], wm["score"], atol=2e-6,
                                       rtol=0)
    assert len(want["edges"]) > 60
    assert [e[:2] for e in got["edges"]] == [e[:2] for e in want["edges"]]
    np.testing.assert_allclose([e[2] for e in got["edges"]],
                               [e[2] for e in want["edges"]], atol=2e-6)


if __name__ == "__main__":
    with open(sys.argv[1], "w") as f:
        json.dump({"dense": _dispatches(False), "paged": _dispatches(True)},
                  f, separators=(",", ":"))
