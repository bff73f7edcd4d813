"""The blocked select-while-scanning core: masked cosine scoring and exact
top-k in ONE pass over the embedding pool, with no ``[queries, rows]`` score
matrix in HBM (ISSUE 26).

The pool streams from HBM once, ``block`` rows at a time. For each block,
on chip: the ``[C, block]`` scores on the MXU, the per-QUERY row mask (each
query sees only its own tenant's rows — ``row_main`` / ``row_gate`` carry,
per pool row, the tenant that may read it in that tier, or ``ROW_DEAD``),
the gate tier's running top-1, and the main tier's running top-k. The
running top-k is a sorted ``[C, K]`` list that stays on chip across blocks:
a block's best remaining score is inserted while it beats the query's
current k-th best, so the selection work follows what the data and each
query's own ``k`` ask for — a block that holds none of a query's rows, or
nothing better than it already has, costs one max over the block. The
loop's bound is DEVICE data (the batch's largest ``k``), never the static
ceiling ``K`` the shapes are compiled to.

Exactness: bf16 rows × bf16-rounded query into an f32 accumulate (what
``nt_dot`` does); equal scores resolve to the lowest pool row (first argmax
inside a block, earlier blocks ahead in the list) — ``lax.top_k``'s order;
slots past a query's ``k``, or past its tenant's live rows, hold
``(NEG, sentinel)``.

Two vehicles run the same step (``_select_step``): a Pallas TPU kernel
(grid over blocks, the pipeline's DMA of block b+1 under the compute of
block b, everything else in VMEM) and a plain-JAX ``fori_loop`` over
``dynamic_slice``d blocks (every other backend, and any pool the block does
not tile: then ONE whole-pool block of the same code). PERF.md §6 (PR 26)
holds the chip measurements that chose between them.

Two more cores share these steps and nothing else, each a body of its own
further down: the int8 coarse scan (``blocked_two_tier_q8``, ISSUE 36) and
the fused ingest's link scan (``blocked_link_scan``, ISSUE 45).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lazzaro_tpu.ops.backend import on_tpu
from lazzaro_tpu.ops.chunking import nt_dot

NEG = -1e30
# a pool row no query may read in a tier; no tenant id (>= -1) equals it,
# and the pad queries the kernel adds carry ROW_DEAD + 1
ROW_DEAD = int(np.iinfo(np.int32).min)

SELECT_BLOCK = 4096
# one embedding block's VMEM budget (it is double-buffered beside the
# [C, block] f32 score tile)
_BLOCK_BYTES = 8 * 1024 * 1024
# queries one kernel call holds on chip; wider batches stream in pieces
_MAX_QUERIES = 128


def select_block_rows(n: int, d: int, itemsize: int) -> int:
    """Rows per block for a pool of ``n`` rows: the largest power of two
    ≤ ``SELECT_BLOCK`` (and ≥ 512) that fits the VMEM budget and divides
    ``n`` — or ``n`` itself (ONE whole-pool block) when none does."""
    blk = SELECT_BLOCK
    while blk > 512 and blk * d * itemsize > _BLOCK_BYTES:
        blk //= 2
    while blk >= 512 and n % blk != 0:
        blk //= 2
    return blk if 512 <= blk < n else n


def block_tiles(n: int, d: int, itemsize: int) -> bool:
    """Whether a block tiles a pool of ``n`` rows — what the Pallas
    vehicle needs (several blocks; a block is a power of two ≥ 512, so
    lane-aligned)."""
    return select_block_rows(n, d, itemsize) < n


def _kth(r_s, lane, k_c):
    """Each query's current k-th best score ([C, 1]; NEG until its list
    holds k entries, and for a query that asks nothing)."""
    return jnp.max(jnp.where(lane == k_c - 1, r_s, NEG), axis=1,
                   keepdims=True)


def _wants(m, r_s, lane, k_c):
    """Per query ([C, 1] bool): the block's best remaining score ``m``
    would enter its list."""
    return (m > _kth(r_s, lane, k_c)) & (k_c > 0)


def _any(flags):
    """Scalar i32: some query's flag is set (a while-loop carry)."""
    return jnp.max(jnp.where(flags, 1, 0))


def _select_step(s, m, col, lane, base, r_s, r_r, k_c, roll):
    """One insertion per query: the block's best remaining score ``m``
    ([C, 1], the row max of ``s``) enters the sorted list where it beats
    the query's k-th best; its column is suppressed in ``s``. Returns the
    new ``(s, m, r_s, r_r, go)``; ``go`` says whether any query still has
    a score in this block that would enter."""
    blk = s.shape[1]
    act = _wants(m, r_s, lane, k_c)
    idx = jnp.min(jnp.where(s == m, col, blk), axis=1, keepdims=True)
    # entries >= m stay ahead (they came from lower rows): m lands at `pos`
    pos = jnp.sum(jnp.where(r_s >= m, 1.0, 0.0), axis=1,
                  keepdims=True).astype(jnp.int32)
    ins_s = jnp.where(lane < pos, r_s,
                      jnp.where(lane == pos, m, roll(r_s)))
    ins_r = jnp.where(lane < pos, r_r,
                      jnp.where(lane == pos, idx + base, roll(r_r)))
    r_s = jnp.where(act, ins_s, r_s)
    r_r = jnp.where(act, ins_r, r_r)
    s = jnp.where(col == idx, NEG, s)
    m = jnp.max(s, axis=1, keepdims=True)
    return s, m, r_s, r_r, _any(_wants(m, r_s, lane, k_c))


def _gate_step(scores, row_gate, tenant_c, col, base, g_s, g_r):
    """The gate tier's running top-1 ([C, 1] each): a later block wins
    only with a strictly better score, so ties keep the lowest row."""
    blk = scores.shape[1]
    sg = jnp.where(row_gate == tenant_c, scores, NEG)
    mg = jnp.max(sg, axis=1, keepdims=True)
    ig = jnp.min(jnp.where(sg == mg, col, blk), axis=1, keepdims=True)
    upd = mg > g_s
    return jnp.where(upd, mg, g_s), jnp.where(upd, ig + base, g_r)


# ---------------------------------------------------------------- plain JAX


def _scan_jax(emb, qn, row_main, row_gate, tenant_c, k_c, kmax, kp: int,
              block: int, sentinel: int):
    """The blocked core in plain JAX. Every argument is already padded:
    ``tenant_c`` / ``k_c`` are [C, 1], the lists are ``kp`` wide."""
    n = emb.shape[0]
    c = qn.shape[0]
    nblocks = n // block
    col = jax.lax.broadcasted_iota(jnp.int32, (c, block), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, kp), 1)
    roll = functools.partial(jnp.roll, shift=1, axis=1)

    def one_block(b, carry):
        r_s, r_r, g_s, g_r = carry
        base = b * block
        with jax.named_scope("lz.scan"):
            emb_b = jax.lax.dynamic_slice_in_dim(emb, base, block, 0)
            rm = jax.lax.dynamic_slice_in_dim(row_main, base, block, 0)
            rg = jax.lax.dynamic_slice_in_dim(row_gate, base, block, 0)
            scores = nt_dot(qn, emb_b)                     # [C, block] f32
            s = jnp.where(rm[None, :] == tenant_c, scores, NEG)
        with jax.named_scope("lz.topk"):
            g_s, g_r = _gate_step(scores, rg[None, :], tenant_c, col, base,
                                  g_s, g_r)
            m = jnp.max(s, axis=1, keepdims=True)
            go = _any(_wants(m, r_s, lane, k_c))

            def body(cy):
                t, s, m, r_s, r_r, _ = cy
                return (t + 1,) + _select_step(s, m, col, lane, base, r_s,
                                               r_r, k_c, roll)

            out = jax.lax.while_loop(
                lambda cy: (cy[5] > 0) & (cy[0] < kmax), body,
                (jnp.int32(0), s, m, r_s, r_r, go))
        return out[3], out[4], g_s, g_r

    init = (jnp.full((c, kp), NEG, jnp.float32),
            jnp.full((c, kp), sentinel, jnp.int32),
            jnp.full((c, 1), NEG, jnp.float32),
            jnp.full((c, 1), sentinel, jnp.int32))
    if nblocks == 1:
        return one_block(0, init)
    return jax.lax.fori_loop(0, nblocks, one_block, init)


# ------------------------------------------------------------------- Pallas


def _select_kernel(block: int, kp: int, sentinel: int):
    def kernel(kmax_ref, hasg_ref, q_ref, tq_ref, kq_ref, emb_ref, rm_ref,
               rg_ref, rs_ref, rr_ref, gs_ref, gr_ref, s_ref):
        b = pl.program_id(0)
        c = q_ref.shape[0]

        @pl.when(b == 0)
        def _():
            rs_ref[...] = jnp.full((c, kp), NEG, jnp.float32)
            rr_ref[...] = jnp.full((c, kp), sentinel, jnp.int32)
            gs_ref[...] = jnp.full((c, 1), NEG, jnp.float32)
            gr_ref[...] = jnp.full((c, 1), sentinel, jnp.int32)

        scores = jax.lax.dot_general(
            q_ref[...], emb_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [C, block]
        tq = tq_ref[...]
        kq = kq_ref[...]
        base = b * block
        col = jax.lax.broadcasted_iota(jnp.int32, (c, block), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, kp), 1)
        roll = functools.partial(pltpu.roll, shift=1, axis=1)

        @pl.when(hasg_ref[b] > 0)       # most blocks hold no super row
        def _():
            g_s, g_r = _gate_step(scores, rg_ref[...], tq, col, base,
                                  gs_ref[...], gr_ref[...])
            gs_ref[...] = g_s
            gr_ref[...] = g_r

        s = jnp.where(rm_ref[...] == tq, scores, NEG)
        s_ref[...] = s
        m = jnp.max(s, axis=1, keepdims=True)
        go = _any(_wants(m, rs_ref[...], lane, kq))
        kmax = kmax_ref[0]

        def body(cy):
            t, m, _ = cy
            s, m, r_s, r_r, go = _select_step(
                s_ref[...], m, col, lane, base, rs_ref[...], rr_ref[...],
                kq, roll)
            s_ref[...] = s
            rs_ref[...] = r_s
            rr_ref[...] = r_r
            return t + 1, m, go

        jax.lax.while_loop(lambda cy: (cy[2] > 0) & (cy[0] < kmax), body,
                           (jnp.int32(0), m, go))

    return kernel


def _scan_pallas(emb, qn, row_main, row_gate, tenant_c, k_c, kmax, kp: int,
                 block: int, sentinel: int, interpret: bool):
    """The blocked core as one Pallas TPU kernel over the grid of blocks.
    Same arguments and results as :func:`_scan_jax`."""
    n, d = emb.shape
    c = qn.shape[0]
    nblocks = n // block
    has_gate = (row_gate.reshape(nblocks, block) != ROW_DEAD).any(
        axis=1).astype(jnp.int32)
    fixed = lambda b, *_: (0, 0)                           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((c, d), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            pl.BlockSpec((1, block), lambda b, *_: (0, b)),
            pl.BlockSpec((1, block), lambda b, *_: (0, b)),
        ],
        out_specs=[
            pl.BlockSpec((c, kp), fixed),
            pl.BlockSpec((c, kp), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((c, 1), fixed),
        ],
        scratch_shapes=[pltpu.VMEM((c, block), jnp.float32)],
    )
    vmem = (2 * block * d * emb.dtype.itemsize + 6 * c * block * 4
            + 8 * 1024 * 1024)
    return pl.pallas_call(
        _select_kernel(block, kp, sentinel),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((c, kp), jnp.float32),
            jax.ShapeDtypeStruct((c, kp), jnp.int32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="lz_select_scan",
    )(kmax.reshape(1), has_gate, qn, tenant_c, k_c, emb,
      row_main.reshape(1, n), row_gate.reshape(1, n))


# ----------------------------------------------------------------- dispatch


def blocked_two_tier(emb: jax.Array, qn: jax.Array, row_main: jax.Array,
                     row_gate: jax.Array, tenant_c: jax.Array, k: int,
                     k_c: Optional[jax.Array] = None, impl: str = "auto"
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Gate top-1 + main top-``k`` of every query over its own tenant's
    rows, selected while the pool streams once.

    ``emb`` [n, d] (rows L2-normalized), ``qn`` [C, d] (normalized, in
    ``emb``'s dtype), ``row_main`` / ``row_gate`` [n] i32 (the tenant that
    may read the row in that tier, ``ROW_DEAD`` for none), ``tenant_c`` [C]
    i32, ``k`` the static list width, ``k_c`` [C] i32 each query's own k
    (None: all ``k``). Returns ``(gate_s [C], gate_r [C], ann_s [C, k],
    ann_r [C, k])`` with POOL rows; an empty slot is ``(NEG, n - 1)``.

    ``impl``: "auto" takes the Pallas kernel on a TPU when the block tiles
    the pool, the plain-JAX loop otherwise; "pallas" / "jax" force one
    (Pallas runs in interpret mode off the TPU)."""
    n, d = emb.shape
    c = qn.shape[0]
    sentinel = n - 1
    block = select_block_rows(n, d, emb.dtype.itemsize)
    tiles = block < n
    if impl == "pallas" and not tiles:
        raise ValueError(f"no block tiles a pool of {n} rows")
    use_pallas = impl == "pallas" or (impl == "auto" and on_tpu() and tiles)
    if k_c is None:
        k_c = jnp.full((c,), k, jnp.int32)
    k_c = jnp.clip(k_c.astype(jnp.int32), 0, k)
    if c > _MAX_QUERIES:
        from lazzaro_tpu.ops.chunking import chunked_map_multi
        return chunked_map_multi(
            lambda q_p, t_p, k_p: blocked_two_tier(
                emb, q_p, row_main, row_gate, t_p, k, k_p, impl),
            (qn, tenant_c, k_c), chunk=_MAX_QUERIES)
    # the kernel's tiles: bf16 queries are 16 deep, the lists 128 lanes wide
    cp = -(-c // 16) * 16 if use_pallas else c
    kp = -(-k // 128) * 128 if use_pallas else k
    pad = cp - c
    t2 = jnp.pad(tenant_c.astype(jnp.int32), (0, pad),
                 constant_values=ROW_DEAD + 1)[:, None]
    k2 = jnp.pad(k_c, (0, pad))[:, None]
    q2 = jnp.pad(qn, ((0, pad), (0, 0)))
    args = (emb, q2, row_main, row_gate, t2, k2, jnp.max(k_c))
    if use_pallas:
        r_s, r_r, g_s, g_r = _scan_pallas(
            *args, kp=kp, block=block, sentinel=sentinel,
            interpret=not on_tpu())
    else:
        r_s, r_r, g_s, g_r = _scan_jax(*args, kp, block, sentinel)
    with jax.named_scope("lz.topk"):
        # an insertion shifts older entries past a query's own k: cut there
        live = jnp.arange(k)[None, :] < k_c[:, None]
        ann_s = jnp.where(live, r_s[:c, :k], NEG)
        ann_r = jnp.where(live, r_r[:c, :k], sentinel)
    return g_s[:c, 0], g_r[:c, 0], ann_s, ann_r


def masked_topk(emb: jax.Array, mask: jax.Array, queries: jax.Array, k: int,
                k_q: Optional[jax.Array] = None, impl: str = "pallas"
                ) -> Tuple[jax.Array, jax.Array]:
    """The core's ONE-mask case — the classic ``arena_search`` and the
    per-shard scorer of ``ops.topk.make_sharded_topk``: every query reads
    the rows ``mask`` [n] allows, one main tier, no gate. ``emb`` [n, d]
    must be tiled by a block (:func:`block_tiles`). Returns ``(scores
    [Q, k], rows [Q, k])``; an empty slot is ``(NEG, n - 1)``."""
    n = emb.shape[0]
    _, _, top_s, top_r = blocked_two_tier(
        emb, queries.astype(emb.dtype),
        jnp.where(mask, 0, ROW_DEAD).astype(jnp.int32),
        jnp.full((n,), ROW_DEAD, jnp.int32),
        jnp.zeros((queries.shape[0],), jnp.int32), k, k_q, impl)
    return top_s, top_r


# ------------------------------------------------- the write path's link scan
#
# The fused ingest's twin of the core above (ISSUE 45): a conversation's facts
# against every row of the pool, ONE pass, several fixed-``k`` tiers — the
# dedup probe's top-1 (the gate tier's step) and one sorted top-``k`` list PER
# link mode — all held on chip across the blocks. A separate body, not the
# serving kernel with a flag: that one's list is ragged (``k_c`` and ``kmax``
# are device data, one main tier); here ``k`` is static and small and the
# tiers are several. They share the step functions and nothing else.
#
# The tiers mask on two per-row int32 columns: ``row_probe`` (0 where the
# probe may read the row, else ``ROW_DEAD``) and ``row_link`` (the row's shard
# where a link may read it, else ``ROW_DEAD``); a fact's key is its shard.
# Every link row is a probe row, so ONE per-block flag (any probe row in the
# block) lets a block that holds no candidate skip the matmul and the masks;
# the block is still streamed.


def _link_mask(sm: int, row_link, q_shard):
    """One link mode's ``[C, block]`` mask from the rows' key column
    ([1, block]) and the facts' shards ([C, 1]): 1 the fact's own shard, 0
    any shard, anything else another shard."""
    rows = jnp.broadcast_to(row_link, (q_shard.shape[0], row_link.shape[1]))
    if sm == 1:
        return rows == q_shard
    live = rows != ROW_DEAD
    return live if sm == 0 else live & (rows != q_shard)


def _probe_key(k_c):
    """The facts' key in the probe tier ([C, 1]): 0, and for the pad facts
    the kernel adds (they ask nothing) a key no row carries."""
    return jnp.where(k_c > 0, 0, ROW_DEAD + 1)


def _link_scan_jax(emb, qn, row_probe, row_link, q_shard, k_c, k: int,
                   modes: Tuple[int, ...], kp: int, block: int,
                   sentinel: int):
    """The link scan in plain JAX. ``q_shard`` / ``k_c`` are [C, 1];
    ``row_probe`` None drops the probe tier. Returns ``(g_s, g_r, r_s_mode,
    r_r_mode, ...)``: the probe's [C, 1] pair, then a [C, kp] pair a mode.

    Off the kernel a tier takes a block's best ``k`` with ONE ``lax.top_k``
    and merges them behind its running list with another (both keep the
    lower index ahead on equal scores, and earlier blocks hold lower rows),
    and the probe its best with a ``top_k`` of 1: the kernel's lists, found
    the way XLA is quick at — ``_select_step``'s insertion loop cost the CPU
    four times the dense scan it replaced at the benchmark's debug geometry
    (PERF.md section 6, PR 45)."""
    n = emb.shape[0]
    c = qn.shape[0]
    nblocks = n // block
    flag_col = row_link if row_probe is None else row_probe
    has = (flag_col.reshape(nblocks, block) != ROW_DEAD).any(axis=1)
    asks = k_c > 0

    def one_block(b, carry):
        g_s, g_r = carry[:2]
        base = b * block
        scores = nt_dot(qn, jax.lax.dynamic_slice_in_dim(emb, base, block, 0))
        if row_probe is not None:
            rp = jax.lax.dynamic_slice_in_dim(row_probe, base, block, 0)
            mg, ig = jax.lax.top_k(
                jnp.where(rp[None, :] == _probe_key(k_c), scores, NEG), 1)
            upd = mg > g_s          # strictly better: ties keep the lower row
            g_s, g_r = jnp.where(upd, mg, g_s), jnp.where(upd, ig + base, g_r)
        rl = jax.lax.dynamic_slice_in_dim(row_link, base, block, 0)[None, :]
        out = [g_s, g_r]
        for t, sm in enumerate(modes):
            s = jnp.where(_link_mask(sm, rl, q_shard) & asks, scores, NEG)
            b_s, b_i = jax.lax.top_k(s, min(kp, block))
            b_r = jnp.where(b_s > NEG / 2, b_i + base, sentinel)
            both_s = jnp.concatenate([carry[2 + 2 * t], b_s], axis=1)
            both_r = jnp.concatenate([carry[3 + 2 * t], b_r], axis=1)
            r_s, at = jax.lax.top_k(both_s, kp)
            out.extend((r_s, jnp.take_along_axis(both_r, at, axis=1)))
        return tuple(out)

    init = (jnp.full((c, 1), NEG, jnp.float32),
            jnp.full((c, 1), sentinel, jnp.int32)) + (
        jnp.full((c, kp), NEG, jnp.float32),
        jnp.full((c, kp), sentinel, jnp.int32)) * len(modes)
    if nblocks == 1:
        return one_block(0, init)
    return jax.lax.fori_loop(
        0, nblocks, lambda b, carry: jax.lax.cond(
            has[b], functools.partial(one_block, b), lambda cy: cy, carry),
        init)


def _link_kernel(block: int, kp: int, k: int, modes: Tuple[int, ...],
                 with_probe: bool, sentinel: int):
    def kernel(has_ref, q_ref, kq_ref, sq_ref, emb_ref, *refs):
        refs = list(refs)
        rp_ref = refs.pop(0) if with_probe else None
        rl_ref, gs_ref, gr_ref, *lists, s_ref = refs
        b = pl.program_id(0)
        c = q_ref.shape[0]

        @pl.when(b == 0)
        def _():
            gs_ref[...] = jnp.full((c, 1), NEG, jnp.float32)
            gr_ref[...] = jnp.full((c, 1), sentinel, jnp.int32)
            for t in range(len(modes)):
                lists[2 * t][...] = jnp.full((c, kp), NEG, jnp.float32)
                lists[2 * t + 1][...] = jnp.full((c, kp), sentinel,
                                                 jnp.int32)

        @pl.when(has_ref[b] > 0)        # most blocks hold no candidate row
        def _():
            scores = jax.lax.dot_general(
                q_ref[...], emb_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [C, block]
            kq = kq_ref[...]
            base = b * block
            col = jax.lax.broadcasted_iota(jnp.int32, (c, block), 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, (c, kp), 1)
            roll = functools.partial(pltpu.roll, shift=1, axis=1)
            if with_probe:
                g_s, g_r = _gate_step(scores, rp_ref[...], _probe_key(kq),
                                      col, base, gs_ref[...], gr_ref[...])
                gs_ref[...] = g_s
                gr_ref[...] = g_r
            for t, sm in enumerate(modes):
                rs_ref, rr_ref = lists[2 * t], lists[2 * t + 1]
                s = jnp.where(_link_mask(sm, rl_ref[...], sq_ref[...]),
                              scores, NEG)
                s_ref[...] = s
                m = jnp.max(s, axis=1, keepdims=True)
                go = _any(_wants(m, rs_ref[...], lane, kq))

                def body(cy, rs_ref=rs_ref, rr_ref=rr_ref):
                    t_, m, _ = cy
                    s, m, r_s, r_r, go = _select_step(
                        s_ref[...], m, col, lane, base, rs_ref[...],
                        rr_ref[...], kq, roll)
                    s_ref[...] = s
                    rs_ref[...] = r_s
                    rr_ref[...] = r_r
                    return t_ + 1, m, go

                jax.lax.while_loop(lambda cy: (cy[2] > 0) & (cy[0] < k),
                                   body, (jnp.int32(0), m, go))

    return kernel


def _link_scan_pallas(emb, qn, row_probe, row_link, q_shard, k_c, k: int,
                      modes: Tuple[int, ...], kp: int, block: int,
                      sentinel: int, interpret: bool):
    """The link scan as one Pallas TPU kernel over the grid of blocks. Same
    arguments and results as :func:`_link_scan_jax`."""
    n, d = emb.shape
    c = qn.shape[0]
    nblocks = n // block
    with_probe = row_probe is not None
    cols = ([row_probe] if with_probe else []) + [row_link]
    has = (cols[0].reshape(nblocks, block) != ROW_DEAD).any(
        axis=1).astype(jnp.int32)
    fixed = lambda b, *_: (0, 0)                           # noqa: E731
    rows_of = lambda b, *_: (0, b)                         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((c, d), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
        ] + [pl.BlockSpec((1, block), rows_of)] * len(cols),
        out_specs=[pl.BlockSpec((c, 1), fixed)] * 2
        + [pl.BlockSpec((c, kp), fixed)] * (2 * len(modes)),
        scratch_shapes=[pltpu.VMEM((c, block), jnp.float32)],
    )
    vmem = (2 * block * d * emb.dtype.itemsize + 8 * c * block * 4
            + 8 * 1024 * 1024)
    return tuple(pl.pallas_call(
        _link_kernel(block, kp, k, modes, with_probe, sentinel),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((c, 1), jnp.float32),
                   jax.ShapeDtypeStruct((c, 1), jnp.int32)]
        + [jax.ShapeDtypeStruct((c, kp), jnp.float32),
           jax.ShapeDtypeStruct((c, kp), jnp.int32)] * len(modes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="lz_link_scan",
    )(has, qn, k_c, q_shard, emb, *(a.reshape(1, n) for a in cols)))


def blocked_link_scan(emb: jax.Array, qn: jax.Array, row_link: jax.Array,
                      q_shard: jax.Array, k: int,
                      shard_modes: Tuple[int, ...],
                      row_probe: Optional[jax.Array] = None,
                      impl: str = "auto") -> Tuple[jax.Array, ...]:
    """The fused ingest's scan: per fact the dedup probe's top-1 and, for
    each entry of ``shard_modes``, a top-``k`` of link candidates, selected
    while the pool streams once.

    ``emb`` [n, d] (rows L2-normalized), ``qn`` [C, d] (the facts,
    normalized, in ``emb``'s dtype), ``row_link`` [n] i32 (the row's shard
    where a link may read it, ``ROW_DEAD`` where none may), ``q_shard`` [C]
    i32, ``row_probe`` [n] i32 (0 where the probe may read the row, else
    ``ROW_DEAD``; every link row must be a probe row; None: no probe tier).
    A mode is 1 (rows of the fact's own shard), 0 (any shard) or anything
    else (another shard). Returns the flat tuple ``(probe_s [C, 1], probe_r
    [C, 1], s_mode [C, k], r_mode [C, k], ...)`` — without the first pair
    when ``row_probe`` is None — with POOL rows, best first, equal scores
    the lower row first; a slot no candidate fills is ``(NEG, n - 1)``.
    ``impl`` as in :func:`blocked_two_tier`."""
    n, d = emb.shape
    c = qn.shape[0]
    block = select_block_rows(n, d, emb.dtype.itemsize)
    tiles = block < n
    if impl == "pallas" and not tiles:
        raise ValueError(f"no block tiles a pool of {n} rows")
    use_pallas = impl == "pallas" or (impl == "auto" and on_tpu() and tiles)
    shard_modes = tuple(shard_modes)
    if c > _MAX_QUERIES:
        from lazzaro_tpu.ops.chunking import chunked_map_multi
        return chunked_map_multi(
            lambda q_p, s_p: blocked_link_scan(
                emb, q_p, row_link, s_p, k, shard_modes, row_probe, impl),
            (qn, q_shard), chunk=_MAX_QUERIES)
    # the kernel's tiles: bf16 facts are 16 deep, the lists 128 lanes wide;
    # a pad fact asks nothing (k 0)
    cp = -(-c // 16) * 16 if use_pallas else c
    kp = -(-k // 128) * 128 if use_pallas else k
    pad = cp - c
    args = (emb, jnp.pad(qn, ((0, pad), (0, 0))), row_probe, row_link,
            jnp.pad(q_shard.astype(jnp.int32), (0, pad))[:, None],
            jnp.pad(jnp.full((c,), k, jnp.int32), (0, pad))[:, None],
            k, shard_modes, kp, block, n - 1)
    if use_pallas:
        flat = _link_scan_pallas(*args, interpret=not on_tpu())
    else:
        flat = _link_scan_jax(*args)
    lists = tuple(a[:c, :k] for a in flat[2:])
    if row_probe is None:
        return lists
    return (flat[0][:c], flat[1][:c]) + lists


# ------------------------------------------------------ the int8 coarse scan
#
# The int8 family's twin of the core above (ISSUE 36): the serving shadow
# (``ops/quant.py``: int8 codes + one f32 scale a row) streams from HBM once,
# ``block`` rows at a time; per block, on chip, the ``[C, block]`` int8 x int8
# -> int32 tile on the MXU, the query's and the rows' scales in f32, the same
# per-query tenant masks, and BOTH tiers' running sorted lists — the gate's
# top-(1 + slack) and the main tier's coarse fetch (k + slack). No
# ``[queries, rows]`` tile and no full-width top-k.
#
# A coarse fetch is wide (136 where an exact list holds a request's k), every
# entry is one insertion, and an insertion's cost is the latency of its
# cross-lane reductions, not their width. So the Pallas vehicle (1) sorts the
# queries by tenant and drains a block per GROUP of eight queries (one f32
# sublane tile) — a block wakes only the groups whose tenants have rows in it,
# and queries of one tenant share a group; (2) cuts the group's tile into
# ``panels`` column panels and takes the best remaining score of EVERY panel
# in one step, so the reductions of a step overlap; (3) inserts by compare
# and shift, with no reduction at all. Lists are ordered by (score, then
# lower row), so panels may hand their candidates over in any order and equal
# scores still resolve to the lowest row, at the fetch boundary too.

_Q8_GROUP = 8
# int8 operands tile 32 rows deep
_Q8_QUERY_TILE = 32
# rows a block of codes may have (12,288 x 768 B = 9.4 MB, double-buffered)
_Q8_BLOCKS = (12288, 8192, 4096, 2048, 1024, 512)
_Q8_BLOCK_BYTES = 12 * 1024 * 1024


def q8_block_rows(n: int, d: int) -> int:
    """Rows per block of the int8 scan over a shadow of ``n`` rows: the
    largest of ``_Q8_BLOCKS`` that fits the VMEM budget and divides ``n``
    into several blocks — or ``n`` itself (ONE whole-pool block)."""
    for blk in _Q8_BLOCKS:
        if blk * d <= _Q8_BLOCK_BYTES and blk < n and n % blk == 0:
            return blk
    return n


def q8_block_tiles(n: int, d: int) -> bool:
    """Whether a block tiles a shadow of ``n`` rows (the Pallas vehicle)."""
    return q8_block_rows(n, d) < n


def _q8_panels(block: int) -> int:
    """Column panels a group's tile is drained in: each a whole number of
    128-lane tiles. Four: a step costs its extraction (the tile's width) and
    one insertion a panel, and the chip read 4 and 8 panels alike (PERF.md
    section 6, PR 36) while 8 cost twice the program to trace and lower."""
    return next(p for p in (4, 2, 1) if block % (p * 128) == 0)


def _q8_scores(qq, qs, codes, scale_b):
    """``[C, block]`` f32 coarse scores: the integer dot of the codes, times
    the query's scale ([C, 1]), times the rows' ([1, block]) — the order
    ``ops/quant.quantized_topk`` multiplies in."""
    dots = jax.lax.dot_general(qq, codes, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)
    return dots.astype(jnp.float32) * qs * scale_b


def _q8_insert(r_s, r_r, m, row, lane, k: int, roll):
    """``(m, row)`` ([Q, 1] each) into the lists sorted by (score down, row
    up), where it beats the query's ``k``-th entry. Compare and shift: the
    entries ahead of it stay, it lands behind them, the rest move one lane
    on. Returns the lists and, per query, whether it entered."""
    ahead = (r_s > m) | ((r_s == m) & (r_r < row))
    act = ~ahead[:, k - 1:k] & (m > NEG / 2)     # NEG: nothing left
    p_s, p_r = roll(r_s), roll(r_r)                # lane i holds entry i - 1
    p_ahead = (lane == 0) | (p_s > m) | ((p_s == m) & (p_r < row))
    ins_s = jnp.where(ahead, r_s, jnp.where(p_ahead, m, p_s))
    ins_r = jnp.where(ahead, r_r, jnp.where(p_ahead, row, p_r))
    return jnp.where(act, ins_s, r_s), jnp.where(act, ins_r, r_r), act


def _q8_best(s, col):
    """A tile's best remaining score per query and its column ([Q, 1] each,
    lowest column on equal scores), and the tile with it taken out."""
    m = jnp.max(s, axis=1, keepdims=True)
    idx = jnp.min(jnp.where(s == m, col, s.shape[1]), axis=1, keepdims=True)
    return m, idx, jnp.where(col == idx, NEG, s)


def _scan_q8_jax(q8a, scale, qq, qs, row_main, row_gate, tenant_c,
                 k_fetch: int, g_fetch: int, block: int, sentinel: int):
    """The int8 core in plain JAX, one candidate a step. ``qs`` and
    ``tenant_c`` are [C, 1]; the lists are as wide as their fetch."""
    n = q8a.shape[0]
    c = qq.shape[0]
    nblocks = n // block
    col = jax.lax.broadcasted_iota(jnp.int32, (c, block), 1)
    roll = functools.partial(jnp.roll, shift=1, axis=1)

    def drain(s, base, r_s, r_r, k):
        lane = jax.lax.broadcasted_iota(jnp.int32, r_s.shape, 1)

        def body(cy):
            s, r_s, r_r, _ = cy
            m, idx, s = _q8_best(s, col)
            r_s, r_r, act = _q8_insert(r_s, r_r, m, idx + base, lane, k, roll)
            return s, r_s, r_r, _any(act)

        out = jax.lax.while_loop(lambda cy: cy[3] > 0, body,
                                 (s, r_s, r_r, jnp.int32(1)))
        return out[1], out[2]

    def one_block(b, carry):
        r_s, r_r, g_s, g_r = carry
        base = b * block
        with jax.named_scope("lz.scan_q8"):
            codes = jax.lax.dynamic_slice_in_dim(q8a, base, block, 0)
            sc = jax.lax.dynamic_slice_in_dim(scale, base, block, 0)
            rm = jax.lax.dynamic_slice_in_dim(row_main, base, block, 0)
            rg = jax.lax.dynamic_slice_in_dim(row_gate, base, block, 0)
            scores = _q8_scores(qq, qs, codes, sc[None, :])
        with jax.named_scope("lz.topk"):
            g_s, g_r = drain(jnp.where(rg[None, :] == tenant_c, scores, NEG),
                             base, g_s, g_r, g_fetch)
            r_s, r_r = drain(jnp.where(rm[None, :] == tenant_c, scores, NEG),
                             base, r_s, r_r, k_fetch)
        return r_s, r_r, g_s, g_r

    init = (jnp.full((c, k_fetch), NEG, jnp.float32),
            jnp.full((c, k_fetch), sentinel, jnp.int32),
            jnp.full((c, g_fetch), NEG, jnp.float32),
            jnp.full((c, g_fetch), sentinel, jnp.int32))
    if nblocks == 1:
        return one_block(0, init)
    return jax.lax.fori_loop(0, nblocks, one_block, init)


def _select_q8_kernel(block: int, k_fetch: int, g_fetch: int, kp: int,
                      gp: int, sentinel: int):
    panels = _q8_panels(block)

    def kernel(hasg_ref, q_ref, qs_ref, tq_ref, codes_ref, sc_ref, rm_ref,
               rg_ref, rs_ref, rr_ref, gs_ref, gr_ref, s_ref, woke_ref):
        b = pl.program_id(0)
        c = q_ref.shape[0]

        @pl.when(b == 0)
        def _():
            rs_ref[...] = jnp.full((c, kp), NEG, jnp.float32)
            rr_ref[...] = jnp.full((c, kp), sentinel, jnp.int32)
            gs_ref[...] = jnp.full((c, gp), NEG, jnp.float32)
            gr_ref[...] = jnp.full((c, gp), sentinel, jnp.int32)

        scores = _q8_scores(q_ref[...], qs_ref[...], codes_ref[...],
                            sc_ref[...])                   # [C, block]
        tq = tq_ref[...]
        base = b * block
        roll = functools.partial(pltpu.roll, shift=1, axis=1)

        def drain(rows, ls_ref, lr_ref, k, cuts):
            """What the group ``rows`` of ``s_ref`` still wants of this
            block, into its sorted lists: the best remaining score of each
            of ``cuts`` column panels a step, until a step's candidates all
            stay out."""
            wide = block // cuts
            col = jax.lax.broadcasted_iota(jnp.int32, (_Q8_GROUP, wide), 1)
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (_Q8_GROUP, ls_ref.shape[1]), 1)

            def step(cy):
                r_s, r_r = ls_ref[rows, :], lr_ref[rows, :]
                took = jnp.zeros((_Q8_GROUP, 1), bool)
                for p in range(cuts):
                    at = slice(p * wide, (p + 1) * wide)
                    m, idx, rest = _q8_best(s_ref[rows, at], col)
                    s_ref[rows, at] = rest
                    r_s, r_r, act = _q8_insert(
                        r_s, r_r, m, idx + (base + p * wide), lane, k, roll)
                    took = took | act
                ls_ref[rows, :] = r_s
                lr_ref[rows, :] = r_r
                return cy[0] + 1, _any(took)

            jax.lax.while_loop(lambda cy: (cy[1] > 0) & (cy[0] < wide), step,
                               (jnp.int32(0), jnp.int32(1)))

        def tier(s, ls_ref, lr_ref, k, cuts):
            """One tier's masked scores ``s`` into its lists: the groups the
            block wakes, one by one. A block of other tenants' rows wakes
            nobody."""
            want = jnp.max(s, axis=1, keepdims=True) > ls_ref[:, k - 1:k]

            @pl.when(_any(want) > 0)
            def _():
                s_ref[...] = s
                for g in range(c // _Q8_GROUP):
                    woke_ref[g] = _any(
                        want[g * _Q8_GROUP:(g + 1) * _Q8_GROUP])

                def group(g, _):
                    @pl.when(woke_ref[g] > 0)
                    def _():
                        drain(pl.ds(pl.multiple_of(g * _Q8_GROUP, _Q8_GROUP),
                                    _Q8_GROUP), ls_ref, lr_ref, k, cuts)
                    return 0

                jax.lax.fori_loop(0, c // _Q8_GROUP, group, 0)

        @pl.when(hasg_ref[b] > 0)       # most blocks hold no super row, and
        def _():                        # the gate's fetch is short: one panel
            tier(jnp.where(rg_ref[...] == tq, scores, NEG), gs_ref, gr_ref,
                 g_fetch, 1)

        tier(jnp.where(rm_ref[...] == tq, scores, NEG), rs_ref, rr_ref,
             k_fetch, panels)

    return kernel


def _scan_q8_pallas(q8a, scale, qq, qs, row_main, row_gate, tenant_c,
                    k_fetch: int, g_fetch: int, kp: int, gp: int, block: int,
                    sentinel: int, interpret: bool):
    """The int8 core as one Pallas TPU kernel over the grid of blocks. Same
    arguments and results as :func:`_scan_q8_jax` (lists ``kp`` / ``gp``
    wide)."""
    n, d = q8a.shape
    c = qq.shape[0]
    nblocks = n // block
    has_gate = (row_gate.reshape(nblocks, block) != ROW_DEAD).any(
        axis=1).astype(jnp.int32)
    fixed = lambda b, *_: (0, 0)                           # noqa: E731
    rows_of = lambda b, *_: (0, b)                         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((c, d), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((c, 1), fixed),
            pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            pl.BlockSpec((1, block), rows_of),
            pl.BlockSpec((1, block), rows_of),
            pl.BlockSpec((1, block), rows_of),
        ],
        out_specs=[
            pl.BlockSpec((c, kp), fixed),
            pl.BlockSpec((c, kp), fixed),
            pl.BlockSpec((c, gp), fixed),
            pl.BlockSpec((c, gp), fixed),
        ],
        scratch_shapes=[pltpu.VMEM((c, block), jnp.float32),
                        pltpu.SMEM((c // _Q8_GROUP,), jnp.int32)],
    )
    vmem = 2 * block * d + 8 * c * block * 4 + 8 * 1024 * 1024
    return pl.pallas_call(
        _select_q8_kernel(block, k_fetch, g_fetch, kp, gp, sentinel),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((c, kp), jnp.float32),
            jax.ShapeDtypeStruct((c, kp), jnp.int32),
            jax.ShapeDtypeStruct((c, gp), jnp.float32),
            jax.ShapeDtypeStruct((c, gp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=int(vmem)),
        interpret=interpret,
        name="lz_select_scan_q8",
    )(has_gate, qq, qs, tenant_c, q8a, scale.reshape(1, n),
      row_main.reshape(1, n), row_gate.reshape(1, n))


@functools.lru_cache(maxsize=None)
def _scan_q8_for_tpu(n: int, d: int, c: int, k_fetch: int, g_fetch: int,
                     kp: int, gp: int, block: int):
    """:func:`_scan_q8_pallas` at one geometry, traced and lowered for the
    TPU ONCE a process (``jax.export``) and called from every serving
    program after that. A warm start finds its executables in the persistent
    cache but still traces and lowers every program to ask for them — eight
    batch buckets and two twins are sixteen, their queries padded to one of
    two tiles — and this kernel's body is what made each of them slow to
    lower (PERF.md section 6, PRs 35 and 36)."""
    sds = jax.ShapeDtypeStruct
    return jax.export.export(
        jax.jit(functools.partial(
            _scan_q8_pallas, k_fetch=k_fetch, g_fetch=g_fetch, kp=kp, gp=gp,
            block=block, sentinel=n - 1, interpret=False)),
        platforms=("tpu",))(
            sds((n, d), jnp.int8), sds((n,), jnp.float32),
            sds((c, d), jnp.int8), sds((c, 1), jnp.float32),
            sds((n,), jnp.int32), sds((n,), jnp.int32),
            sds((c, 1), jnp.int32)).call


def blocked_two_tier_q8(q8a: jax.Array, scale: jax.Array, qq: jax.Array,
                        qs: jax.Array, row_main: jax.Array,
                        row_gate: jax.Array, tenant_c: jax.Array,
                        k_fetch: int, g_fetch: int, impl: str = "auto"
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """The gate tier's ``g_fetch`` and the main tier's ``k_fetch`` best rows
    of every query BY INT8 SCORE over its own tenant's rows, selected while
    the shadow streams once.

    ``q8a`` [n, d] i8 / ``scale`` [n] f32 (the shadow), ``qq`` [C, d] i8 /
    ``qs`` [C] f32 (the unit queries' codes and scales), ``row_main`` /
    ``row_gate`` [n] i32 and ``tenant_c`` [C] i32 as in
    :func:`blocked_two_tier`. Returns ``(gate_s [C, g_fetch], gate_r,
    ann_s [C, k_fetch], ann_r)``, each best first (equal scores: the lower
    row first) with rows of ``q8a``; an empty slot is ``(NEG, n - 1)``.
    ``impl`` as in :func:`blocked_two_tier`."""
    n, d = q8a.shape
    c = qq.shape[0]
    sentinel = n - 1
    block = q8_block_rows(n, d)
    tiles = block < n
    if impl == "pallas" and not tiles:
        raise ValueError(f"no block tiles a pool of {n} rows")
    use_pallas = impl == "pallas" or (impl == "auto" and on_tpu() and tiles)
    if c > _MAX_QUERIES:
        from lazzaro_tpu.ops.chunking import chunked_map_multi
        return chunked_map_multi(
            lambda q_p, s_p, t_p: blocked_two_tier_q8(
                q8a, scale, q_p, s_p, row_main, row_gate, t_p, k_fetch,
                g_fetch, impl),
            (qq, qs, tenant_c), chunk=_MAX_QUERIES)
    tenant_c = tenant_c.astype(jnp.int32)
    if not use_pallas:
        r_s, r_r, g_s, g_r = _scan_q8_jax(
            q8a, scale, qq, qs[:, None], row_main, row_gate,
            tenant_c[:, None], k_fetch, g_fetch, block, sentinel)
        return g_s, g_r, r_s, r_r
    # queries of one tenant side by side: they wake the same groups; the pad
    # queries (no row answers to ROW_DEAD + 1) make up the int8 tile. The
    # batch is small: its order is counted, not sorted, and both permutations
    # are one-hot sums (exact; no sort and no row-by-row gather on the chip)
    i = jnp.arange(c, dtype=jnp.int32)
    before = (tenant_c[None, :] < tenant_c[:, None]) | (
        (tenant_c[None, :] == tenant_c[:, None]) & (i[None, :] < i[:, None]))
    place = jnp.sum(before, axis=1, dtype=jnp.int32)     # query i's new row
    to_sorted = place[None, :] == i[:, None]             # [new row, query]

    def permute(onehot, a):
        wide = a.reshape(c, -1)
        out = jnp.sum(jnp.where(onehot[:, :, None], wide[None, :, :], 0),
                      axis=1, dtype=wide.dtype)
        return out.reshape(a.shape)

    pad = -c % _Q8_QUERY_TILE
    kp, gp = -(-k_fetch // 128) * 128, -(-g_fetch // 128) * 128
    if on_tpu():
        scan = _scan_q8_for_tpu(n, d, c + pad, k_fetch, g_fetch, kp, gp,
                                block)
    else:                               # interpret mode: small shapes only
        scan = functools.partial(
            _scan_q8_pallas, k_fetch=k_fetch, g_fetch=g_fetch, kp=kp, gp=gp,
            block=block, sentinel=sentinel, interpret=True)
    r_s, r_r, g_s, g_r = scan(
        q8a, scale, jnp.pad(permute(to_sorted, qq), ((0, pad), (0, 0))),
        jnp.pad(permute(to_sorted, qs), (0, pad))[:, None], row_main,
        row_gate, jnp.pad(permute(to_sorted, tenant_c), (0, pad),
                          constant_values=ROW_DEAD + 1)[:, None])
    back = to_sorted.T                                   # [query, new row]
    return (permute(back, g_s[:c, :g_fetch]), permute(back, g_r[:c, :g_fetch]),
            permute(back, r_s[:c, :k_fetch]), permute(back, r_r[:c, :k_fetch]))
