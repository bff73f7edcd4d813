"""Pallas TPU kernel: fused masked cosine scoring + two-stage exact top-k.

The retrieval hot op (SURVEY §7.2). The XLA path materializes a [Q, N] f32
score matrix in HBM and runs a full-width ``lax.top_k`` over N (sort-network
heavy at N=1M). This kernel streams the embedding matrix through VMEM once,
blocks of BLK rows at a time: each grid step computes the block's scores on
the MXU, applies the alive/tenant mask additively, and keeps only the block's
top-K (iterative max-and-suppress on the VPU) — so HBM traffic is the
embedding read plus a tiny [nblocks, Q, K] candidate tensor, and the final
exact top-k runs over nblocks·K ≪ N candidates.

Use ``interpret=True`` (automatic on CPU) for tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lazzaro_tpu.ops.backend import on_tpu

NEG = -1e30


def _topk_block_kernel(k: int):
    def kernel(q_ref, emb_ref, madd_ref, out_s_ref, out_i_ref):
        blk_idx = pl.program_id(0)
        emb_blk = emb_ref[:]                        # [BLK, d]
        q = q_ref[:]                                # [Q, d]
        scores = jax.lax.dot_general(
            q, emb_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [Q, BLK]
        scores = scores + madd_ref[:]               # additive mask [1, BLK]
        blk = scores.shape[1]
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        base = blk_idx * blk
        for t in range(k):                          # iterative max-and-suppress
            m = jnp.max(scores, axis=1, keepdims=True)           # [Q, 1]
            hit = scores == m
            idx = jnp.min(jnp.where(hit, col, blk), axis=1,
                          keepdims=True)                          # first argmax
            out_s_ref[0, :, t] = m[:, 0]
            out_i_ref[0, :, t] = idx[:, 0] + base
            scores = jnp.where(col == idx, NEG, scores)
    return kernel


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def pallas_masked_topk(emb: jax.Array, madd: jax.Array, queries: jax.Array,
                       k: int = 10, block_rows: int = 4096,
                       interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """emb [N, d] (L2-normalized, N % block_rows == 0), madd [N] additive mask
    (0 alive / -1e30 dead), queries [Q, d]. Returns (scores [Q,k], rows [Q,k]).
    """
    n, d = emb.shape
    assert n % block_rows == 0, f"N={n} must be a multiple of {block_rows}"
    nblocks = n // block_rows
    q = queries.astype(emb.dtype)
    nq = q.shape[0]
    madd2 = madd.reshape(1, n).astype(jnp.float32)

    grid_spec = pl.GridSpec(
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((nq, d), lambda b: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, d), lambda b: (b, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_rows), lambda b: (0, b),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, nq, k), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, nq, k), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    block_s, block_i = pl.pallas_call(
        _topk_block_kernel(k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, emb, madd2)

    # Stage 2: exact top-k over the nblocks*k candidates per query.
    cand_s = jnp.moveaxis(block_s, 0, 1).reshape(nq, nblocks * k)
    cand_i = jnp.moveaxis(block_i, 0, 1).reshape(nq, nblocks * k)
    top_s, pos = jax.lax.top_k(cand_s, k)
    top_i = jnp.take_along_axis(cand_i, pos, axis=1)
    return top_s, top_i


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def pallas_masked_topk_ragged(emb: jax.Array, madd: jax.Array,
                              queries: jax.Array, k_q: jax.Array,
                              k: int = 10, block_rows: int = 4096,
                              interpret: bool = False
                              ) -> Tuple[jax.Array, jax.Array]:
    """Ragged-K variant of the blocked scan (ISSUE 7): ``k`` is the STATIC
    batch ceiling the VMEM-streaming kernel computes to — the per-block
    max-and-suppress loop and the stage-2 candidate sort are compiled
    once per (geometry, ceiling) — and ``k_q`` ([Q] i32 device data) is
    each query's own k. Positions at or past a query's k come back as
    (NEG, -1), exactly the per-query ``top_k(k_i)`` result because the
    ceiling output is score-sorted. One compiled kernel therefore serves
    any mix of request k's ≤ the ceiling; mixed-k fleets stop burning a
    compile-cache entry per distinct k."""
    top_s, top_i = pallas_masked_topk(emb, madd, queries, k=k,
                                      block_rows=block_rows,
                                      interpret=interpret)
    live = jnp.arange(k)[None, :] < k_q[:, None]
    return jnp.where(live, top_s, NEG), jnp.where(live, top_i, -1)


def masked_topk_arena_ragged(emb: jax.Array, mask: jax.Array,
                             queries: jax.Array, k_q: jax.Array,
                             k: int = 10) -> Tuple[jax.Array, jax.Array]:
    """Ragged twin of :func:`masked_topk_arena`: boolean mask → additive
    mask, block size fitted to VMEM, per-query k as data against the
    static ``k`` ceiling."""
    n, d = emb.shape
    blk = fit_block_rows(n, d, emb.dtype.itemsize)
    assert blk, f"arena rows {n} have no VMEM-fitting block divisor >= 512"
    madd = jnp.where(mask, 0.0, NEG).astype(jnp.float32)
    return pallas_masked_topk_ragged(emb, madd, queries.astype(emb.dtype),
                                     k_q, k=k, block_rows=blk,
                                     interpret=not on_tpu())


def masked_topk_auto(emb, madd, queries, k=10, block_rows=4096):
    """Dispatch: pallas on TPU, interpret-mode pallas elsewhere."""
    return pallas_masked_topk(emb, madd, queries, k=k, block_rows=block_rows,
                              interpret=not on_tpu())


# One embedding block's VMEM budget: blocks are double-buffered and the
# scoped-vmem ceiling is 16 MB, so ~6 MB per block leaves room for the
# [Q, blk] f32 score tile and outputs (blk=8192 at d=768 OOMs — measured).
_BLOCK_BYTES = 6 * 1024 * 1024


def fit_block_rows(n: int, d: int, itemsize: int) -> int:
    """Largest power-of-two block ≤ 4096 that fits the VMEM budget AND
    divides ``n``; 0 when no block ≥ 512 divides n (caller falls back to the
    XLA path). Shared by the single-chip arena dispatch and the shard_map
    per-shard dispatch, whose local row counts are N/n_shards."""
    blk = 4096
    while blk > 512 and blk * d * itemsize > _BLOCK_BYTES:
        blk //= 2
    while blk >= 512 and n % blk != 0:
        blk //= 2
    return blk if blk >= 512 else 0


def masked_topk_arena(emb: jax.Array, mask: jax.Array, queries: jax.Array,
                      k: int = 10) -> Tuple[jax.Array, jax.Array]:
    """The ``arena_search`` serving path: boolean mask → additive mask, block
    size fitted to VMEM for the embedding dtype/width. Requires
    ``emb.shape[0] %% block == 0`` — arenas allocate row counts in
    ``state.TOPK_BLOCK`` multiples precisely so no padded copy of the matrix
    is ever made here."""
    n, d = emb.shape
    blk = fit_block_rows(n, d, emb.dtype.itemsize)
    assert blk, f"arena rows {n} have no VMEM-fitting block divisor >= 512"
    madd = jnp.where(mask, 0.0, NEG).astype(jnp.float32)
    return pallas_masked_topk(emb, madd, queries.astype(emb.dtype),
                              k=k, block_rows=blk, interpret=not on_tpu())
