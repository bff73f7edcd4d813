"""Int8 serving path: quantized arena scan at half the HBM traffic.

Retrieval at 1M rows is HBM-bound: a bf16 arena streams N·d·2 bytes per
scan (~1.5 GB at 1M×768 — a ~1.9 ms floor on a v5e's 0.82 TB/s). Rows are
L2-normalized, so components live in [-1, 1] and symmetric per-row int8
quantization (x ≈ scale_r · q_r, q ∈ [-127, 127]) costs ~0.4% cosine error
— far inside the 0.95/0.5 thresholds the memory system acts on — while
halving scan bytes AND running the dot products on the MXU's int8 path
(2× bf16 peak). This is VERDICT r3 next-step #7's "int8 arena": the honest
route below the bf16 bandwidth floor, as opposed to a faster clock.

The quantized copy is a SERVING SHADOW: the bf16/f32 arena stays the
mutable master (scatter updates, decay sweeps, exact merge thresholds).
Freshness is incremental where it matters: the fused ingest program
scatters codes+scales for freshly written rows in-kernel
(``core/state._shadow_scatter`` — O(batch)), and ``core/index.py``
re-quantizes lazily only when no maintained shadow exists (first build,
arena growth, mesh path). Reference analog: LanceDB's ANN index over the
raw vectors (vector_store.py:132-140) — same split of exact store vs.
scan-optimized replica.

Serving consumes the shadow two ways: the classic ``quantized_topk`` scan
below (pure int8 ranking over a dense ``[chunk, N]`` tile — the non-fused
``search_batch`` path; mesh path via ops/topk.make_sharded_int8_topk), and
the single-dispatch fused chat-turn program
(``core/state.search_fused_quant_ragged``), which uses the int8 scores only
as a COARSE top-(k+slack) stage — selected WHILE THE SHADOW STREAMS, block by
block, by ``ops/pallas_topk.blocked_two_tier_q8`` (the kernel
``lz_select_scan_q8``; no ``[queries, rows]`` tile, no full-width sort) —
and exactly rescores the survivors from the master: returned scores and
threshold verdicts never carry quantization error there. The shadow itself
is built by ``quantize_arena``: ONE program over the arena, blocked inside.

MEASURED on the chip (TPU v5e, 5M rows x 768, 64 queries a dispatch,
``fill.q8``; PERF.md section 6, PRs 35 and 36): the dense form of the coarse
stage — one ``[64, 5M]`` int32 tile, its f32 twin and two full-width
``lax.top_k`` — took 166.6 ms a dispatch, 2.85% of what the codes' bytes
allow, sixteen times SLOWER than the exact scan's 10.2 ms: halving the bytes
bought nothing while the selection was a sort. Selecting while the codes
stream (PR 36) takes 8.59 ms a dispatch, 55% of that roofline — the stream
alone 5.3 ms against the exact scan's 10.2, the rest the selection of 64 x
136 survivors — and serves 6,600-7,000 requests a second where the dense
form served 381 and the exact deployment under the same callers ~6,200. On
a 1-core CPU int8 is slower than exact either way
(67.4 ms vs 60.7 ms at 100k x 768, ``bench_artifacts/r5_kernels_100k_cpu.json``:
no int8 SIMD path there; a CPU timing, not a device number).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from lazzaro_tpu.ops.chunking import chunked_map

NEG_INF = -1e30


@jax.jit
def quantize_rows(emb: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8: returns (q [N, d] i8, scale [N] f32) with
    x ≈ scale[r] · q[r]. Zero rows quantize to zeros with scale 0."""
    x = emb.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 0.0)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


# rows one step of the shadow build quantizes: its f32 temporary is this
# many rows, never the arena
SHADOW_BLOCK_ROWS = 65_536


def shadow_block_rows(n: int) -> int:
    """Rows per step of :func:`quantize_arena` for an arena of ``n`` rows:
    the largest multiple of 512 that divides ``n`` and is at most
    ``SHADOW_BLOCK_ROWS`` — or ``n`` itself (ONE step) for an arena that
    small, or one that no such block divides."""
    if n <= SHADOW_BLOCK_ROWS:
        return n
    blk = SHADOW_BLOCK_ROWS - SHADOW_BLOCK_ROWS % 512
    while blk >= 512 and n % blk:
        blk -= 512
    return blk if blk >= 512 else n


@jax.jit
def quantize_arena(emb: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """:func:`quantize_rows` over a whole arena as ONE program, blocked
    inside: ``shadow_block_rows`` rows a step, so the f32 temporary is a
    block's and the codes and scales are each written once, in place. What
    ``MemoryIndex._int8_shadow_for`` builds the serving shadow with."""
    n, d = emb.shape
    block = shadow_block_rows(n)
    if block == n:
        return quantize_rows(emb)
    q, scale = jax.lax.map(quantize_rows, emb.reshape(n // block, block, d))
    return q.reshape(n, d), scale.reshape(n)


def quantize_arena_sharded(mesh, axis: str):
    """:func:`quantize_arena` for an arena row-sharded over ``axis``: every
    chip quantizes its own rows, and the shadow comes out sharded like the
    master — still one program, and nothing crosses chips."""
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        quantize_arena, mesh=mesh, in_specs=P(axis, None),
        out_specs=(P(axis, None), P(axis))))


@functools.partial(jax.jit, static_argnames=("k",))
def quantized_topk(q_arena: jax.Array,    # [N, d] i8
                   scale: jax.Array,      # [N] f32
                   mask: jax.Array,       # [N] bool
                   queries: jax.Array,    # [Q, d] f32 (need not be normalized)
                   k: int) -> Tuple[jax.Array, jax.Array]:
    """Masked cosine top-k over the int8 shadow.

    The query is quantized per-row too, so the inner product runs int8×int8
    → int32 entirely on the MXU; the two scales multiply back in f32. Score
    error vs the exact scan is ≤ ~1e-2 absolute — ranking-stable for the
    system's 0.95 dedup / 0.5 link gates. Queries stream through the shared
    [chunk, N] tiles (ops/chunking.py) like every other arena scan."""
    qq, qscale = quantize_rows(queries)

    def chunk(idx_c):
        qq_c = qq[idx_c]                                       # [C, d] i8
        dots = jax.lax.dot_general(
            qq_c, q_arena, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                  # [C, N] i32
        scores = (dots.astype(jnp.float32)
                  * qscale[idx_c][:, None] * scale[None, :])
        scores = jnp.where(mask[None, :], scores, NEG_INF)
        return jax.lax.top_k(scores, k)

    return chunked_map(chunk, jnp.arange(queries.shape[0], dtype=jnp.int32))
