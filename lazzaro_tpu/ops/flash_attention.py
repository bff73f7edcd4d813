"""Pallas TPU kernel: causal flash attention with GQA (online softmax).

The decoder LM's full-sequence attention (``models/llm.py`` Attention) is the
FLOPs-heavy op of on-TPU consolidation and training. The plain XLA path
materializes a [B, H, T, S] f32 score tensor in HBM; this kernel tiles Q into
VMEM blocks and streams K/V through VMEM one ``blk_k`` block per grid step
(accumulators live in VMEM scratch across the inner grid dimension), so the
score tensor never touches HBM, VMEM usage is independent of sequence length,
and the matmuls stay on the MXU in the input dtype (bf16) with f32
accumulation.

Grouped-query attention costs nothing here: the K/V BlockSpec index map sends
query head ``h`` to kv head ``h // rep``, so kv heads are never materialized
``rep`` times (the XLA path pays a ``jnp.repeat``).

The causal mask is END-ALIGNED: query row ``i`` (of T) attends keys
``0 .. (S - T) + i``, so chunked prefill — q = the last T positions of an
S-token context — is supported, with standard self-attention as the S == T
special case. Fully-masked kv blocks above the diagonal skip their compute
via predication.

The backward pass is a fused Pallas VJP: the forward stores one log-sum-exp
per query row (lanes-broadcast [B, H, T, 128] layout, the same residual
trick as jax's in-tree kernel) and the dQ / dK+dV kernels recompute each
score block from it — so NEITHER direction materializes a [T, S] tensor in
HBM and training peak memory is O(T·D). Measured on a v5e chip at
B=2, T=8192, H=8, D=128 (bf16): fwd+bwd temp HBM 101 MB vs 8,691 MB for the
materialized-scores XLA path (86×); at T=32768 the fused pair runs in
336 MB where the XLA backward would need ~137 GB for scores alone. The
dK/dV kernel accumulates a GQA group's rep query heads into one kv-head
block in VMEM scratch across two sequential grid dims.

Single-device semantics: under a tensor-parallel ('model') mesh the heads
axis is sharded and ``pallas_call`` has no partitioning rule — callers must
run it inside ``shard_map`` or fall back to the XLA path
(``models/llm.py`` guards this).

Use ``interpret=True`` (automatic off-TPU) for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lazzaro_tpu.ops.backend import on_tpu

NEG = -1e30
LANES = 128   # scalar-per-row scratch is stored broadcast across lanes


def _flash_kernel(blk_q: int, blk_k: int, nk: int, offset: int, scale: float):
    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
        iq = pl.program_id(2)
        jk = pl.program_id(3)

        @pl.when(jk == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, NEG)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # Query rows of this block cover absolute key window up to
        # offset + iq*blk_q + blk_q - 1; kv blocks fully above it skip.
        @pl.when(jk * blk_k <= offset + iq * blk_q + blk_q - 1)
        def _():
            q = q_ref[0, 0]                                   # [blk_q, D]
            k_blk = k_ref[0, 0]                               # [blk_k, D]
            v_blk = v_ref[0, 0]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
            row = offset + iq * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            col = jk * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(col <= row, s, NEG)
            m_prev = m_ref[:, :1]                             # [blk_q, 1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * corr + jnp.dot(
                p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(jk == nk - 1)
        def _():
            l = jnp.maximum(l_ref[:, :1], 1e-30)
            o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
            # log-sum-exp per row — the ONLY forward residual the fused
            # backward needs beyond q/k/v/o (softmax recomputes from it as
            # p = exp(s - lse), no [T, S] tensor ever stored in HBM).
            lse_ref[0, 0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                             (blk_q, LANES))

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("blk_q", "blk_k", "offset", "interpret"))
def _flash_fwd_bhtd(q: jax.Array, k: jax.Array, v: jax.Array,
                    blk_q: int, blk_k: int, offset: int,
                    interpret: bool):
    """q [B, H, T, D], k/v [B, Hkv, S, D] (pre-transposed; T % blk_q == 0,
    S % blk_k == 0). ``offset`` is the UNPADDED S - T: query row i attends
    absolute keys 0..offset+i (padded tail rows/cols are positionally
    outside every real window). → ([B, H, T, D] out, [B, H, T, LANES] f32
    LSE). The LSE is logically per-row ([B, H, T]) but stored broadcast
    across the 128 lanes so it stays (8, 128)-tileable on TPU — residual
    memory is T*128 f32 per head, 128× a per-row scalar would cost."""
    B, H, T, D = q.shape
    _, Hkv, S, _ = k.shape
    assert H % Hkv == 0, f"heads {H} not a multiple of kv heads {Hkv}"
    rep = H // Hkv
    nq, nk = T // blk_q, S // blk_k
    scale = 1.0 / np.sqrt(D)

    return pl.pallas_call(
        _flash_kernel(blk_q, blk_k, nk, offset, scale),
        grid=(B, H, nq, nk),          # jk innermost: accumulators in scratch
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            # rank-4 lanes-broadcast layout: (8, 128)-tileable on TPU (the
            # same trick jax's own flash kernel uses for its l/m residuals)
            jax.ShapeDtypeStruct((B, H, T, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # running max m
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # running sum l
            pltpu.VMEM((blk_q, D), jnp.float32),       # output accumulator
        ],
        # B/H/nq are independent → Megacore-parallel; only the innermost nk
        # dimension carries the scratch accumulators and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _flash_dq_kernel(blk_q: int, blk_k: int, nk: int, offset: int,
                     scale: float):
    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref):
        iq = pl.program_id(2)
        jk = pl.program_id(3)

        @pl.when(jk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(jk * blk_k <= offset + iq * blk_q + blk_q - 1)
        def _():
            q = q_ref[0, 0]
            k_blk = k_ref[0, 0]
            v_blk = v_ref[0, 0]
            do = do_ref[0, 0]
            lse = lse_ref[0, 0][:, :1]                        # [blk_q, 1]
            delta = delta_ref[0, 0][:, :1]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            row = offset + iq * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            col = jk * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(col <= row, s, NEG)
            p = jnp.exp(s - lse)                              # [blk_q, blk_k]
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            acc_ref[:] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                                  preferred_element_type=jnp.float32)

        @pl.when(jk == nk - 1)
        def _():
            dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)

    return kernel


def _flash_dkv_kernel(blk_q: int, blk_k: int, nq: int, rep: int,
                      offset: int, scale: float):
    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref, dk_acc, dv_acc):
        jk = pl.program_id(1)
        h = pl.program_id(2)
        iq = pl.program_id(3)

        # One (b, kv-head, kv-block) output accumulates over the rep query
        # heads of its GQA group AND all query blocks — both grid dims are
        # sequential, so the scratch lives across the whole group.
        @pl.when((h % rep == 0) & (iq == 0))
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(jk * blk_k <= offset + iq * blk_q + blk_q - 1)
        def _():
            q = q_ref[0, 0]
            k_blk = k_ref[0, 0]
            v_blk = v_ref[0, 0]
            do = do_ref[0, 0]
            lse = lse_ref[0, 0][:, :1]
            delta = delta_ref[0, 0][:, :1]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            row = offset + iq * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            col = jk * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(col <= row, s, NEG)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dv_acc[:] += jax.lax.dot_general(          # p^T @ do
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:] += jax.lax.dot_general(          # ds^T @ q
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when((h % rep == rep - 1) & (iq == nq - 1))
        def _():
            dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("blk_q", "blk_k", "offset", "interpret"))
def _flash_bwd_bhtd(q, k, v, o, lse, do, blk_q: int, blk_k: int,
                    offset: int, interpret: bool):
    """Fused backward: q/o/do [B, H, T, D], k/v [B, Hkv, S, D], lse
    [B, H, T, LANES] (the forward's lanes-broadcast residual; logically
    per-row) → (dq [B, H, T, D], dk [B, Hkv, S, D], dv [B, Hkv, S, D]).
    Scores are recomputed per block from the stored LSE — no [T, S] HBM
    tensor. The delta residual built below is likewise broadcast to
    [B, H, T, LANES]; each of lse and delta costs T*128 f32 per head."""
    B, H, T, D = q.shape
    _, Hkv, S, _ = k.shape
    rep = H // Hkv
    nq, nk = T // blk_q, S // blk_k
    scale = 1.0 / np.sqrt(D)
    delta = jnp.einsum("bhtd,bhtd->bht", do.astype(jnp.float32),
                       o.astype(jnp.float32))                 # [B, H, T]
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    dq = pl.pallas_call(
        _flash_dq_kernel(blk_q, blk_k, nk, offset, scale),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        _flash_dkv_kernel(blk_q, blk_k, nq, rep, offset, scale),
        # kv-block outermost-but-one; (h, iq) sequential so the GQA group's
        # partial sums stay resident in scratch until the group finishes.
        grid=(B, nk, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, j, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, j, h, i: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, j, h, i: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, D), lambda b, j, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, LANES),
                         lambda b, j, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_q, LANES),
                         lambda b, j, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, j, h, i: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, j, h, i: (b, h // rep, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, D), jnp.float32),
            pltpu.VMEM((blk_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def reference_attention(q, k, v, attn_mask, scale: float = 0.0,
                        softcap: float = 0.0):
    """Materialized-scores GQA attention — THE canonical einsum formulation,
    shared by the decoder's XLA path (``models/llm.py``), the flash VJP, and
    the parity tests. q [B,T,H,D], k/v [B,S,Hkv,D], attn_mask [B,T,S] (or
    broadcastable) → [B,T,H,D] in q's dtype.

    ``scale``: score multiplier; 0 → the standard 1/sqrt(head_dim).
    ``softcap``: >0 applies Gemma-2 logit softcapping cap·tanh(s/cap)
    BEFORE masking."""
    H, D = q.shape[2], q.shape[3]
    Hkv = k.shape[2]
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    s = s * (scale if scale > 0 else 1.0 / np.sqrt(D))
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(attn_mask[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _reference_gqa(q, k, v):
    """End-aligned causal reference — VJP + parity oracle."""
    T, S = q.shape[1], k.shape[1]
    row = (S - T) + jnp.arange(T)[:, None]
    col = jnp.arange(S)[None, :]
    return reference_attention(q, k, v, (col <= row)[None])


def _resolve(blk_q: int, blk_k: int, T: int, S: int, interpret):
    """Deterministic (block sizes, padded lengths, interpret) from shapes —
    shared by forward and backward so their grids always agree."""
    if interpret is None:
        interpret = not on_tpu()
    blk_q = min(blk_q, max(8, 1 << (T - 1).bit_length()))
    blk_k = min(blk_k, max(8, 1 << (S - 1).bit_length()))
    Tp = -(-T // blk_q) * blk_q
    Sp = -(-S // blk_k) * blk_k
    return blk_q, blk_k, Tp, Sp, interpret


def _pad_bhtd(x, Lp):
    """[B, L, H, D] → transposed [B, H, L, D], back-padded to Lp rows."""
    xt = jnp.moveaxis(x, 1, 2)
    L = xt.shape[2]
    if Lp != L:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
    return xt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Causal GQA flash attention, fused forward AND backward.

    q: [B, T, H, D]; k, v: [B, S, Hkv, D] with H % Hkv == 0 and S >= T. The
    causal diagonal is end-aligned: query row i attends keys 0..(S-T)+i
    (standard self-attention when S == T; chunked prefill when S > T).
    Sequence lengths are padded internally to the block size — padded kv
    columns fall outside every real row's causal window, so no explicit
    length mask is needed. Returns [B, T, H, D] in q's dtype.

    The VJP recomputes per-block scores from the stored log-sum-exp
    (forward residual), so neither direction ever materializes a [T, S]
    tensor in HBM — training peak memory is O(T·D), not O(T·S).
    """
    out, _, _ = _forward_with_residuals(q, k, v, blk_q, blk_k, interpret)
    return out


def _forward_with_residuals(q, k, v, blk_q, blk_k, interpret):
    B, T, H, D = q.shape
    S = k.shape[1]
    if S < T:
        raise ValueError(f"kv length {S} shorter than query length {T}")
    blk_q, blk_k, Tp, Sp, interpret = _resolve(blk_q, blk_k, T, S, interpret)
    # Back-pad both; the kernel masks by ABSOLUTE positions with the
    # unpadded offset S - T, so padded q rows are garbage (sliced off) and
    # padded kv columns sit beyond every real row's window.
    qt = _pad_bhtd(q, Tp)
    kt = _pad_bhtd(k, Sp)
    vt = _pad_bhtd(v, Sp)
    out_p, lse = _flash_fwd_bhtd(qt, kt, vt, blk_q, blk_k, S - T, interpret)
    return jnp.moveaxis(out_p[:, :, :T], 2, 1), out_p, lse


def _fwd(q, k, v, blk_q, blk_k, interpret):
    out, out_p, lse = _forward_with_residuals(q, k, v, blk_q, blk_k, interpret)
    return out, (q, k, v, out_p, lse)


def _bwd(blk_q, blk_k, interpret, res, g):
    q, k, v, out_p, lse = res
    T, S = q.shape[1], k.shape[1]
    blk_q, blk_k, Tp, Sp, interpret = _resolve(blk_q, blk_k, T, S, interpret)
    qt = _pad_bhtd(q, Tp)
    kt = _pad_bhtd(k, Sp)
    vt = _pad_bhtd(v, Sp)
    gt = _pad_bhtd(g, Tp)          # zero-padded rows contribute nothing
    dq, dk, dv = _flash_bwd_bhtd(qt, kt, vt, out_p, lse, gt,
                                 blk_q, blk_k, S - T, interpret)
    return (jnp.moveaxis(dq[:, :, :T], 2, 1),
            jnp.moveaxis(dk[:, :, :S], 2, 1),
            jnp.moveaxis(dv[:, :, :S], 2, 1))


flash_attention.defvjp(_fwd, _bwd)
