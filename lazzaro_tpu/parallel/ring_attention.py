"""Ring attention: sequence-parallel causal attention over a device ring.

Long-context support the reference cannot have (it never runs a model; its
"long context" strategy is the memory system itself — SURVEY §5). For the
in-tree decoder LM, sequences are sharded along time over a mesh axis; each
device holds a Q/K/V chunk, computes flash-style streaming-softmax block
attention against the K/V chunk it currently holds, and passes K/V around the
ring with ``ppermute`` — n_devices steps, each overlapping compute with an
ICI hop. Memory per chip is O(T/n · d) instead of O(T · d).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG = -1e30


def _block_attn(q, k, v, q_pos, k_pos, m, l, acc, scale):
    """One streaming-softmax accumulation step.

    q [B,Tq,H,D], k/v [B,Tk,Hkv,D] with H %% Hkv == 0 (the GQA repeat is
    done HERE, per block, so ring hops move only Hkv heads), *_pos
    [Tq]/[Tk] global positions, m/l [B,H,Tq] running max / denominator,
    acc [B,H,Tq,D]."""
    H, Hkv = q.shape[2], k.shape[2]
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None])[None, None, :, :]  # causal
    scores = jnp.where(mask, scores, NEG)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(mask, p, 0.0)                                 # kill dead blocks
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def make_ring_attention(mesh: Mesh, axis: str = "sp",
                        batch_axis: str | None = None):
    """Returns ``attn(q, k, v) -> out`` where q/k/v are [B, T, H, D] sharded
    along T over ``axis`` (and along B over ``batch_axis`` when given, so the
    ring composes with data parallelism inside one mesh); output has the same
    sharding. Causal; assumes global positions 0..T-1 in contiguous blocks
    (GSPMD's block partitioning of the T dim)."""
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local_fn(q, k, v):
        B, Tc, H, D = q.shape
        scale = 1.0 / np.sqrt(D)
        i = jax.lax.axis_index(axis)
        q_pos = i * Tc + jnp.arange(Tc)

        m0 = jnp.full((B, H, Tc), NEG, jnp.float32)
        l0 = jnp.zeros((B, H, Tc), jnp.float32)
        acc0 = jnp.zeros((B, H, Tc, D), jnp.float32)

        def step(s, carry):
            m, l, acc, k_cur, v_cur = carry
            # after s hops, we hold the chunk originally on device (i - s) mod n
            j = (i - s) % n
            k_pos = j * Tc + jnp.arange(Tc)
            m, l, acc = _block_attn(q, k_cur, v_cur, q_pos, k_pos, m, l, acc, scale)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc, _, _ = jax.lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)

    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axis, axis, None, None),) * 3,
        out_specs=P(batch_axis, axis, None, None),
        check_vma=False,
    )
    return jax.jit(mapped)


def reference_causal_attention(q, k, v) -> jax.Array:
    """Dense single-device causal attention (correctness oracle)."""
    B, T, H, D = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
