"""Device-side streaming map — the "[chunk, N] tile bounds HBM" rule, once.

Whole-arena scans (search, linking, pairwise merge) score a [B, capacity+1]
f32 matrix; at 1M rows that is ~4 GB per 1k queries, and the naive all-pairs
form is ~4 TB. Every such kernel therefore streams row-chunks through
``lax.map`` INSIDE one jitted dispatch: HBM holds a single [chunk, N] tile
(512×1M×4 B ≈ 2 GB), while the host still pays exactly ONE round trip for
the whole batch (a host-side chunk loop would pay one per chunk).

This module is that scaffold in one place; ``core/state.py`` and
``ops/graphops.py`` express their kernels as a per-chunk body and call
:func:`chunked_map`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# [QUERY_CHUNK, capacity+1] f32 is the HBM high-water mark of every arena
# scan — ~2 GB transient beside a 1.5 GB bf16 arena on a 16 GB chip.
QUERY_CHUNK = 512


def nt_dot(q: jax.Array, rows: jax.Array) -> jax.Array:
    """``q @ rows.T`` as a direct dim-1×dim-1 contraction.

    Numerically identical to ``jnp.dot(q, rows.T)`` and lowers to the same
    MXU contraction on TPU — but on the CPU backend the explicit ``.T``
    lowers as transpose-then-dot, which misses the fast bf16 gemm path
    (measured 31 vs 128 GFLOP/s at [4096,768]×[262k,768] on this host).
    Every whole-arena scan scores through this helper."""
    return jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def chunked_map_multi(fn, arrays, chunk: int = QUERY_CHUNK):
    """``chunked_map`` over SEVERAL same-leading-dim arrays at once.

    The fused retrieval kernel maps per-query metadata (tenant id, gate
    flag, boost flag) alongside the query rows; ``lax.map`` happily maps a
    tuple pytree, so the padding/reshape scaffold is the only thing this
    adds over :func:`chunked_map`."""
    b = arrays[0].shape[0]
    if b <= chunk:
        return fn(*arrays)
    nc = -(-b // chunk)

    def prep(a):
        pad = [(0, nc * chunk - b)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad).reshape((nc, chunk) + a.shape[1:])

    outs = jax.lax.map(lambda t: fn(*t), tuple(prep(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((nc * chunk,) + o.shape[2:])[:b], outs)


def chunked_map(fn, xs: jax.Array, chunk: int = QUERY_CHUNK):
    """Apply ``fn`` ([C, ...] → pytree of [C, ...]) to row-chunks of ``xs``.

    Traces into the CURRENT computation (no extra dispatch): small batches
    call ``fn`` directly; larger ones are zero-padded to a chunk multiple,
    streamed via ``lax.map``, and the padding rows are sliced back off every
    output leaf. Zero-padding is safe because callers discard the padded
    tail — pad rows just recompute row 0's answer."""
    b = xs.shape[0]
    if b <= chunk:
        return fn(xs)
    nc = -(-b // chunk)
    pad = [(0, nc * chunk - b)] + [(0, 0)] * (xs.ndim - 1)
    xs_p = jnp.pad(xs, pad).reshape((nc, chunk) + xs.shape[1:])
    outs = jax.lax.map(fn, xs_p)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((nc * chunk,) + o.shape[2:])[:b], outs)
