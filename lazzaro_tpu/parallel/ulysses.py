"""Ulysses (DeepSpeed-style) sequence parallelism via all-to-all.

The second canonical long-context scheme, complementing ring attention
(``parallel/ring_attention.py``): instead of streaming K/V around a device
ring (n ``ppermute`` hops, O(n) latency), one ``all_to_all`` re-shards the
activations from sequence-sharded to head-sharded, each device computes FULL
dense attention over the whole sequence for its subset of heads, and a second
``all_to_all`` restores sequence sharding. Two collectives total, so it wins
when heads ≥ devices and the sequence fits per-device HBM after the swap;
ring wins at extreme lengths where the full sequence never fits. The
reference has no model execution at all (SURVEY §2.3) — both schemes are
TPU-native capabilities of the in-tree LM stack.
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_ulysses_attention(mesh: Mesh, axis: str = "sp"):
    """Returns ``attn(q, k, v) -> out`` for q/k/v [B, T, H, D] sharded along
    T over ``axis`` (same contract as ``make_ring_attention``). Causal.

    Requires H % n_devices == 0: the all-to-all scatters heads across the
    axis while gathering the sequence.
    """
    n = mesh.shape[axis]

    def local_fn(q, k, v):
        B, Tc, H, D = q.shape          # local chunk: T/n positions, all H heads
        if H % n:
            raise ValueError(f"ulysses needs heads ({H}) divisible by mesh "
                             f"axis '{axis}' ({n}); use ring attention")
        if k.shape[2] != H or v.shape[2] != H:
            raise ValueError("ulysses requires full MHA (kv heads == q heads);"
                             " repeat GQA kv heads first or use ring attention")

        def seq_to_heads(x):
            # [B, Tc, H, D] seq-sharded → [B, n·Tc, H/n, D] head-sharded.
            # split_axis=2 scatters heads over the axis; concat_axis=1
            # gathers the full sequence. tiled=True keeps pure reshape
            # semantics (no added major axis).
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        # Full-sequence dense causal attention on the head shard — the same
        # oracle formulation ring attention is verified against.
        from lazzaro_tpu.parallel.ring_attention import reference_causal_attention
        return heads_to_seq(reference_causal_attention(qg, kg, vg))

    mapped = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )
    return jax.jit(mapped)
