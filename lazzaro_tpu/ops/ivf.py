"""Coarse-to-fine retrieval: centroid prefilter over the HBM arena.

The exact scan reads all N·d bytes per query batch (~1.9 ms floor at
1M×768 bf16 on a v5e). This is the OTHER honest route below that floor
(VERDICT r3 next #7, SURVEY §7.2's hierarchy-as-coarse-stage): spherical
k-means clusters the arena; a query scores C centroids (C ≈ √N), visits
only the ``nprobe`` nearest clusters' member rows, and scans those — HBM
traffic per query drops from N·d to ~(C + nprobe·N/C)·d (analytically
~25× at 1M rows with C=1024, nprobe=8). Approximate by construction:
recall is controlled by ``nprobe`` (= exact when nprobe == C, because
every alive row lives in exactly one cluster or the residual).

MEASURED (r5, clustered bench corpus, recall@5 vs the exact oracle —
``bench_artifacts/r5_kernels_100k_cpu.json``, 100k×768, single-core CPU,
backend-independent recall): nprobe=4 → 0.869 recall at 1.2 ms; nprobe=8
→ 0.884 at 4.0 ms; nprobe=16 → 0.938 at 7.1 ms; exact scan 60.7 ms —
an 8-50× CPU latency ratio at the stated recall; on a TPU: not measured.

Freshness without per-write rebuilds (the same sealed/fresh split as the
ArrowStore's LSM segments): rows added after a build go to a RESIDUAL set
that every search scans exactly; a periodic rebuild folds them into the
clusters. Skew is bounded the same way — clusters overflow their fixed
member capacity into the residual, so no row is ever silently dropped.

Reference analog: LanceDB's IVF-PQ ANN index over the raw vectors
(vector_store.py's table ANN) — here the coarse stage is an explicit,
testable kernel instead of a library call.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from lazzaro_tpu.ops.chunking import chunked_map

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("n_clusters", "iters"))
def _kmeans_device(emb: jax.Array, mask: jax.Array, init_rows: jax.Array,
                   n_clusters: int, iters: int) -> jax.Array:
    """Spherical k-means (cosine): normalized centroids [C, d]. Dead rows
    never contribute; a cluster that goes empty keeps its old centroid."""
    x = emb.astype(jnp.float32)
    cent = x[init_rows]                                    # [C, d]

    def assign(c):
        def chunk(rows):
            scores = jnp.dot(x[rows], c.T,
                             preferred_element_type=jnp.float32)
            return jnp.argmax(scores, axis=1).astype(jnp.int32)
        return chunked_map(chunk, jnp.arange(x.shape[0], dtype=jnp.int32))

    def step(c, _):
        a = jnp.where(mask, assign(c), n_clusters)         # dead -> bucket C
        sums = jnp.zeros((n_clusters + 1, x.shape[1]), jnp.float32
                         ).at[a].add(jnp.where(mask[:, None], x, 0.0))
        counts = jnp.zeros((n_clusters + 1,), jnp.float32).at[a].add(
            mask.astype(jnp.float32))
        new = sums[:n_clusters]
        norms = jnp.linalg.norm(new, axis=1, keepdims=True)
        new = jnp.where((counts[:n_clusters, None] > 0) & (norms > 1e-9),
                        new / jnp.maximum(norms, 1e-9), c)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return cent


@jax.jit
def _assign_device(emb: jax.Array, mask: jax.Array, cent: jax.Array
                   ) -> jax.Array:
    """Final cluster assignment [N] (dead rows -> -1)."""
    x = emb.astype(jnp.float32)

    def chunk(rows):
        scores = jnp.dot(x[rows], cent.T, preferred_element_type=jnp.float32)
        return jnp.argmax(scores, axis=1).astype(jnp.int32)

    a = chunked_map(chunk, jnp.arange(x.shape[0], dtype=jnp.int32))
    return jnp.where(mask, a, -1)


@dataclass
class IvfIndex:
    centroids: jax.Array     # [C, d] f32, L2-normalized
    members: jax.Array       # [C, M] i32 arena rows, -1 padded
    residual: jax.Array      # [R] i32 arena rows scanned exactly, -1 padded
    built_rows: int          # alive rows at build time (staleness signal)

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]


def _pow2(n: int, lo: int = 8) -> int:
    return max(lo, 1 << max(0, int(n - 1)).bit_length())


def build_ivf(emb: jax.Array, mask_np: np.ndarray,
              n_clusters: Optional[int] = None, iters: int = 8,
              member_cap_factor: int = 4, seed: int = 0) -> IvfIndex:
    """Cluster the alive rows and build the fixed-shape member table.

    ``member_cap_factor``: per-cluster capacity = factor · N/C (pow2-
    rounded); rows beyond a cluster's capacity overflow into the residual,
    so skewed data degrades to a bigger exact scan — never to dropped
    rows."""
    alive_rows = np.nonzero(mask_np)[0]
    n_alive = len(alive_rows)
    if n_alive == 0:
        raise ValueError("cannot build an IVF over an empty arena")
    if n_clusters is None:
        n_clusters = max(4, _pow2(int(np.sqrt(n_alive)), lo=4))
    n_clusters = min(n_clusters, n_alive)
    rng = np.random.default_rng(seed)
    init = rng.choice(alive_rows, size=n_clusters, replace=False)

    mask = jnp.asarray(mask_np)
    cent = _kmeans_device(emb, mask, jnp.asarray(init, jnp.int32),
                          n_clusters, iters)
    assign = np.asarray(_assign_device(emb, mask, cent))

    cap = _pow2(member_cap_factor * max(1, n_alive // n_clusters))
    members = np.full((n_clusters, cap), -1, np.int32)
    # vectorized table build: stable-sort rows by cluster, slice per
    # cluster (a per-row Python loop costs seconds of host time at 1M)
    a = assign[alive_rows]
    order = np.argsort(a, kind="stable")
    sorted_rows = alive_rows[order].astype(np.int32)
    counts = np.bincount(a, minlength=n_clusters)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    overflow_parts = []
    for c in range(n_clusters):            # C iterations, not N
        seg = sorted_rows[starts[c]:starts[c] + counts[c]]
        members[c, :min(cap, len(seg))] = seg[:cap]
        if len(seg) > cap:
            overflow_parts.append(seg[cap:])
    overflow = (np.concatenate(overflow_parts) if overflow_parts
                else np.zeros((0,), np.int32))
    residual = np.full((_pow2(len(overflow), lo=8),), -1, np.int32)
    residual[:len(overflow)] = overflow
    return IvfIndex(centroids=cent, members=jnp.asarray(members),
                    residual=jnp.asarray(residual), built_rows=n_alive)


def online_counts(members) -> jax.Array:
    """Per-cluster live-prefix occupancy of a member table — the ``counts``
    column the online-IVF ingest kernels (``core.state._ivf_online_update``)
    append through. Build-time tables are dense prefixes per cluster, so
    the live count IS the append cursor."""
    m = jnp.asarray(members)
    return (m >= 0).sum(axis=-1).astype(jnp.int32)


@jax.jit
def _staleness_device(emb: jax.Array, mask: jax.Array, cent: jax.Array,
                      members: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Device side of :func:`assignment_staleness`: count member-table
    slots whose row's argmax centroid (under the CURRENT centroids) is no
    longer the cluster the slot lives in."""
    assign = _assign_device(emb, mask, cent)               # [N], dead -> -1
    safe = jnp.maximum(members, 0)
    C = cent.shape[0]
    ok = (members >= 0) & (assign[safe] >= 0)
    stale = ok & (assign[safe] != jnp.arange(C)[:, None])
    return stale.sum(), ok.sum()


def assignment_staleness(emb, mask_np, cent, members) -> float:
    """Fraction of live member-table slots whose cluster no longer matches
    the row's argmax under the current centroids — the staleness number
    online IVF bounds (mini-batch centroid drift can strand old members;
    an offline rebuild by construction measures 0.0 here). An O(N·C)
    DIAGNOSTIC probe for bench/maintenance — never the serving path."""
    stale, live = _staleness_device(emb, jnp.asarray(mask_np),
                                    jnp.asarray(cent), jnp.asarray(members))
    live = int(live)
    return float(stale) / live if live else 0.0


def gather_rows(centroids: jax.Array, members: jax.Array,
                extras: jax.Array, q_c: jax.Array, nprobe: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Device-friendly coarse gather, the single place EVERY member scan —
    the classic ``ivf_search``, ``ops.pq.ivf_pq_search``, and the fused
    serving kernel (``core.state.search_fused_ivf_ragged``) — assembles its
    candidate row set, so the 'identical candidate set' invariant between
    the paths is structural, not a docstring promise: score C centroids,
    take the ``nprobe`` best clusters, and return their member rows plus
    ``extras`` (the sealed residual, and for the fused path the fresh
    residual + super rows appended by the host).

    The ``optimization_barrier`` after the cluster top-k is the PR 2
    consumer-split fix: the visited-cluster ids feed both the member
    gather and (through the scores built on it) the packed readback —
    without the barrier XLA may clone the full [qc, C] centroid sort per
    consumer.

    Returns ``(cand [qc, L], safe [qc, L])`` with L = nprobe·M + len
    (extras); ``safe = max(cand, 0)`` is the gather-legal view (padding
    is -1). Callers apply their own validity mask (single-tenant kernels
    a [N] mask, the fused kernel a per-query tenant column)."""
    cs = jnp.dot(q_c, centroids.T,
                 preferred_element_type=jnp.float32)       # [qc, C]
    _, cids = jax.lax.top_k(cs, nprobe)                    # [qc, P]
    cids = jax.lax.optimization_barrier(cids)
    cand = members[cids].reshape(q_c.shape[0], -1)         # [qc, P*M]
    cand = jnp.concatenate(
        [cand, jnp.broadcast_to(extras[None, :],
                                (q_c.shape[0], extras.shape[0]))],
        axis=1)                                            # [qc, P*M+E]
    return cand, jnp.maximum(cand, 0)


def gather_candidates(centroids: jax.Array, members: jax.Array,
                      residual: jax.Array, mask: jax.Array, q_c: jax.Array,
                      nprobe: int
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-tenant view over :func:`gather_rows` (the exact and PQ member
    scans): adds the [N] alive/tenant mask and returns
    ``(cand, safe_rows, valid_mask)``."""
    cand, safe = gather_rows(centroids, members, residual, q_c, nprobe)
    valid = (cand >= 0) & mask[safe]
    return cand, safe, valid


def pack_extras(residual: np.ndarray, fresh_rows, super_rows) -> np.ndarray:
    """Host-side export of the exact-scan row set for the fused serving
    kernel: sealed-build residual ++ fresh rows (added post-build) ++ the
    tenant-agnostic super-node rows, -1-padded to a pow2 bucket so jit
    specializations stay bounded. Super rows ride here so the in-kernel
    super-gate top-1 sees EVERY super node exactly — the gate threshold
    (0.4) must never depend on whether a centroid routed near a super
    node. A super row can then appear twice (its cluster slot + here);
    duplicates only matter for the ANN tier, where the kernel's top-k
    dedup drops them (top-1 gates are duplicate-immune anyway)."""
    base = np.asarray(residual)
    comb = np.concatenate([base[base >= 0],
                           np.asarray(list(fresh_rows), np.int32),
                           np.asarray(list(super_rows), np.int32)])
    padded = np.full((_pow2(len(comb)),), -1, np.int32)
    padded[:len(comb)] = comb
    return padded


def shard_serve_tables(members: np.ndarray, extras: np.ndarray,
                       n_shards: int, part_rows: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Split the GLOBAL member/extras tables into per-shard LOCAL-row
    tables for the distributed fused IVF kernel
    (``core.state.make_fused_sharded`` mode="ivf"): shard ``p`` keeps only
    the rows it owns (global rows ``[p·part_rows, (p+1)·part_rows)``),
    re-indexed to local offsets and left-packed per cluster, -1 padded.
    The union over shards is exactly the global candidate set, so the
    distributed scan visits the same rows as the single-chip kernel —
    each from the chip whose HBM holds it. Every per-(shard, cluster)
    member list fits the global member cap, so the stacked table keeps
    the global [C, M] geometry and the local gather never widens."""
    members = np.asarray(members, np.int64)
    extras = np.asarray(extras, np.int64)
    C, M = members.shape
    out_m = np.full((n_shards, C, M), -1, np.int32)
    out_e = np.full((n_shards, max(8, extras.shape[0])), -1, np.int32)
    for p in range(n_shards):
        lo, hi = p * part_rows, (p + 1) * part_rows
        msk = (members >= lo) & (members < hi)
        # left-pack per cluster: stable-sort selected-first
        order = np.argsort(~msk, axis=1, kind="stable")
        out_m[p] = np.take_along_axis(
            np.where(msk, members - lo, -1), order, axis=1).astype(np.int32)
        sel = extras[(extras >= lo) & (extras < hi)] - lo
        out_e[p, :len(sel)] = sel.astype(np.int32)
    return out_m, out_e


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "q_chunk"))
def ivf_search(centroids: jax.Array, members: jax.Array, residual: jax.Array,
               emb: jax.Array, mask: jax.Array, queries: jax.Array,
               k: int, nprobe: int = 8, q_chunk: int = 8
               ) -> Tuple[jax.Array, jax.Array]:
    """Coarse (centroid) → fine (member gather) masked top-k.

    Per query: the shared coarse stage assembles candidates, which are
    scored exactly and top-k'd. Candidate tensors are
    [q_chunk, nprobe·M + R, d], so queries stream in small chunks to
    bound the gather footprint."""
    q = queries.astype(jnp.float32)
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    nprobe = min(nprobe, centroids.shape[0])

    def chunk(q_c):                                        # [qc, d]
        cand, safe, valid = gather_candidates(centroids, members, residual,
                                              mask, q_c, nprobe)
        vecs = emb[safe].astype(jnp.float32)               # [qc, L, d]
        scores = jnp.einsum("qld,qd->ql", vecs, q_c)
        scores = jnp.where(valid, scores, NEG_INF)
        ts, pos = jax.lax.top_k(scores, k)
        return ts, jnp.take_along_axis(cand, pos, axis=1)

    return chunked_map(chunk, q, chunk=q_chunk)