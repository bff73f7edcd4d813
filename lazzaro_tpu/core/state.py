"""Device-resident memory state: structure-of-arrays arena in HBM.

This is the TPU-native replacement for the reference's object graph of Python
dicts (``memory_shard.py`` node/edge dicts + ``vector_store.py`` LanceDB rows).
All numeric per-memory fields live in fixed-capacity device arrays so that the
hot operations — similarity search, decay sweeps, importance scoring, linking —
are single batched XLA programs instead of O(N) Python loops (reference hot
loops at ``memory_system.py:464-470``, ``:797-836``, ``:838-891``).

Design notes (SURVEY §7.1):
- Static shapes: capacity is fixed per-compile; growth doubles capacity on the
  host (rare, amortized). Batched mutations pad their index vectors to
  power-of-two buckets so jit caches stay small.
- A sentinel scratch row at index ``capacity`` absorbs padded writes, so every
  scatter runs with a full static-size index vector and no masking branches.
- Embeddings are stored L2-normalized; cosine similarity is a plain dot
  product and retrieval is one matvec + ``lax.top_k``.
- ``tenant_id`` is a first-class column: multi-tenant isolation is a vectorized
  mask, replacing the reference's per-user SQL filters (``vector_store.py:118``).

State ownership & donation invariants
-------------------------------------
Every mutation kernel below ships as a PAIR of jit specializations over one
impl: the default export (e.g. ``arena_add``) donates its state argument(s)
so XLA scatters in place — a small write costs the scatter, not a full-arena
HBM copy (~1.5 GB at 1M×768 bf16) — and a ``*_copy`` twin keeps the classic
copy-on-write semantics. Donation consumes the input buffers: after a call
to the donated variant, EVERY live reference to the old state (the pytree
AND any leaf array pulled out of it) is deleted, and using one raises
``RuntimeError: Array has been deleted``.

Who may hold a reference to an ``ArenaState``/``EdgeState``:
- ``MemoryIndex`` owns the live state and is the only durable holder. Its
  mutation gate (``core/index.py``) donates ONLY when it can prove, under
  ``_state_lock``, that it holds the sole reference; otherwise it runs the
  ``*_copy`` twin, so a concurrent reader's snapshot is never invalidated.
- Readers (search/link/sweep paths) may snapshot ``index.state`` for the
  duration of one operation — the gate sees the raised refcount and falls
  back to copying. They must re-snapshot per operation, never cache across
  mutations.
- Direct callers of the donated module-level kernels (bench, tests) own
  the handoff themselves: treat the argument as consumed, thread the
  returned state forward, and never touch the old pytree or its leaves.
- A donated state pytree must hold one DISTINCT buffer per leaf (the
  runtime rejects donating the same buffer twice). ``init_arena`` /
  ``init_edges`` guarantee this; hand-built states must too.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from lazzaro_tpu.ops.backend import on_tpu
from lazzaro_tpu.ops.chunking import (QUERY_CHUNK, chunked_map,
                                      chunked_map_multi)
from lazzaro_tpu.utils.batching import (REQUEST_COLS, REQUEST_FIELDS,
                                        REQUEST_SCALARS)

NEG_INF = -1e30

TYPE_IDS = {"semantic": 0, "episodic": 1, "procedural": 2}
TYPE_NAMES = {v: k for k, v in TYPE_IDS.items()}


@struct.dataclass
class ArenaState:
    """Node arena. All arrays have leading dim ``capacity + 1`` (last row is
    the sentinel scratch row).

    Paged mode (ISSUE 17): when ``row_map``/``inv_map`` are set, ONLY ``emb``
    is pool-shaped ``[pool_n, d]`` — every other column stays logical
    ``[cap+1]``. ``row_map[logical] -> pool slot`` (unmapped rows point at
    the pool sentinel slot ``pool_n - 1``, which is all-zeros) and
    ``inv_map[slot] -> logical`` (free slots hold -1; the sentinel slot
    holds ``capacity``). Dense mode keeps both maps ``None`` and every
    kernel below reduces to the identity indirection."""

    emb: jax.Array            # [cap+1, d] dense / [pool_n, d] paged
    salience: jax.Array       # [cap+1] f32 in [0, 1]
    timestamp: jax.Array      # [cap+1] f32 seconds (host-epoch offset)
    last_accessed: jax.Array  # [cap+1] f32
    access_count: jax.Array   # [cap+1] i32
    type_id: jax.Array        # [cap+1] i32 (TYPE_IDS)
    shard_id: jax.Array       # [cap+1] i32
    tenant_id: jax.Array      # [cap+1] i32
    alive: jax.Array          # [cap+1] bool
    is_super: jax.Array       # [cap+1] bool
    row_map: Optional[jax.Array] = None   # [cap+1] i32 logical -> pool slot
    inv_map: Optional[jax.Array] = None   # [pool_n] i32 pool slot -> logical

    @property
    def capacity(self) -> int:
        # salience (not emb): emb is pool-shaped under paging
        return self.salience.shape[0] - 1

    @property
    def dim(self) -> int:
        return self.emb.shape[1]

    @property
    def pool_rows(self) -> int:
        """Physical embedding rows (== capacity + 1 when dense)."""
        return self.emb.shape[0]


@struct.dataclass
class PageTable:
    """Device-side free-list for the paged embedding pool (ISSUE 17).

    ``free_slots`` is a LIFO stack of pool slot indices with one trailing
    scratch entry (index ``pool_n - 1``) that absorbs masked pushes, so
    every push/pop runs with full static-size scatters and no branches.
    ``free_top`` is the live stack depth (entries below it are free pool
    slots; the newest free slot — popped first — sits at ``free_top - 1``)."""

    free_slots: jax.Array     # [pool_n] i32 (last entry = scratch)
    free_top: jax.Array       # [] i32

    @property
    def stack_cap(self) -> int:
        return self.free_slots.shape[0] - 1


@struct.dataclass
class EdgeState:
    """Edge arena: directed weighted associations, by arena row index."""

    src: jax.Array           # [E+1] i32 arena row of source node
    tgt: jax.Array           # [E+1] i32
    weight: jax.Array        # [E+1] f32 in [0, 1]
    co: jax.Array            # [E+1] i32 co-occurrence count
    last_updated: jax.Array  # [E+1] f32
    alive: jax.Array         # [E+1] bool
    tenant_id: jax.Array     # [E+1] i32 (tenant of the owning graph)

    @property
    def capacity(self) -> int:
        return self.src.shape[0] - 1


# Blocked-scan geometry: arenas of a block or more allocate row counts in
# TOPK_BLOCK multiples (== ops/pallas_topk.SELECT_BLOCK) so the blocked
# select-while-scanning core tiles them without a padded copy of the
# embedding matrix (extra rows are ordinary free capacity).
TOPK_BLOCK = 4096
PALLAS_TOPK_MIN_ROWS = 262_144


@struct.dataclass
class SemanticRing:
    """Device-resident semantic query cache (ISSUE 20): a small ring of
    recent query embeddings + their packed top-k serving results, probed
    as an extra candidate group inside every fused serving kernel. Row
    ``R`` (the last) is a scratch sentinel — ring writes that must be
    dropped scatter there, the probe never reads it (same trick as the
    arena's sentinel row).

    Validity and the rotation head are HOST-owned and ride each dispatch
    as sidecar inputs: invalidation (a lifecycle/dedup write touching a
    cached entry's rows, or a tenant-scoped flush) is a host bitmask
    flip, never a device dispatch. An entry is usable for a query only
    when tenant / gate flag / serving mode match, ``stored_k`` covers
    the query's k, the nprobe matches (IVF/PQ), and the stored
    embedding's cosine clears the threshold."""

    emb: jax.Array       # [R+1, d] f32 normalized query embeddings
    tenant: jax.Array    # [R+1] i32 owning tenant
    gate_on: jax.Array   # [R+1] bool gate flag the entry was served under
    mode: jax.Array      # [R+1] i32 serving-mode id (SEM_MODE_IDS)
    stored_k: jax.Array  # [R+1] i32 result depth the entry can serve
    nprobe: jax.Array    # [R+1] i32 probe width (0 for dense modes)
    gate_s: jax.Array    # [R+1] f32 cached gate score
    gate_r: jax.Array    # [R+1] i32 cached gate row
    ann_s: jax.Array     # [R+1, K] f32 cached top-k scores (desc, NEG_INF pad)
    ann_r: jax.Array     # [R+1, K] i32 cached top-k rows (sentinel pad)

    @property
    def slots(self) -> int:
        return self.tenant.shape[0] - 1

    @property
    def width(self) -> int:
        return self.ann_s.shape[1]


# Serving-mode ids for the ring's mode column: a cached entry only serves
# queries dispatched through the SAME kernel family (scores are not
# comparable across coarse stages, and the tiered window width differs).
SEM_MODE_IDS = {
    "exact": 0, "quant": 1, "ivf": 2, "ivf_quant": 3, "pq": 4,
    "tiered": 5, "ivf_tiered": 6, "pq_tiered": 7,
}


def init_semantic_ring(slots: int, dim: int, width: int,
                       row_sentinel: int = 0) -> SemanticRing:
    """Fresh (all-invalid, from the host's view) ring. ``width`` must
    cover the widest candidate window any serving kernel packs (k, or
    k+slack for the tiered families); ``row_sentinel`` pre-fills the row
    columns with the arena sentinel so a never-written slot can't alias
    row 0 even if misused."""
    if slots < 1:
        raise ValueError("semantic ring needs at least one slot")
    n = slots + 1
    return SemanticRing(
        emb=jnp.zeros((n, dim), jnp.float32),
        tenant=jnp.full((n,), -1, jnp.int32),
        gate_on=jnp.zeros((n,), bool),
        mode=jnp.full((n,), -1, jnp.int32),
        stored_k=jnp.zeros((n,), jnp.int32),
        nprobe=jnp.zeros((n,), jnp.int32),
        gate_s=jnp.full((n,), NEG_INF, jnp.float32),
        gate_r=jnp.full((n,), row_sentinel, jnp.int32),
        ann_s=jnp.full((n, width), NEG_INF, jnp.float32),
        ann_r=jnp.full((n, width), row_sentinel, jnp.int32),
    )


def init_arena(capacity: int, dim: int, dtype=jnp.float32) -> ArenaState:
    n = capacity + 1
    return ArenaState(
        emb=jnp.zeros((n, dim), dtype=dtype),
        salience=jnp.zeros((n,), jnp.float32),
        timestamp=jnp.zeros((n,), jnp.float32),
        last_accessed=jnp.zeros((n,), jnp.float32),
        access_count=jnp.zeros((n,), jnp.int32),
        type_id=jnp.zeros((n,), jnp.int32),
        shard_id=jnp.full((n,), -1, jnp.int32),
        tenant_id=jnp.full((n,), -1, jnp.int32),
        alive=jnp.zeros((n,), bool),
        is_super=jnp.zeros((n,), bool),
    )


def init_edges(capacity: int) -> EdgeState:
    n = capacity + 1
    return EdgeState(
        src=jnp.full((n,), -1, jnp.int32),
        tgt=jnp.full((n,), -1, jnp.int32),
        weight=jnp.zeros((n,), jnp.float32),
        co=jnp.zeros((n,), jnp.int32),
        last_updated=jnp.zeros((n,), jnp.float32),
        alive=jnp.zeros((n,), bool),
        tenant_id=jnp.full((n,), -1, jnp.int32),
    )


def grow_arena(state: ArenaState, new_capacity: int) -> ArenaState:
    """Host-side reallocation (not jitted; rare, amortized O(1))."""
    old = state.capacity
    assert new_capacity > old
    fresh = init_arena(new_capacity, state.dim, state.emb.dtype)

    def copy(new, cur):
        return new.at[:old].set(cur[:old])

    return ArenaState(
        emb=copy(fresh.emb, state.emb),
        salience=copy(fresh.salience, state.salience),
        timestamp=copy(fresh.timestamp, state.timestamp),
        last_accessed=copy(fresh.last_accessed, state.last_accessed),
        access_count=copy(fresh.access_count, state.access_count),
        type_id=copy(fresh.type_id, state.type_id),
        shard_id=copy(fresh.shard_id, state.shard_id),
        tenant_id=copy(fresh.tenant_id, state.tenant_id),
        alive=copy(fresh.alive, state.alive),
        is_super=copy(fresh.is_super, state.is_super),
    )


def grow_edges(state: EdgeState, new_capacity: int) -> EdgeState:
    old = state.capacity
    assert new_capacity > old
    fresh = init_edges(new_capacity)

    def copy(new, cur):
        return new.at[:old].set(cur[:old])

    return EdgeState(
        src=copy(fresh.src, state.src),
        tgt=copy(fresh.tgt, state.tgt),
        weight=copy(fresh.weight, state.weight),
        co=copy(fresh.co, state.co),
        last_updated=copy(fresh.last_updated, state.last_updated),
        alive=copy(fresh.alive, state.alive),
        tenant_id=copy(fresh.tenant_id, state.tenant_id),
    )


# ---------------------------------------------------------------------------
# Row-sharded creation and growth (mesh path). A pod arena is larger than
# one chip, so it can never be made whole and resharded afterwards: every
# column is created, and grown, in its shards. ``make(capacity)`` is
# ``init_arena`` / ``init_edges`` with the widths bound; its fills are the
# same for every row, so a chip's shard is ``make`` of the shard's rows.
# ---------------------------------------------------------------------------


def _row_specs(tree, axis: str):
    from jax.sharding import PartitionSpec as P

    return jax.tree_util.tree_map(
        lambda a: P(axis, None) if a.ndim == 2 else P(axis), tree)


def _shard_rows(total: int, mesh, axis: str) -> int:
    local_n, rest = divmod(total, mesh.shape[axis])
    if rest:
        raise ValueError(f"{total} rows do not divide over the "
                         f"{mesh.shape[axis]} shards of axis {axis!r}")
    return local_n


def sharded_init(make: Callable, capacity: int, mesh, axis: str) -> Callable:
    """The compiled program that creates ``make(capacity)`` in its row
    shards: called with nothing, each chip makes the ``(capacity + 1) / n``
    rows it owns, and no chip ever holds a whole column (one ``shard_map``
    with no input and no collective)."""
    from jax import shard_map

    local = functools.partial(make, _shard_rows(capacity + 1, mesh, axis) - 1)
    specs = _row_specs(jax.eval_shape(local), axis)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(), out_specs=specs,
                             check_vma=False))


def grow_sharded(make: Callable, state, new_capacity: int, mesh, axis: str):
    """The row-sharded twin of ``grow_arena`` / ``grow_edges``: rows
    ``[0, old capacity)`` keep their GLOBAL numbers (the host's maps, the
    edges and the CSR all speak them) and the rest are fresh.

    Shards are contiguous row blocks, so a grown arena is blocked anew: new
    shard ``s`` holds the global rows ``[s·m', (s+1)·m')``, which lay on the
    old shards ``s`` and after. Growth therefore MOVES rows between chips
    (a per-shard pad would renumber them): each chip makes its new shard
    fresh and takes in the old shards that overlap it, one ``ppermute``
    (chip ``s + d`` to chip ``s``) for each distance ``d`` at which any pair
    overlaps. A chip holds its old shard, its new shard and one old shard in
    transit; never a whole column, and there is no ``all-gather``."""
    from jax import shard_map

    n = mesh.shape[axis]
    old_total = jax.tree_util.tree_leaves(state)[0].shape[0]
    old = old_total - 1                       # the old sentinel is not kept
    assert new_capacity > old
    m_old = _shard_rows(old_total, mesh, axis)
    m_new = _shard_rows(new_capacity + 1, mesh, axis)

    def overlap(s: int, j: int) -> bool:      # new shard s, old shard j
        return (min((s + 1) * m_new, (j + 1) * m_old, old)
                > max(s * m_new, j * m_old))

    # old shard j lies on new shards <= j (m_new >= m_old): distance j - s
    hops = {d: pairs for d in range(n)
            if (pairs := [(s + d, s) for s in range(n - d)
                          if overlap(s, s + d)])}

    def local(cur):
        s = jax.lax.axis_index(axis)

        def take(new, blk, d):
            """``blk`` is old shard ``s + d``: write the rows of it that
            fall into this new shard (and are not the old sentinel)."""
            src = s + d
            off = src * m_old - s * m_new     # blk's first row, in new
            at = jnp.clip(off, 0, m_new - m_old)
            j = jnp.arange(m_old, dtype=jnp.int32) + (at - off)
            ok = ((j >= 0) & (j < m_old) & (src * m_old + j < old)
                  & (src < n))
            ok = ok.reshape((-1,) + (1,) * (new.ndim - 1))
            seg = jax.lax.dynamic_slice_in_dim(new, at, m_old)
            seg = jnp.where(ok, jnp.roll(blk, off - at, axis=0), seg)
            return jax.lax.dynamic_update_slice_in_dim(new, seg, at, 0)

        def column(new, old_col):
            for d, pairs in hops.items():
                blk = (old_col if d == 0 else
                       jax.lax.ppermute(old_col, axis, pairs))
                new = take(new, blk, d)
            return new

        return jax.tree_util.tree_map(column, make(m_new - 1), cur)

    specs = _row_specs(state, axis)
    return jax.jit(shard_map(local, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, check_vma=False))(state)


# ---------------------------------------------------------------------------
# Paged arena (ISSUE 17): pool init/growth + the logical<->physical
# indirection helpers every kernel routes its emb access through. All
# helpers are the identity when ``row_map`` is None, so dense arenas trace
# exactly the same programs as before.
# ---------------------------------------------------------------------------


def init_arena_paged(capacity: int, dim: int, pool_slots: int,
                     dtype=jnp.float32) -> Tuple[ArenaState, PageTable]:
    """Paged arena: logical columns at ``[cap+1]``, emb pool at
    ``[pool_slots + 1, d]`` (last slot = all-zero pool sentinel). The free
    stack starts full, ordered so slot 0 pops first (host mirror parity)."""
    n = capacity + 1
    pool_n = pool_slots + 1
    base = init_arena(capacity, dim, dtype)
    state = base.replace(
        emb=jnp.zeros((pool_n, dim), dtype=dtype),
        row_map=jnp.full((n,), pool_n - 1, jnp.int32),
        inv_map=jnp.full((pool_n,), -1, jnp.int32).at[pool_n - 1]
                   .set(capacity),
    )
    ptable = PageTable(
        free_slots=jnp.concatenate([
            jnp.arange(pool_n - 2, -1, -1, dtype=jnp.int32),
            jnp.zeros((1,), jnp.int32)]),
        free_top=jnp.int32(pool_n - 1),
    )
    return state, ptable


def grow_arena_paged(state: ArenaState, new_capacity: int) -> ArenaState:
    """Logical growth WITHOUT touching the embedding pool: metadata columns
    realloc+copy (a few MB), ``row_map`` extends with pool-sentinel fill,
    and the ``[pool_n, d]`` emb buffer — the term that dominates arena
    bytes — is carried over by reference. This is the copy-free growth
    claim: O(metadata), never O(N·d). The pool grows independently (and by
    page multiples) via ``grow_pool`` when free slots run out."""
    old = state.capacity
    assert new_capacity > old
    assert state.row_map is not None
    pool_sent = state.emb.shape[0] - 1
    fresh = init_arena(new_capacity, state.dim, state.emb.dtype)

    def copy(new, cur):
        return new.at[:old].set(cur[:old])

    n = new_capacity + 1
    return state.replace(
        salience=copy(fresh.salience, state.salience),
        timestamp=copy(fresh.timestamp, state.timestamp),
        last_accessed=copy(fresh.last_accessed, state.last_accessed),
        access_count=copy(fresh.access_count, state.access_count),
        type_id=copy(fresh.type_id, state.type_id),
        shard_id=copy(fresh.shard_id, state.shard_id),
        tenant_id=copy(fresh.tenant_id, state.tenant_id),
        alive=copy(fresh.alive, state.alive),
        is_super=copy(fresh.is_super, state.is_super),
        row_map=jnp.full((n,), pool_sent, jnp.int32)
                   .at[:old].set(state.row_map[:old]),
        inv_map=jnp.where(state.inv_map == old, new_capacity,
                          state.inv_map),
    )


def grow_pool(state: ArenaState, ptable: PageTable, new_pool_slots: int
              ) -> Tuple[ArenaState, PageTable]:
    """Grow the physical embedding pool by whole pages (host-side, rare).
    Copies the OLD pool rows only (pool ≈ live set, not logical capacity),
    rebinds the sentinel slot to the new last index, converts the old
    sentinel slot into an ordinary free slot (it is all-zero and unbound),
    and pushes the freed slots in ONE fixed order (old sentinel first,
    then the new slots ascending) — the host mirror replays the same
    order, so device and mirror stay pop-for-pop identical."""
    assert state.row_map is not None
    old_pool_n = state.emb.shape[0]
    new_pool_n = new_pool_slots + 1
    assert new_pool_n > old_pool_n
    old_sent = old_pool_n - 1
    new_sent = new_pool_n - 1
    cap = state.capacity
    emb = jnp.zeros((new_pool_n, state.dim), state.emb.dtype)
    emb = emb.at[:old_pool_n].set(state.emb)
    row_map = jnp.where(state.row_map == old_sent, new_sent, state.row_map)
    inv_map = jnp.full((new_pool_n,), -1, jnp.int32)
    inv_map = inv_map.at[:old_pool_n].set(state.inv_map)
    inv_map = inv_map.at[old_sent].set(-1).at[new_sent].set(cap)
    # new free slots, deepest-first push order: old sentinel, then the
    # new slots ascending (so the highest new slot pops first)
    added = np.concatenate([
        np.asarray([old_sent], np.int32),
        np.arange(old_pool_n, new_sent, dtype=np.int32)])
    top = int(ptable.free_top)
    free = np.full((new_pool_n,), 0, np.int32)
    free[:top] = np.asarray(ptable.free_slots)[:top]
    free[top:top + len(added)] = added
    return (state.replace(emb=emb, row_map=row_map, inv_map=inv_map),
            PageTable(free_slots=jnp.asarray(free),
                      free_top=jnp.int32(top + len(added))))


def _nrows(state: ArenaState) -> int:
    """Logical row count ``cap + 1`` (emb.shape[0] is pool-shaped when
    paged — every full-corpus scan sizes by a logical column instead)."""
    return state.salience.shape[0]


def _phys(state: ArenaState, rows: jax.Array) -> jax.Array:
    """Logical row indices -> physical emb rows (identity when dense).
    Unbound logical rows — including the logical sentinel — land on the
    all-zero pool sentinel slot, so stray gathers read zeros and stray
    scatters are absorbed exactly like the dense scratch row."""
    if state.row_map is None:
        return rows
    return state.row_map[rows]


def _pool_mask(state: ArenaState, mask: jax.Array) -> jax.Array:
    """Re-index a logical ``[cap+1]`` bool mask into pool space
    ``[pool_n]`` for whole-corpus scans over the paged emb. Free pool
    slots (inv_map == -1) are masked off."""
    if state.row_map is None:
        return mask
    inv = state.inv_map
    return mask[jnp.maximum(inv, 0)] & (inv >= 0)


def _pool_col(state: ArenaState, col: jax.Array) -> jax.Array:
    """Re-index a logical per-row column (e.g. shard_id) into pool space
    so row-wise compares line up with a pool-space scan. Free slots read
    row 0's value — callers must pair this with a ``_pool_mask``-derived
    validity mask."""
    if state.row_map is None:
        return col
    return col[jnp.maximum(state.inv_map, 0)]


def _pool_to_logical(state: ArenaState, rows: jax.Array) -> jax.Array:
    """Pool-space top-k survivor indices -> logical rows (identity when
    dense). Free slots map to the logical sentinel ``capacity``."""
    if state.row_map is None:
        return rows
    inv = state.inv_map[rows]
    return jnp.where(inv >= 0, inv, jnp.int32(state.capacity))


def _page_alloc(state: ArenaState, ptable: PageTable, rows: jax.Array,
                live: jax.Array
                ) -> Tuple[ArenaState, PageTable, jax.Array, jax.Array]:
    """Bind pool slots to logical ``rows`` inside a fused dispatch:
    prefix-sum pop from the free stack (the PR 3 edge-slot compactor
    idiom). Rows already bound, sentinel-padded rows, and ``~live`` rows
    allocate nothing. Returns ``(state, ptable, pops, overflow)`` — the
    pop count and an exhaustion flag ride the packed readback tail; the
    host pre-grows the pool so overflow is a can't-happen guard, not a
    recovery path (an exhausted pop leaves the row unbound, its scatters
    absorbed by the pool sentinel)."""
    cap = state.capacity
    pool_sent = state.emb.shape[0] - 1
    # suppress duplicate rows within the batch: only the FIRST occurrence
    # pops (same tri-mask as _page_free — keeps the host mirror's replay
    # pop-for-pop when one batch names a row twice)
    eq = rows[:, None] == rows[None, :]
    first = ~jnp.any(eq & (jnp.arange(rows.shape[0])[:, None]
                           > jnp.arange(rows.shape[0])[None, :]), axis=1)
    need = (first & live & (rows < cap)
            & (state.row_map[rows] == pool_sent))
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    idx = ptable.free_top - 1 - rank
    ok = need & (idx >= 0)
    slots = jnp.where(ok, ptable.free_slots[jnp.maximum(idx, 0)],
                      pool_sent)
    rows_b = jnp.where(ok, rows, cap)
    row_map = state.row_map.at[rows_b].set(slots.astype(jnp.int32))
    inv_map = state.inv_map.at[slots].set(rows_b.astype(jnp.int32))
    # re-pin the sentinel bindings every masked scatter routed through them
    row_map = row_map.at[cap].set(pool_sent)
    inv_map = inv_map.at[pool_sent].set(cap)
    pops = ok.sum().astype(jnp.int32)
    overflow = (need & ~ok).any()
    return (state.replace(row_map=row_map, inv_map=inv_map),
            ptable.replace(free_top=ptable.free_top - pops),
            pops, overflow)


def _page_free(state: ArenaState, ptable: PageTable, rows: jax.Array
               ) -> Tuple[ArenaState, PageTable, jax.Array]:
    """Unbind logical ``rows`` from their pool slots and push the slots
    back on the free stack (delete + tier-demote reclamation). Freed
    slots' emb rows are ZEROED — bit-parity with the dense
    commit-then-zero demote, and re-allocation hands out clean rows.
    Unbound/sentinel rows and intra-batch duplicates push nothing (their
    scatters land on the stack scratch entry)."""
    cap = state.capacity
    pool_sent = state.emb.shape[0] - 1
    slots = state.row_map[rows]
    # suppress duplicate rows within the batch: only the FIRST occurrence
    # pushes (a tri-mask over pairwise equality, B is a padded bucket)
    eq = rows[:, None] == rows[None, :]
    first = ~jnp.any(eq & (jnp.arange(rows.shape[0])[:, None]
                           > jnp.arange(rows.shape[0])[None, :]), axis=1)
    do = first & (rows < cap) & (slots < pool_sent)
    rank = jnp.cumsum(do.astype(jnp.int32)) - 1
    stack_cap = ptable.free_slots.shape[0] - 1
    pos = jnp.where(do, jnp.minimum(ptable.free_top + rank, stack_cap),
                    stack_cap)
    slots_b = jnp.where(do, slots, pool_sent)
    rows_b = jnp.where(do, rows, cap)
    free_slots = ptable.free_slots.at[pos].set(
        jnp.where(do, slots, ptable.free_slots[stack_cap]).astype(jnp.int32))
    row_map = state.row_map.at[rows_b].set(pool_sent)
    inv_map = state.inv_map.at[slots_b].set(-1)
    row_map = row_map.at[cap].set(pool_sent)
    inv_map = inv_map.at[pool_sent].set(cap)
    emb = state.emb.at[slots_b].set(0)
    pushes = do.sum().astype(jnp.int32)
    return (state.replace(emb=emb, row_map=row_map, inv_map=inv_map),
            ptable.replace(free_slots=free_slots,
                           free_top=ptable.free_top + pushes),
            pushes)


# ---------------------------------------------------------------------------
# Jitted mutation kernels. Index vectors are sentinel-padded on the host
# (see pad_rows) so shapes bucket to powers of two. Each kernel is one impl
# jitted twice: the donated default (zero-copy in-place scatter) and a
# ``*_copy`` twin for callers that cannot prove sole ownership of the state
# (see the module docstring's donation invariants).
# ---------------------------------------------------------------------------


def _donated_pair(impl, donate=(0,), **jit_kwargs):
    """(donated, copying) jit pair over one mutation impl."""
    return (jax.jit(impl, donate_argnums=donate, **jit_kwargs),
            jax.jit(impl, **jit_kwargs))


def pad_rows(rows: np.ndarray, sentinel: int, min_bucket: int = 8) -> np.ndarray:
    """Pad an int row-index vector to a size bucket with the sentinel row
    index, bounding the number of distinct jit specializations: powers of
    two up to 4096, then multiples of 1024 — a 5,000-row conversation
    batch pays a 5,120-row scan, not an 8,192-row one (pow2 padding wasted
    ~1.6× of every whole-arena link/dedup matmul at that size, and the
    kernels-per-bucket count stays small either way)."""
    n = len(rows)
    if n > 4096:
        bucket = -(-n // 1024) * 1024
    else:
        bucket = max(min_bucket, 1 << (max(1, n - 1)).bit_length())
    out = np.full((bucket,), sentinel, np.int32)
    out[:n] = rows
    return out


@jax.jit
def normalize(x: jax.Array) -> jax.Array:
    n = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) / jnp.maximum(n, 1e-9)).astype(x.dtype)


from lazzaro_tpu.ops.chunking import nt_dot  # noqa: E402  (re-export: scans
#                                              score through this helper)


def _arena_add(
    state: ArenaState,
    rows: jax.Array,        # [B] i32, sentinel-padded
    emb: jax.Array,         # [B, d] (normalized by caller or here)
    salience: jax.Array,    # [B] f32
    timestamp: jax.Array,   # [B] f32
    type_id: jax.Array,     # [B] i32
    shard_id: jax.Array,    # [B] i32
    tenant_id: jax.Array,   # [B] i32
    is_super: jax.Array,    # [B] bool
    sentinel=None,          # scratch row; None = ``state.capacity``
) -> ArenaState:
    """``sentinel`` is the scratch row that absorbs padded and duplicate
    scatters. It must never read as a live node: a live, tenant-tagged
    sentinel surfaces in that tenant's top-k as an id-less hit and costs
    the request one result. A shard-local caller passes its localized
    index (out of bounds, hence dropped, on every shard but the last) — a
    shard's own last row is an ordinary row."""
    sentinel = state.capacity if sentinel is None else sentinel
    emb = normalize(emb).astype(state.emb.dtype)
    new_emb = state.emb.at[_phys(state, rows)].set(emb)
    if state.row_map is not None:
        # the pool sentinel absorbs padded/dup scatters but must STAY
        # all-zero: every unbound logical row aliases it, and tiered
        # rescore reads those zeros for bit-parity with the dense
        # demote-zeroed rows
        new_emb = new_emb.at[state.emb.shape[0] - 1].set(0)
    return state.replace(
        emb=new_emb,
        salience=state.salience.at[rows].set(salience),
        timestamp=state.timestamp.at[rows].set(timestamp),
        last_accessed=state.last_accessed.at[rows].set(timestamp),
        access_count=state.access_count.at[rows].set(0),
        type_id=state.type_id.at[rows].set(type_id),
        shard_id=state.shard_id.at[rows].set(shard_id),
        tenant_id=state.tenant_id.at[rows].set(tenant_id),
        alive=state.alive.at[rows].set(True).at[sentinel].set(False),
        is_super=state.is_super.at[rows].set(is_super),
    )


arena_add, arena_add_copy = _donated_pair(_arena_add)


def _arena_delete(state: ArenaState, rows: jax.Array) -> ArenaState:
    return state.replace(
        alive=state.alive.at[rows].set(False),
        tenant_id=state.tenant_id.at[rows].set(-1),
    )


arena_delete, arena_delete_copy = _donated_pair(_arena_delete)


def _arena_update_access(
    state: ArenaState,
    rows: jax.Array,
    now: jax.Array,
    boost: jax.Array,
    cap_salience: float = 1.0,
) -> ArenaState:
    """access_count += 1, salience += boost (capped), refresh last_accessed.

    Mirrors ``buffer_graph.py:79-86`` (update_access) and the neighbor boost in
    ``memory_system.py:242-260`` — one scatter instead of per-node Python."""
    sal = state.salience.at[rows].add(boost)
    sal = jnp.minimum(sal, cap_salience)
    return state.replace(
        access_count=state.access_count.at[rows].add(1),
        salience=sal,
        last_accessed=state.last_accessed.at[rows].set(now),
    )


arena_update_access, arena_update_access_copy = _donated_pair(
    _arena_update_access, static_argnames=("cap_salience",))


def _arena_boost(state: ArenaState, rows: jax.Array, now: jax.Array,
                 boost: jax.Array) -> ArenaState:
    """Associative neighbor boost: salience += boost (cap 1.0) and freshness
    inheritance (last_accessed = now) WITHOUT an access_count bump — exact
    parity with ``_boost_neighbors`` (memory_system.py:242-260)."""
    sal = jnp.minimum(state.salience.at[rows].add(boost), 1.0)
    return state.replace(
        salience=sal,
        last_accessed=state.last_accessed.at[rows].set(now),
    )


arena_boost, arena_boost_copy = _donated_pair(_arena_boost)


def _arena_merge_touch(state: ArenaState, rows: jax.Array,
                       candidate_salience: jax.Array, now: jax.Array) -> ArenaState:
    """Dedup-merge bookkeeping: salience = max(salience, candidate),
    access_count += 1, last_accessed = now (memory_system.py:732-741)."""
    sal = state.salience.at[rows].max(candidate_salience)
    return state.replace(
        salience=sal,
        access_count=state.access_count.at[rows].add(1),
        last_accessed=state.last_accessed.at[rows].set(now),
    )


arena_merge_touch, arena_merge_touch_copy = _donated_pair(_arena_merge_touch)


def _arena_set_salience(state: ArenaState, rows: jax.Array, values: jax.Array) -> ArenaState:
    return state.replace(salience=state.salience.at[rows].set(values))


arena_set_salience, arena_set_salience_copy = _donated_pair(_arena_set_salience)


def _arena_set_parentage(state: ArenaState, rows: jax.Array, is_super: jax.Array) -> ArenaState:
    return state.replace(is_super=state.is_super.at[rows].set(is_super))


arena_set_parentage, arena_set_parentage_copy = _donated_pair(_arena_set_parentage)


def _arena_restore_access(state: ArenaState, rows: jax.Array,
                          access_count: jax.Array,
                          last_accessed: jax.Array) -> ArenaState:
    """Reload path: ``arena_add`` zeroes access history for fresh inserts;
    restored rows get their persisted counters back so importance-ranked
    eviction keeps favoring heavily-used memories across restarts."""
    return state.replace(
        access_count=state.access_count.at[rows].set(access_count),
        last_accessed=state.last_accessed.at[rows].set(last_accessed),
    )


arena_restore_access, arena_restore_access_copy = _donated_pair(_arena_restore_access)


def _arena_decay(state: ArenaState, tenant: jax.Array, rate: jax.Array,
                 floor: jax.Array) -> ArenaState:
    """Asymptotic salience decay toward ``floor``:  s' = floor + (s-floor)(1-rate).

    Tenant-masked and vectorized over the whole arena (reference loops per
    node of the current user's graph, ``memory_shard.py:64-77``)."""
    s = state.salience
    decayed = floor + (s - floor) * (1.0 - rate)
    mask = state.alive & (state.tenant_id == tenant)
    return state.replace(salience=jnp.where(mask, decayed, s))


arena_decay, arena_decay_copy = _donated_pair(_arena_decay)


# ---------------------------------------------------------------------------
# Retrieval / scoring kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("super_filter",))
def arena_mask(state: ArenaState, tenant: jax.Array,
               super_filter: int = 0) -> jax.Array:
    """The retrieval row mask: alive ∧ tenant ∧ super-node filter. Shared by
    ``arena_search`` (single-chip) and the shard_map mesh searcher
    (core/index.py) so tenant-isolation semantics live in one place."""
    mask = state.alive & (state.tenant_id == tenant)
    if super_filter == 1:
        mask = mask & state.is_super
    elif super_filter == -1:
        mask = mask & ~state.is_super
    return mask


@functools.partial(jax.jit, static_argnames=("k", "super_filter", "impl"))
def arena_search(
    state: ArenaState,
    query: jax.Array,      # [d] or [Q, d]
    tenant: jax.Array,     # scalar i32
    k: int,
    super_filter: int = 0,  # 0: any, 1: only super nodes, -1: exclude super
    impl: str = "auto",     # "auto" | "xla" | "pallas"
    cold: Optional[jax.Array] = None,  # [cap+1] bool residency column
) -> Tuple[jax.Array, jax.Array]:
    """Masked cosine top-k over the whole arena. Replaces
    ``LanceDBStore.search_nodes`` (vector_store.py:132-140) AND the super-node
    fast-path scan (memory_system.py:464-470) — same kernel, different mask.

    Dispatch (all static at trace time): big block-aligned arenas on TPU
    take the blocked select-while-scanning kernel as its one-mask case
    (``ops/pallas_topk.masked_topk``, the fused serving core's kernel) —
    it streams the matrix through VMEM with a running top-k, so no [Q, N]
    f32 score tensor ever lands in HBM (4 GB per 1k queries at 1M rows).
    Everything else takes the one-matmul XLA path. Callers with a
    row-sharded arena must pass ``impl="xla"``
    (pallas_call has no GSPMD partitioning rule) or go through the
    shard_map composition in ``ops/topk.make_sharded_topk``."""
    q = normalize(jnp.atleast_2d(query)).astype(state.emb.dtype)
    lmask = arena_mask(state, tenant, super_filter)
    # Tier residency (ISSUE 18 parity fix): a DENSE-layout demote zero-fills
    # the master row but leaves it alive, so without this mask a cold row
    # would surface as a score-0.0 top-k tail — while the PAGED layout frees
    # the slot and `_pool_mask` drops it. Masking cold rows to -inf here
    # makes the two layouts bit-identical (no-op under paging).
    if cold is not None:
        lmask = lmask & ~cold
    # paged arenas scan the emb POOL: the logical mask re-indexes into pool
    # space (free slots masked off) and survivors map back to logical rows
    mask = _pool_mask(state, lmask)
    from lazzaro_tpu.ops.pallas_topk import block_tiles, masked_topk
    n, d = state.emb.shape
    use_pallas = impl == "pallas" or (
        impl == "auto" and on_tpu() and n >= PALLAS_TOPK_MIN_ROWS
        and block_tiles(n, d, state.emb.dtype.itemsize))
    if use_pallas:
        top_scores, top_rows = masked_topk(state.emb, mask, q, k)
    else:
        def chunk(q_c):
            scores = nt_dot(q_c, state.emb)                       # [C, pool]
            return jax.lax.top_k(jnp.where(mask[None, :], scores, NEG_INF), k)

        # Big query fleets stream through [512, cap+1] tiles inside ONE
        # dispatch (HBM-bounded; one host round trip for the whole batch).
        top_scores, top_rows = chunked_map(chunk, q)
    top_rows = _pool_to_logical(state, top_rows)
    if query.ndim == 1:
        return top_scores[0], top_rows[0]
    return top_scores, top_rows


def _arena_link_candidates_multi(
    state: ArenaState,
    new_rows: jax.Array,   # [B] i32 rows to find candidates FOR (whole batch)
    excl_rows: jax.Array,  # [E] i32 rows excluded as candidates (ALL new rows)
    tenant: jax.Array,
    k: int,
    shard_modes: Tuple[int, ...] = (1, 0),
    # 0: any shard, 1: same shard only, -1: other shards only
) -> Tuple[jax.Array, ...]:
    """For each new node, top-k most similar existing nodes (excluding self
    and other new rows), for SEVERAL shard modes in one pass. One scan
    replaces reference hot loops #2/#3 (``memory_system.py:797-836``
    within-shard, ``:838-891`` cross-shard): the fused ingest's
    select-while-scanning core without its probe tier
    (``_ingest_scan_core``) — every mode is a mask over the same block's
    scores, so the arena streams from HBM once whatever the modes, and a
    whole-conversation link batch costs ONE host round trip. Returns
    ``(scores, rows)`` pairs flattened in ``shard_modes`` order; a slot no
    candidate fills holds ``(NEG_INF, capacity)``."""
    excl = jnp.zeros((_nrows(state),), bool).at[excl_rows].set(True)
    return _ingest_scan_core(
        state, state.emb[_phys(state, new_rows)], state.shard_id[new_rows],
        jnp.zeros_like(excl), excl, tenant, k, tuple(shard_modes),
        with_probe=False)


arena_link_candidates_multi = jax.jit(
    _arena_link_candidates_multi, static_argnames=("k", "shard_modes"))


def arena_link_candidates(
    state: ArenaState,
    new_rows: jax.Array,
    excl_rows: jax.Array,
    tenant: jax.Array,
    k: int,
    shard_mode: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Single-mode view of ``arena_link_candidates_multi``."""
    s, r = arena_link_candidates_multi(state, new_rows, excl_rows, tenant, k,
                                       (shard_mode,))
    return s, r


@jax.jit
def arena_importance(state: ArenaState, now: jax.Array,
                     w_sal: jax.Array, w_acc: jax.Array, w_rec: jax.Array) -> jax.Array:
    """importance = salience*w1 + min(1, access/10)*w2 + 1/(1+days_old)*w3.

    Parity with ``_enforce_buffer_limit`` scoring (memory_system.py:544-549):
    days_old counts from last_accessed. Computed for every row in one pass;
    dead rows get +inf so they never rank as eviction candidates."""
    days_old = jnp.maximum(now - state.last_accessed, 0.0) / 86400.0
    imp = (state.salience * w_sal
           + jnp.minimum(1.0, state.access_count.astype(jnp.float32) / 10.0) * w_acc
           + 1.0 / (1.0 + days_old) * w_rec)
    return jnp.where(state.alive, imp, jnp.inf)


@functools.partial(jax.jit, static_argnames=("k",))
def arena_evict_candidates(state: ArenaState, tenant: jax.Array, now: jax.Array,
                           w_sal: jax.Array, w_acc: jax.Array, w_rec: jax.Array,
                           k: int) -> Tuple[jax.Array, jax.Array]:
    """Rows of the k least-important alive, non-super nodes for a tenant."""
    imp = arena_importance(state, now, w_sal, w_acc, w_rec)
    mask = state.alive & (state.tenant_id == tenant) & ~state.is_super
    imp = jnp.where(mask, imp, jnp.inf)
    neg_scores, rows = jax.lax.top_k(-imp, k)
    return -neg_scores, rows


@jax.jit
def arena_mean_embedding(state: ArenaState, rows: jax.Array) -> jax.Array:
    """Mean of child embeddings → super-node centroid (memory_system.py:916-917).
    Sentinel-padded rows contribute zero weight."""
    valid = (rows < state.capacity)[:, None].astype(jnp.float32)
    embs = state.emb[_phys(state, rows)].astype(jnp.float32) * valid
    mean = embs.sum(0) / jnp.maximum(valid.sum(), 1.0)
    return normalize(mean)


# ---------------------------------------------------------------------------
# Edge kernels
# ---------------------------------------------------------------------------


def _edges_add(state: EdgeState, slots: jax.Array, src: jax.Array, tgt: jax.Array,
               weight: jax.Array, co: jax.Array, now: jax.Array,
               tenant: jax.Array, live: jax.Array) -> EdgeState:
    """``live`` is False for sentinel-padded positions so the scratch slot
    never becomes an alive phantom edge."""
    return state.replace(
        src=state.src.at[slots].set(src),
        tgt=state.tgt.at[slots].set(tgt),
        weight=state.weight.at[slots].set(jnp.clip(weight, 0.0, 1.0)),
        co=state.co.at[slots].set(co),
        last_updated=state.last_updated.at[slots].set(now),
        alive=state.alive.at[slots].set(live),
        tenant_id=state.tenant_id.at[slots].set(tenant),
    )


edges_add, edges_add_copy = _donated_pair(_edges_add)


def _edges_reinforce(state: EdgeState, slots: jax.Array, bump: jax.Array,
                     now: jax.Array) -> EdgeState:
    """Existing edge: weight += bump (capped at 1.0), co_occurrence += 1
    (parity: memory_shard.py:42-52)."""
    w = jnp.minimum(state.weight.at[slots].add(bump), 1.0)
    return state.replace(
        weight=w,
        co=state.co.at[slots].add(1),
        last_updated=state.last_updated.at[slots].set(now),
    )


edges_reinforce, edges_reinforce_copy = _donated_pair(_edges_reinforce)


def _edges_decay(state: EdgeState, tenant: jax.Array, rate: jax.Array) -> EdgeState:
    """weight *= (1 - rate) for the tenant's alive edges (memory_shard.py:64-71)."""
    mask = state.alive & (state.tenant_id == tenant)
    w = jnp.where(mask, state.weight * (1.0 - rate), state.weight)
    return state.replace(weight=w)


edges_decay, edges_decay_copy = _donated_pair(_edges_decay)


def _prune_compact(weak: jax.Array, prune_cap: int) -> Tuple[jax.Array, jax.Array]:
    """Prefix-sum compaction of a weak-edge mask into a dense [prune_cap]
    vector of slot indices (-1 padded, ascending slot order) — the PR 3
    pool-compactor idiom pointed at prune victims, so host cleanup walks
    O(pruned) slots instead of re-scanning every live edge. Returns
    ``(ok, slots)`` where ``ok`` is the mask of edges actually compacted
    (== ``weak`` whenever ``prune_cap`` covers the weak count; the host
    sizes it off the live-edge count so the cap can never bind — edges
    past it stay alive and are caught by the overflow counter rather
    than silently leaking from the host mirror)."""
    weak = jax.lax.optimization_barrier(weak)
    pos = jnp.cumsum(weak.astype(jnp.int32)) - 1
    ok = weak & (pos < prune_cap)
    slot_ids = jnp.arange(weak.shape[0], dtype=jnp.int32)
    buf = jnp.full((prune_cap + 1,), -1, jnp.int32)
    buf = buf.at[jnp.where(ok, jnp.minimum(pos, prune_cap - 1),
                           prune_cap)].set(slot_ids)
    return ok, buf[:prune_cap]


def _edges_prune(state: EdgeState, tenant: jax.Array, threshold: jax.Array,
                 prune_cap: int) -> Tuple[EdgeState, jax.Array]:
    """Kill the tenant's edges with weight < threshold; returns
    ``(state, pruned_slots)`` where ``pruned_slots`` is the compacted
    [prune_cap] slot-index vector (-1 padded) from :func:`_prune_compact`."""
    weak = state.alive & (state.tenant_id == tenant) & (state.weight < threshold)
    ok, slots = _prune_compact(weak, prune_cap)
    return state.replace(alive=state.alive & ~ok), slots


edges_prune, edges_prune_copy = _donated_pair(
    _edges_prune, static_argnames=("prune_cap",))


def _decay_fused(arena: ArenaState, edges: EdgeState, tenant: jax.Array,
                 rate: jax.Array, floor: jax.Array
                 ) -> Tuple[ArenaState, EdgeState]:
    """Classic per-tenant decay, arena + edges folded into ONE dispatch
    (ISSUE 19 satellite): the old ``MemoryIndex.decay`` paid two device
    round trips per tenant per tick — same arithmetic, half the dispatches.
    Bitwise identical to ``_arena_decay`` ∘ ``_edges_decay``."""
    return (_arena_decay(arena, tenant, rate, floor),
            _edges_decay(edges, tenant, rate))


decay_fused, decay_fused_copy = _donated_pair(_decay_fused, donate=(0, 1))


def _edges_delete_for_nodes(state: EdgeState, node_rows: jax.Array) -> EdgeState:
    """Remove all edges touching any of ``node_rows`` (eviction cleanup,
    memory_system.py:560-570). node_rows is a small sentinel-padded batch, so
    a broadcast membership test [E, B] is one fused VPU pass."""
    touched_src = (state.src[:, None] == node_rows[None, :]).any(axis=1)
    touched_tgt = (state.tgt[:, None] == node_rows[None, :]).any(axis=1)
    return state.replace(alive=state.alive & ~(touched_src | touched_tgt))


edges_delete_for_nodes, edges_delete_for_nodes_copy = _donated_pair(
    _edges_delete_for_nodes)


# ---------------------------------------------------------------------------
# Device-side lifecycle: decay + prune + archive as ONE all-tenant sweep
# ---------------------------------------------------------------------------

# Counter leaves riding the packed-payload tail (ISSUE 19): decayed arena
# rows, decayed edges, pruned edges, weak-edge total, prune overflow flag.
LIFECYCLE_TAIL = 5


def _bitcast_i32(x: jax.Array) -> jax.Array:
    """f32 → int32 bit-pattern view so float sections can ride a single
    INT32 packed readback; the host views them back with
    ``.view(np.float32)``. The carrier is int32 because a small row id or
    counter is a denormal bit pattern as a float, and a TPU flushes
    denormals to zero when it moves floats."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


class Requests(NamedTuple):
    """What ``_unpack_requests`` reads out of one dispatch's carrier."""

    q: jax.Array            # [Q, d] f32 padded query batch
    q_valid: jax.Array      # [Q] bool (False for pad rows)
    tenant: jax.Array       # [Q] i32 per-query tenant (-1 matches no row)
    gate_on: jax.Array      # [Q] bool hierarchy gate enabled
    boost_on: jax.Array     # [Q] bool apply device boosts for this query
    k_q: jax.Array          # [Q] i32 per-query k (0 for pad rows)
    cap_q: jax.Array        # [Q] i32 per-query retrieval cap
    nprobe_q: jax.Array     # [Q] i32 per-query probe width (coarse families)
    super_gate: jax.Array   # f32 scalars of the dispatch
    now: jax.Array
    acc_boost: jax.Array
    nbr_boost: jax.Array


def _unpack_requests(carrier: jax.Array, dim: int) -> Requests:
    """The shared prologue of every fused serving program (ISSUE 37): the
    ONE ``[Q, dim + REQUEST_COLS]`` int32 array a dispatch hands the device
    (``utils.batching.RequestCarrier``, the host half) back into its
    fields — a slice per column, a bitcast for the f32 ones (the query's
    bits and the scalars arrive exactly as the host wrote them), which XLA
    fuses into whatever reads the field first."""
    with jax.named_scope("lz.unpack"):
        if carrier.shape[1] != dim + REQUEST_COLS:
            raise ValueError(f"request carrier of {carrier.shape[1]} columns "
                             f"for dim {dim}")

        def f32(x):
            return jax.lax.bitcast_convert_type(x, jnp.float32)

        def col(name):
            return carrier[:, dim + REQUEST_FIELDS.index(name)]

        def scalar(name):
            return f32(carrier[0, dim + len(REQUEST_FIELDS)
                               + REQUEST_SCALARS.index(name)])

        return Requests(
            q=f32(carrier[:, :dim]), q_valid=col("valid") != 0,
            tenant=col("tenant"), gate_on=col("gate_on") != 0,
            boost_on=col("boost_on") != 0, k_q=col("k"), cap_q=col("cap"),
            nprobe_q=col("nprobe"), super_gate=scalar("super_gate"),
            now=scalar("now"), acc_boost=scalar("acc_boost"),
            nbr_boost=scalar("nbr_boost"))


def _lifecycle_core(arena: ArenaState, edges: EdgeState, passes: jax.Array,
                    verdict_tids: jax.Array, rate: jax.Array,
                    floor: jax.Array, threshold: jax.Array, now: jax.Array,
                    w_sal: jax.Array, w_acc: jax.Array, w_rec: jax.Array,
                    prune_cap: int, archive_k: int):
    """Shard-local body of the all-tenant maintenance sweep. Both the
    single-chip jit and the ``make_lifecycle_sharded`` shard_map trace this
    one function, so single-chip/mesh parity is structural.

    ``passes`` is a dense [Tc] per-tenant-id owed-decay-pass table (0 =
    tenant not swept this tick) — the per-row pass count is one gather by
    ``tenant_id``, honoring the ``decay_pass`` stamping discipline from
    ``MemorySystem`` without an O(cap × tenants) mask product.

    Bit-parity with the classic host loop: the steady-state single owed
    pass multiplies by ``(1 - rate)`` ONCE — the exact expression
    ``_arena_decay`` / ``_edges_decay`` evaluate — and only catch-up ticks
    (p > 1, e.g. after a deferred sweep) take the closed form
    ``(1 - rate) ** p`` that the checkpoint-load replay already uses.
    Per-tenant stages are disjoint by tenant mask, so fusing all tenants
    into one scatter is order-equivalent to the classic per-tenant loop."""
    tc = passes.shape[0]

    def owed(tid):
        inb = (tid >= 0) & (tid < tc)
        return jnp.where(inb, passes[jnp.clip(tid, 0, tc - 1)], 0)

    # (a) closed-form salience decay over every swept tenant's live rows
    p = owed(arena.tenant_id)
    d_mask = arena.alive & (p > 0)
    base = arena.salience - floor
    stepped = floor + base * (1.0 - rate)
    closed = floor + base * jnp.power(1.0 - rate, p.astype(jnp.float32))
    arena = arena.replace(salience=jnp.where(
        d_mask, jnp.where(p == 1, stepped, closed), arena.salience))

    # (b) edge-weight decay, then weak-edge prune on the DECAYED weights
    # (classic order: decay tick precedes the prune pass)
    ep = owed(edges.tenant_id)
    e_mask = edges.alive & (ep > 0)
    w = edges.weight
    w_new = jnp.where(
        e_mask,
        jnp.where(ep == 1, w * (1.0 - rate),
                  w * jnp.power(1.0 - rate, ep.astype(jnp.float32))),
        w)
    weak = e_mask & (w_new < threshold)
    ok, pruned_slots = _prune_compact(weak, prune_cap)
    edges = edges.replace(weight=w_new, alive=edges.alive & ~ok)

    # (c) importance verdicts on the decayed salience (classic order:
    # ``evict_candidates`` after the decay tick) — bottom-k per verdict
    # tenant, the archive-means-demote feed for the TierPump
    imp = jax.lax.optimization_barrier(
        arena_importance(arena, now, w_sal, w_acc, w_rec))

    def bottom_k(t):
        mask = (arena.alive & (arena.tenant_id == t) & ~arena.is_super
                & (t >= 0))
        neg_scores, rows = jax.lax.top_k(
            -jnp.where(mask, imp, jnp.inf), archive_k)
        return -neg_scores, rows

    v_imps, v_rows = jax.vmap(bottom_k)(verdict_tids)
    counters = jnp.stack([
        d_mask.sum().astype(jnp.int32),
        e_mask.sum().astype(jnp.int32),
        ok.sum().astype(jnp.int32),
        weak.sum().astype(jnp.int32),
        (weak & ~ok).any().astype(jnp.int32),
    ])
    return arena, edges, v_imps, v_rows, pruned_slots, counters


def _lifecycle_payload(v_imps, v_rows, pruned_slots, counters) -> jax.Array:
    """ONE flat int32 payload so the whole sweep comes home in ONE
    transfer: [Tv·k] verdict importances (bitcast) | [Tv·k] verdict rows |
    [prune_cap] pruned slots | [LIFECYCLE_TAIL] counters. Static offsets —
    the host slices by shape, no header."""
    return jnp.concatenate([
        _bitcast_i32(v_imps).reshape(-1),
        v_rows.astype(jnp.int32).reshape(-1),
        pruned_slots.astype(jnp.int32),
        counters.astype(jnp.int32),
    ])


def _lifecycle_sweep(arena: ArenaState, edges: EdgeState, passes: jax.Array,
                     verdict_tids: jax.Array, rate: jax.Array,
                     floor: jax.Array, threshold: jax.Array, now: jax.Array,
                     w_sal: jax.Array, w_acc: jax.Array, w_rec: jax.Array,
                     prune_cap: int, archive_k: int
                     ) -> Tuple[ArenaState, EdgeState, jax.Array]:
    """ONE donated dispatch + ONE packed readback: salience decay, edge
    decay + weak-edge prune (compacted victim slots ride the readback like
    the paged free-list leaves), and per-tenant bottom-k archive verdicts
    — over the live arena and edge pool for ALL tenants at once."""
    arena, edges, v_imps, v_rows, pruned_slots, counters = _lifecycle_core(
        arena, edges, passes, verdict_tids, rate, floor, threshold, now,
        w_sal, w_acc, w_rec, prune_cap, archive_k)
    return arena, edges, _lifecycle_payload(v_imps, v_rows, pruned_slots,
                                            counters)


lifecycle_sweep, lifecycle_sweep_copy = _donated_pair(
    _lifecycle_sweep, donate=(0, 1),
    static_argnames=("prune_cap", "archive_k"))


def _lifecycle_sweep_read(arena: ArenaState, edges: EdgeState,
                          passes: jax.Array, verdict_tids: jax.Array,
                          rate: jax.Array, floor: jax.Array,
                          threshold: jax.Array, now: jax.Array,
                          w_sal: jax.Array, w_acc: jax.Array,
                          w_rec: jax.Array, prune_cap: int, archive_k: int
                          ) -> jax.Array:
    """Read-only twin: payload only, states untouched (dry-run / gauges)."""
    return _lifecycle_sweep(arena, edges, passes, verdict_tids, rate, floor,
                            threshold, now, w_sal, w_acc, w_rec,
                            prune_cap, archive_k)[2]


lifecycle_sweep_read = jax.jit(_lifecycle_sweep_read,
                               static_argnames=("prune_cap", "archive_k"))


# ---------------------------------------------------------------------------
# Fused ingest: the whole per-conversation mutation sequence in ONE program
# ---------------------------------------------------------------------------


def _shadow_scatter(shadow, rows: jax.Array, emb_stored: jax.Array):
    """Incremental int8 serving-shadow maintenance INSIDE the fused ingest
    program: quantize exactly the rows being written (``emb_stored`` is the
    normalized arena-dtype embedding the node scatter stores) and scatter
    their codes + scales into the shadow — an O(batch) update instead of
    the host-side O(arena) lazy re-quantize the dirty flag used to force.
    ``shadow`` is ``(q8, scale)`` or None (int8 serving off / shadow not
    yet built); None passes through untouched."""
    if shadow is None:
        return None
    from lazzaro_tpu.ops.quant import quantize_rows

    q8, scale = shadow
    q_new, s_new = quantize_rows(emb_stored)
    return (q8.at[rows].set(q_new), scale.at[rows].set(s_new))


def _pq_scatter(pq, rows: jax.Array, emb_stored: jax.Array):
    """Incremental PQ code maintenance INSIDE the fused ingest program
    (ISSUE 16, the PQ twin of ``_shadow_scatter``): encode exactly the
    rows being written against the FROZEN codebook — m small
    [B, dsub]×[dsub, 256] matmuls, the same argmax ``ops.pq.encode_pq``
    runs over the whole arena — and scatter their m-byte codes in place.
    An O(batch) update instead of the offline full re-encode the old
    ``_pq_dirty`` flag forced; codebook drift is handled by the rare
    ``ivf_maintenance`` re-seed, never here. ``pq`` is ``(book_cent
    [m, 256, dsub] f32, codes [cap+1, m] u8)`` or None (PQ serving off /
    no published pack); None passes through untouched. Sentinel-padded
    rows encode into the sentinel row — harmless, every serving scan
    masks it."""
    if pq is None:
        return None
    book_cent, codes = pq
    m, _, dsub = book_cent.shape
    x = emb_stored.astype(jnp.float32).reshape(rows.shape[0], m, dsub)
    cnorm = jnp.sum(book_cent * book_cent, axis=2)              # [m, 256]
    scores = (2.0 * jnp.einsum("nmd,mkd->nmk", x, book_cent)
              - cnorm[None, :, :])                              # [B, m, 256]
    new = jnp.argmax(scores, axis=2).astype(jnp.uint8)
    return (book_cent, codes.at[rows].set(new))


def _ivf_online_assign(cent: jax.Array, qf: jax.Array, live: jax.Array
                       ) -> jax.Array:
    """Cluster assignment of the accepted batch against the CURRENT
    centroids — the marginal [B, C] matmul the online-IVF tentpole rides
    on (the same dispatch already streams the [B, rows] dedup/link score
    matrix, so C ≈ √rows extra columns are noise). Ties resolve to the
    lowest centroid id (``argmax``), matching ``ops.ivf._assign_device``.
    Dead/padded facts route to bucket C (one past the end — every scatter
    built on it drops)."""
    cs = jnp.dot(qf, cent.T, preferred_element_type=jnp.float32)  # [B, C]
    assign = jnp.argmax(cs, axis=1).astype(jnp.int32)
    return jnp.where(live, assign, cent.shape[0])


def _ivf_online_update(ivf, rows: jax.Array, qf: jax.Array,
                       live: jax.Array, eta_scale: jax.Array):
    """Online IVF maintenance INSIDE the fused ingest program (ISSUE 12):
    score the accepted facts against the centroids, append each live row
    to its cluster's member table via the same prefix-sum compaction idiom
    as the gated link insert (an accepted append whose position lands past
    the cluster capacity scatters out of bounds — dropped, never a phantom
    write — and its readback position reports -1 so the host re-inserts
    it into the exact-scan extras, exactly like link-pool overflow), then
    blend a bounded mini-batch spherical k-means step into the centroids:
    ``cent_c ← normalize((1 - η_c)·cent_c + η_c·mean(batch_c))`` with
    ``η_c = eta_scale · b_c / (count_c + b_c)`` — the classic mini-batch
    step, so a mature cluster barely moves per batch and the update term
    is O(B·C·d), not O(rows).

    ``ivf = (cent [C, d] f32 normalized, members [C, M] i32 -1-padded,
    counts [C] i32 live-prefix occupancy)``; all three are donated state.
    Returns ``(new_ivf, assign [B] (-1 = not live), pos [B] (member slot,
    -1 = overflowed/not live), (overflow, occupancy, appends, shift_ppm)
    int32 scalars for the readback tail)``."""
    cent, members, counts = ivf
    C, M = members.shape
    b = rows.shape[0]
    a = _ivf_online_assign(cent, qf, live)                 # [B], dead -> C
    assign = jnp.where(live, a, -1)
    # append position = cluster occupancy + rank among EARLIER live facts
    # of the same cluster (intra-batch prefix sum, the PR 3 compaction
    # idiom applied per cluster)
    same = (a[:, None] == a[None, :]) & live[None, :]
    rank = (same & jnp.tri(b, k=-1, dtype=bool)).sum(axis=1)
    counts_pre = counts
    pos = jnp.where(live, counts_pre[jnp.where(live, a, 0)]
                    + rank.astype(jnp.int32), -1)
    ok = live & (pos >= 0) & (pos < M)
    a_s = jnp.where(ok, a, C)                              # OOB -> dropped
    p_s = jnp.where(ok, pos, M)
    members = members.at[a_s, p_s].set(rows.astype(jnp.int32))
    counts = counts_pre.at[a_s].add(ok.astype(jnp.int32))
    # mini-batch centroid step (overflowed facts still inform the mean —
    # they are real cluster mass even though their member slot spilled)
    sums = jnp.zeros((C, qf.shape[1]), jnp.float32
                     ).at[a].add(jnp.where(live[:, None], qf, 0.0))
    bc = jnp.zeros((C,), jnp.float32).at[a].add(live.astype(jnp.float32))
    tot = counts_pre.astype(jnp.float32)
    eta = jnp.clip(eta_scale * bc / jnp.maximum(tot + bc, 1.0), 0.0, 1.0)
    mean = sums / jnp.maximum(bc[:, None], 1.0)
    prop = cent * (1.0 - eta[:, None]) + mean * eta[:, None]
    nrm = jnp.linalg.norm(prop, axis=1, keepdims=True)
    moved = (bc[:, None] > 0) & (nrm > 1e-9)
    new_cent = jnp.where(moved, prop / jnp.maximum(nrm, 1e-9), cent)
    # staleness proxy riding the readback tail: total angular drift of the
    # touched centroids this batch, in parts-per-million of cosine
    shift = jnp.where(bc > 0, 1.0 - (new_cent * cent).sum(axis=1), 0.0)
    tail = (
        (live & ~ok).any().astype(jnp.int32),              # overflow flag
        jnp.minimum(counts.sum(), jnp.int32(C * M)).astype(jnp.int32),
        ok.sum().astype(jnp.int32),                        # appends
        jnp.clip(jnp.round(shift.sum() * 1e6), 0,
                 2 ** 30).astype(jnp.int32),               # shift ppm
    )
    pos_rb = jnp.where(ok, pos, -1)
    return (new_cent, members, counts), assign, pos_rb, tail


# Number of wide + tail readback leaves _ivf_online_update appends to the
# fused ingest readback (assign, pos, overflow, occupancy, appends, shift).
IVF_INGEST_TAIL = 6


def _ivf_drop_rows(ivf_members: jax.Array, drop_map: jax.Array
                   ) -> jax.Array:
    """Scrub rows out of the member tables (tier demotion: a demoted row's
    exact master embedding is zeroed, so its member slot must not feed the
    exact in-kernel rescore — the full-corpus int8 shadow coarse path
    covers it instead). Slots become -1 holes; occupancy counts are NOT
    rewound (append positions stay monotone until the next re-seed packs
    the table). O(C·M) elementwise — runs on the background demote path,
    never a serving query."""
    safe = jnp.maximum(ivf_members, 0)
    hit = (ivf_members >= 0) & drop_map[safe]
    return jnp.where(hit, -1, ivf_members)


ivf_members_drop = jax.jit(_ivf_drop_rows, donate_argnums=(0,))
ivf_members_drop_copy = jax.jit(_ivf_drop_rows)


def _ingest_fused(
    arena: ArenaState,
    edges: EdgeState,
    shadow,                  # (q8 [cap+1, d] i8, scale [cap+1] f32) or None
    ivf,                     # (cent [C,d], members [C,M], counts [C]) or None
    pq,                      # (book_cent [m,256,dsub], codes [cap+1,m]) or None
    ptable,                  # PageTable or None (dense arena)
    rows: jax.Array,         # [B] i32 new-node rows, sentinel-padded
    emb: jax.Array,          # [B, d]
    salience: jax.Array,     # [B] f32
    timestamp: jax.Array,    # [B] f32
    type_id: jax.Array,      # [B] i32
    shard_id: jax.Array,     # [B] i32
    tenant_id: jax.Array,    # [B] i32
    is_super: jax.Array,     # [B] bool
    touch_rows: jax.Array,   # [M] i32 dedup-merge rows, sentinel-padded
    touch_sal: jax.Array,    # [M] f32 candidate saliences
    chain_slots: jax.Array,  # [C] i32 edge slots, sentinel-padded
    chain_src: jax.Array,    # [C] i32 arena rows (-1 padding)
    chain_tgt: jax.Array,    # [C] i32
    chain_w: jax.Array,      # [C] f32
    link_pool: jax.Array,    # [P+1] i32 compaction slot pool (last = sentinel)
    pool_len: jax.Array,     # scalar i32: REAL slots at the pool head
    now: jax.Array,
    tenant: jax.Array,
    link_gate: jax.Array,
    link_scale: jax.Array,
    ivf_eta: jax.Array,      # centroid learning-rate scale (inert w/o ivf)
    k: int,
    shard_modes: Tuple[int, ...] = (1, 0),
) -> Tuple[ArenaState, EdgeState, object, object, object,
           Tuple[jax.Array, ...]]:
    """The per-conversation ingest sequence — ``arena_add`` →
    ``arena_merge_touch`` → ``arena_link_candidates_multi`` → gated
    ``edges_add`` — fused into ONE donated device program.

    The host hands the kernel a POOL of edge slots covering the worst case
    (every potential (mode, new-row, candidate) link); the gate (score >
    link_gate, valid non-sentinel query row, not a duplicate of an earlier
    mode's hit) is evaluated ON DEVICE and accepted edges are prefix-sum
    compacted into the pool's leading slots — rejected candidates never
    write the edge arena, and the host reclaims the untouched pool suffix
    as one slice. Host round trips per conversation drop from ~4
    dispatches + 1 readback to 1 + 1: the returned per-mode ``(scores,
    cands, pos)`` triples (pos = pool position, -1 = rejected) are the
    single packed readback the host needs for id decode and edge
    bookkeeping. With int8 serving on, the shadow codes for the written
    rows update in the same program (``_shadow_scatter``). With online IVF
    tables threaded (``ivf``), the written rows are scored against the
    centroids, appended to their clusters' member tables, and the
    mini-batch centroid step runs — all inside this same dispatch
    (``_ivf_online_update``; the extra readback leaves trail the link
    counters). With PQ serving on, the written rows' m-byte codes are
    re-encoded against the frozen codebook in the same program
    (``_pq_scatter``) — no extra dispatches, no extra readback leaves.
    With a paged arena (``ptable`` threaded), every valid row binds a pool
    slot via the prefix-sum free-stack pop FIRST (``_page_alloc``), and
    the pop count / post-pop stack depth / overflow flag ride the SAME
    packed readback as trailing leaves (``PAGE_INGEST_TAIL``) — paging
    adds an int32 gather to the scatters and scans, never a dispatch."""
    qf = normalize(emb)
    emb_stored = qf.astype(arena.emb.dtype)
    valid_q = rows < arena.capacity        # sentinel-padded rows make no edges
    page_tail = ()
    if ptable is not None:
        arena, ptable, pops, p_over = _page_alloc(arena, ptable, rows,
                                                  valid_q)
        page_tail = (pops, ptable.free_top, p_over.astype(jnp.int32))
    arena = _arena_add(arena, rows, emb, salience, timestamp, type_id,
                       shard_id, tenant_id, is_super)
    shadow = _shadow_scatter(shadow, rows, emb_stored)
    pq = _pq_scatter(pq, rows, emb_stored)
    arena = _arena_merge_touch(arena, touch_rows, touch_sal, now)
    link_flat = _arena_link_candidates_multi(arena, rows, rows, tenant, k,
                                             shard_modes)
    n_chain = chain_slots.shape[0]
    edges = _edges_add(edges, chain_slots, chain_src, chain_tgt, chain_w,
                       jnp.ones((n_chain,), jnp.int32), now, tenant,
                       chain_src >= 0)
    edges, outs = _gated_link_insert(edges, link_flat, link_pool, pool_len,
                                     rows, valid_q, now, tenant, link_gate,
                                     link_scale, shard_modes)
    if ivf is not None:
        leaf = outs[0].shape
        ivf, a_rb, p_rb, tail = _ivf_online_update(ivf, rows, qf, valid_q,
                                                   ivf_eta)
        outs = outs + tuple(
            jnp.broadcast_to(x[:, None], leaf) for x in (a_rb, p_rb)
        ) + tuple(jnp.broadcast_to(t, leaf) for t in tail)
    if page_tail:
        leaf = outs[0].shape
        outs = outs + tuple(jnp.broadcast_to(t, leaf) for t in page_tail)
    return arena, edges, shadow, ivf, pq, ptable, outs


def _gated_link_insert(edges, link_flat, link_pool, pool_len, src_rows,
                       valid_q, now, tenant, link_gate, link_scale,
                       shard_modes):
    """Device-gated similarity-edge insert with prefix-sum slot compaction
    (ROADMAP ceiling #2), shared by the fused ingest kernels: per shard
    mode the gate verdict (gate pass, valid source row, not already
    inserted by an earlier mode) is evaluated on device, then accepted
    edges across ALL modes pack into a dense PREFIX of the host-provided
    slot pool via a cumulative sum over the gate mask. Rejected candidates
    scatter to the sentinel slot — the edge arena never sees speculative
    dead writes — and ONE ``_edges_add`` covers every mode. The readback
    triples carry each candidate's pool position (-1 = rejected) so the
    host can register accepted keys and reclaim the unused pool suffix as
    a single contiguous slice.

    ``pool_len`` (device scalar: the count of REAL slots at the pool's
    head — the tail up to the jit bucket is sentinel padding) lets the
    host size the pool by its measured link-acceptance rate instead of
    the 2·B·k worst case (``MemoryConfig.link_accept_hint``): an accepted
    edge whose prefix-sum position lands past ``pool_len`` scatters to
    the sentinel slot (never a phantom write), its readback position
    still carries the TRUE prefix position so the host can identify and
    re-insert exactly the overflowed edges, and the trailing overflow
    flag in the packed readback tells the host a retry is needed at
    all."""
    # The link-scan top-k results feed BOTH the gate logic here and the
    # packed readback; the barrier stops XLA from splitting those consumers
    # into duplicate full-arena sorts (same fix as _search_fused_scan).
    link_flat = jax.lax.optimization_barrier(link_flat)
    pool_cap = link_pool.shape[0] - 1      # last pool entry = sentinel slot
    per_mode = []
    prior = []                             # (cands, live) of earlier modes
    for mi in range(len(shard_modes)):
        scores, cand = link_flat[2 * mi], link_flat[2 * mi + 1]
        live = (scores > link_gate) & valid_q[:, None]
        for p_cand, p_live in prior:
            # an (src, cand) pair an earlier mode already inserted must not
            # become a second live edge row (mode masks overlap: every
            # same-shard candidate is also an any-shard candidate)
            dup = (cand[:, :, None] == p_cand[:, None, :]) & p_live[:, None, :]
            live = live & ~dup.any(-1)
        prior.append((cand, live))
        per_mode.append((scores, cand, live))
    live_all = jnp.concatenate([lv.reshape(-1) for _, _, lv in per_mode])
    pos_all = jnp.cumsum(live_all.astype(jnp.int32)) - 1
    ok = live_all & (pos_all < jnp.minimum(pool_len, pool_cap))
    slots = link_pool[jnp.where(ok, jnp.minimum(pos_all, pool_cap - 1),
                                pool_cap)]
    overflow = (live_all & ~ok).any()
    src_all = jnp.concatenate([
        jnp.broadcast_to(src_rows[:, None], c.shape).reshape(-1)
        for _, c, _ in per_mode])
    cand_all = jnp.concatenate([c.reshape(-1) for _, c, _ in per_mode])
    w_all = jnp.concatenate([(s * link_scale).reshape(-1)
                             for s, _, _ in per_mode])
    edges = _edges_add(edges, slots, src_all, cand_all, w_all,
                       jnp.ones((live_all.size,), jnp.int32), now, tenant,
                       ok)
    outs = []
    off = 0
    for scores, cand, live in per_mode:
        m = live.size
        pos_m = jnp.where(live.reshape(-1), pos_all[off:off + m],
                          -1).reshape(live.shape)
        outs.extend((scores, cand, pos_m))
        off += m
    # trailing counter leaves, broadcast to the common readback leaf shape
    # so the whole tuple still fetches in ONE packed transfer (ISSUE 6:
    # the overflow flag, the device-gated accepted-link count, and the
    # pool-slot occupancy ride the readback — bytes, not dispatches)
    leaf = per_mode[0][2].shape
    accepted = live_all.sum().astype(jnp.int32)
    pool_used = jnp.minimum(accepted, jnp.minimum(pool_len, pool_cap))
    outs.append(jnp.broadcast_to(overflow.astype(jnp.int32), leaf))
    outs.append(jnp.broadcast_to(accepted, leaf))
    outs.append(jnp.broadcast_to(pool_used.astype(jnp.int32), leaf))
    return edges, tuple(outs)


PAGE_INGEST_TAIL = 3  # trailing paged leaves: pops, free_top, overflow

ingest_fused, ingest_fused_copy = _donated_pair(
    _ingest_fused, donate=(0, 1, 2, 3, 4, 5),
    static_argnames=("k", "shard_modes"))


# ---------------------------------------------------------------------------
# Fused ingest WITH device-side dedup: the probe that decides merge-vs-insert
# runs against the pre-add arena INSIDE the same dispatch (ROADMAP item 2),
# so ingest is one round trip end-to-end.
#
# The scan and resolve bodies below are the SHARD-LOCAL CORES of the pod
# ingest program too (``make_ingest_fused_sharded``): the single-chip kernel
# and the distributed kernel trace the same functions, so parity is
# structural — the PR 5 recipe applied to the write path (ISSUE 9).
# ---------------------------------------------------------------------------


def _ingest_scan_core(state: ArenaState, qd: jax.Array, q_shard: jax.Array,
                      probe_excl: jax.Array, link_excl: jax.Array,
                      tenant: jax.Array, k: int,
                      shard_modes: Tuple[int, ...],
                      with_probe: bool = True):
    """The whole-arena ingest scan: dedup-probe top-1 plus the per-mode
    link top-k, SELECTED WHILE THE POOL STREAMS from HBM once (ISSUE 45;
    ``ops/pallas_topk.blocked_link_scan``, the write path's twin of
    ``_exact_two_tier``'s core): the probe and every link mode are masks
    over one block's scores, and the probe's running top-1 and each mode's
    running top-k stay on chip across the blocks, so no ``[facts, rows]``
    score tile exists. The pre-add scan is equivalent to the probe followed
    by a post-add link scan — the batch's own rows are excluded as
    candidates either way, and no other row's embedding changes between the
    two points.

    ``qd`` is each fact's normalized arena-dtype embedding (exactly the
    bytes the node scatter stores, so scores match a post-add gather of
    the live rows bit for bit). ``probe_excl`` masks the sentinel scratch
    row out of the probe — the classic host probe drops the id-less
    sentinel at decode; in-kernel the mask does (a previous batch's
    padding can leave the sentinel alive, and a dedup hit on it would
    silently eat a fact). ``link_excl`` additionally masks the batch's
    own rows out of the link candidates. Shard-local by construction:
    single-chip callers pass the whole arena, the sharded program passes
    each chip's local slice with localized exclusion masks; a paged pool
    scans in pool space and maps the survivors back. Returns the flat tuple
    ``(p_s [B,1], p_r [B,1], s_mode, r_mode, ...)``; a slot no candidate
    fills holds ``(NEG_INF, capacity)``. ``with_probe=False`` (the non-dedup
    sharded program) skips the probe group — the link modes alone, post-add
    semantics — and then ``probe_excl`` only shapes the link mask."""
    from lazzaro_tpu.ops.pallas_topk import ROW_DEAD, blocked_link_scan

    pmask = _pool_mask(state, state.alive & (state.tenant_id == tenant)
                       & ~state.is_super & ~probe_excl)
    lmask = pmask & ~_pool_mask(state, link_excl)
    shard_pool = _pool_col(state, state.shard_id).astype(jnp.int32)
    flat = blocked_link_scan(
        state.emb, qd, jnp.where(lmask, shard_pool, ROW_DEAD), q_shard, k,
        shard_modes,
        jnp.where(pmask, 0, ROW_DEAD) if with_probe else None)
    return tuple(_pool_to_logical(state, a) if i % 2 else a
                 for i, a in enumerate(flat))


def _dedup_resolve(qf: jax.Array, rows: jax.Array, valid: jax.Array,
                   chain_gid: jax.Array, p_s: jax.Array, p_r: jax.Array,
                   dedup_gate: jax.Array, cap: int):
    """Sequential duplicate resolution shared by the single-chip and the
    sharded fused ingest (replicated compute on the pod — the inputs are
    the replicated batch plus the MERGED probe top-1): intra-batch gram
    picks the best match among EARLIER valid facts (sentinel padding rows
    share one unit vector and must never match anything), the scan blends
    it with the pre-add probe, chains targets (a dup-of-a-dup merges into
    the surviving node), and tracks the chain predecessor (last LIVE fact
    of the same shard group — a dup in the middle bridges its neighbors,
    exactly like the host path that skips it). Returns ``(target [B] i32,
    dup [B] bool, chain_src [B] i32)``."""
    b = rows.shape[0]
    gram = nt_dot(qf, qf)
    tril = jnp.where(jnp.tri(b, k=-1, dtype=bool) & valid[None, :],
                     gram, NEG_INF)
    g_j = jnp.argmax(tril, axis=1)
    g_s = tril[jnp.arange(b), g_j]

    def step(carry, i):
        target, dup, last = carry
        use_g = g_s[i] > p_s[i]
        best_s = jnp.where(use_g, g_s[i], p_s[i])
        best_t = jnp.where(use_g, target[g_j[i]], p_r[i])
        is_dup = valid[i] & (best_s > dedup_gate)
        target = target.at[i].set(jnp.where(is_dup, best_t, rows[i]))
        dup = dup.at[i].set(is_dup)
        live_i = valid[i] & ~is_dup
        gid = jnp.maximum(chain_gid[i], 0)
        prev = jnp.where(chain_gid[i] >= 0, last[gid], -1)
        src_i = jnp.where(live_i & (prev >= 0), prev, -1)
        last = last.at[gid].set(jnp.where(live_i, rows[i], last[gid]))
        return (target, dup, last), src_i

    init = (jnp.full((b,), cap, jnp.int32), jnp.zeros((b,), bool),
            jnp.full((b,), -1, jnp.int32))
    (target, dup, _), chain_src = jax.lax.scan(step, init, jnp.arange(b))
    return target, dup, chain_src


def _ingest_dedup_fused(
    arena: ArenaState,
    edges: EdgeState,
    shadow,                  # (q8 [cap+1, d] i8, scale [cap+1] f32) or None
    ivf,                     # (cent [C,d], members [C,M], counts [C]) or None
    pq,                      # (book_cent [m,256,dsub], codes [cap+1,m]) or None
    ptable,                  # PageTable or None (dense arena)
    rows: jax.Array,         # [B] i32 candidate row per fact, sentinel-padded
    emb: jax.Array,          # [B, d]
    salience: jax.Array,     # [B] f32 (doubles as the merge-touch candidate)
    timestamp: jax.Array,    # [B] f32
    type_id: jax.Array,      # [B] i32
    shard_id: jax.Array,     # [B] i32
    tenant_id: jax.Array,    # [B] i32
    is_super: jax.Array,     # [B] bool
    chain_gid: jax.Array,    # [B] i32 densified shard-group id, -1 padding
    chain_slots: jax.Array,  # [B] i32 edge slot per fact, sentinel-padded
    link_pool: jax.Array,    # [P+1] i32 compaction slot pool (last = sentinel)
    pool_len: jax.Array,     # scalar i32: REAL slots at the pool head
    now: jax.Array,
    tenant: jax.Array,
    dedup_gate: jax.Array,   # cosine threshold; > 1.0 disables dedup
    chain_w: jax.Array,
    link_gate: jax.Array,
    link_scale: jax.Array,
    ivf_eta: jax.Array,      # centroid learning-rate scale (inert w/o ivf)
    k: int,
    shard_modes: Tuple[int, ...] = (1, 0),
) -> Tuple[ArenaState, EdgeState, object, object, object,
           Tuple[jax.Array, ...]]:
    """``_ingest_fused`` plus the dedup probe the classic pipeline pays a
    separate dispatch+readback for: masked top-1 against the PRE-add arena
    and an intra-batch gram resolve duplicate facts ON DEVICE, duplicate
    rows are scattered to the sentinel (never become alive nodes), their
    merge targets get the merge-touch, and chain edges link consecutive
    LIVE facts per shard group (a dup in the middle bridges its
    neighbors, exactly like the host path that skips it). The packed
    readback adds ``(dup, target, chain_src)`` so the host can finish id
    bookkeeping — still ONE dispatch + ONE readback per mega-batch."""
    cap = arena.capacity
    b = rows.shape[0]
    valid = rows < cap
    qf = normalize(emb)                    # f32 — intra gram parity w/ host
    qd = qf.astype(arena.emb.dtype)        # arena dtype — probe parity

    # Paged arena: bind a pool slot to EVERY valid row up front — dup
    # verdicts aren't known until the resolve, and allocating for the
    # whole batch keeps the free-stack op replayable on the host mirror
    # at dispatch time (LIFO order parity under concurrent demote pushes).
    # Dup rows keep their slots bound-but-dead: their logical rows are
    # never alive, the host reuses them first (its row free-list is LIFO
    # too), so over-residency is bounded by one batch.
    page_tail = ()
    if ptable is not None:
        arena, ptable, pops, p_over = _page_alloc(arena, ptable, rows,
                                                  valid)
        page_tail = (pops, ptable.free_top, p_over.astype(jnp.int32))

    # ONE pass over the arena feeds BOTH the pre-add dedup probe and the
    # per-mode link scans (_ingest_scan_core): the probe sees the same
    # visibility the classic host probe has (its batch insert also lands
    # after the probe), and the link candidates exclude the batch's own
    # rows — so the pre-add scan is exactly the post-add-with-exclusion
    # scan the unfused path runs, at HALF the HBM traffic.
    probe_excl = jnp.arange(cap + 1) == cap
    link_excl = (jnp.zeros((cap + 1,), bool).at[rows].set(True)
                 | probe_excl)
    # named scopes: compile-time metadata for the device trace, as in
    # ``_exact_two_tier`` (lz.link is the shared probe + link scan)
    with jax.named_scope("lz.link"):
        flat = _ingest_scan_core(arena, qd, shard_id, probe_excl, link_excl,
                                 tenant, k, shard_modes)
        p_s, p_r = flat[0][:, 0], flat[1][:, 0]
        link_flat = flat[2:]

    with jax.named_scope("lz.dedup"):
        target, dup, chain_src = _dedup_resolve(qf, rows, valid, chain_gid,
                                                p_s, p_r, dedup_gate, cap)

    with jax.named_scope("lz.scatter"):
        live_new = valid & ~dup
        add_rows = jnp.where(live_new, rows, cap)
        arena = _arena_add(arena, add_rows, emb, salience, timestamp,
                           type_id, shard_id, tenant_id, is_super)
        shadow = _shadow_scatter(shadow, add_rows, qd)
        pq = _pq_scatter(pq, add_rows, qd)
        touch_rows = jnp.where(dup, target, cap)
        arena = _arena_merge_touch(arena, touch_rows, salience, now)
        chain_live = chain_src >= 0
        edges = _edges_add(edges, chain_slots, chain_src, rows,
                           jnp.broadcast_to(chain_w, (b,)),
                           jnp.ones((b,), jnp.int32), now, tenant,
                           chain_live)
        edges, outs = _gated_link_insert(edges, link_flat, link_pool,
                                         pool_len, rows, live_new, now,
                                         tenant, link_gate, link_scale,
                                         shard_modes)
    if ivf is not None:
        # Online IVF maintenance (ISSUE 12): the SAME dispatch scores the
        # surviving facts against the centroids, appends them to their
        # clusters' member tables, and blends the mini-batch centroid
        # step — assignments are never stale behind an offline rebuild.
        # Duplicates never append (live_new gates them); merge targets
        # already sit in their clusters.
        ivf, a_rb, p_rb, tail = _ivf_online_update(ivf, rows, qf, live_new,
                                                   ivf_eta)
        outs = outs + tuple(
            jnp.broadcast_to(x[:, None], (b, k)) for x in (a_rb, p_rb)
        ) + tuple(jnp.broadcast_to(t, (b, k)) for t in tail)
    if page_tail:
        outs = outs + tuple(jnp.broadcast_to(t, (b, k)) for t in page_tail)
    # [B] verdicts broadcast to [B, k] so every readback leaf has one shape
    # and the host fetches them all in ONE packed transfer
    wide = tuple(jnp.broadcast_to(a[:, None], (b, k))
                 for a in (dup.astype(jnp.int32), target, chain_src))
    return arena, edges, shadow, ivf, pq, ptable, wide + outs


ingest_dedup_fused, ingest_dedup_fused_copy = _donated_pair(
    _ingest_dedup_fused, donate=(0, 1, 2, 3, 4, 5),
    static_argnames=("k", "shard_modes"))


# ---------------------------------------------------------------------------
# Pod-scale fused INGEST (ISSUE 9): the whole ``ingest_dedup_fused`` program
# — dedup probe, intra-batch gram resolve, node scatter, merge touch, both
# link scans, gated edge insert with prefix-sum pool compaction, incremental
# int8 shadow update — composed with the device mesh as ONE distributed
# shard_map dispatch + ONE packed readback. The write-path mirror of
# ``make_fused_sharded`` (PR 5):
#
# - Every arena column, the edge arena, and the int8 shadow are row-sharded
#   over the mesh axis; the fact batch (rows, embeddings, metadata, edge
#   slots, link pool) is replicated.
# - Each chip runs the SAME shard-local scan core the single-chip kernel
#   traces (``_ingest_scan_core`` — dedup-probe top-1 + per-mode link top-k
#   selected in one pass over the local rows), and the ONLY cross-chip traffic is ONE
#   all_gather merging probe + every link mode's candidates in a single
#   grouped combine (``ops.topk.sharded_grouped_topk_merge``).
# - The dedup resolve, gate verdicts, and prefix-sum pool compaction are
#   then REPLICATED arithmetic on the merged lists (identical on every
#   chip), and all writes land owner-chip-local: row/slot index vectors are
#   localized per chip with non-owned entries routed one-past-the-end —
#   XLA drops out-of-bounds scatter updates, the PR 5 boost-scatter trick —
#   so the node scatter, merge touch, shadow update, chain edges, and the
#   compacted link insert are all shard-local writes through the SAME
#   mutation kernels (``_arena_add`` / ``_arena_merge_touch`` /
#   ``_shadow_scatter`` / ``_edges_add`` / ``_gated_link_insert``) the
#   single-chip program traces. Parity is structural.
# - The packed readback (dup verdicts, per-mode candidate triples, overflow
#   flag, accepted-link count, pool occupancy) is replicated output — the
#   host fetches it once, exactly like the single-chip readback.
# ---------------------------------------------------------------------------


class IngestShardedKernels(NamedTuple):
    """The jit entry points one ``make_ingest_fused_sharded`` call builds:
    the donated distributed ingest program and its copy-on-write twin (for
    callers that cannot prove sole ownership of the states — also the
    surface the peak-HBM gauge AOT-lowers, since it has no donation).
    Tests and bench wrap the caller's dispatch hook to count calls — each
    call is exactly ONE distributed dispatch."""

    ingest: Callable
    ingest_copy: Callable


def make_ingest_fused_sharded(mesh, axis: str, *, k: int,
                              shard_modes: Tuple[int, ...] = (1, 0),
                              with_shadow: bool = False,
                              with_ivf: bool = False,
                              with_pq: bool = False,
                              dedup: bool = True
                              ) -> IngestShardedKernels:
    """Build the distributed fused ingest program for ``mesh``.

    Call signature (``with_shadow=False``, ``dedup=True``)::

        ingest(arena, edges, rows [B], emb [B,d], salience [B],
               timestamp [B], type_id [B], shard_id [B], tenant_id [B],
               is_super [B], chain_gid [B], chain_slots [B],
               link_pool [P+1], pool_len, now, tenant, dedup_gate,
               chain_w, link_gate, link_scale, ivf_eta)
            -> (arena, edges, outs)

    with ``arena``/``edges`` row-sharded over ``axis`` and every batch
    input replicated; ``outs`` is bit-compatible with the single-chip
    ``ingest_dedup_fused`` readback tuple (3 wide dup/target/chain leaves,
    3 per shard mode, 3 trailing counters — all [B, k], fetched with
    ``utils.batching.fetch_packed`` in ONE transfer). ``rows``,
    ``chain_slots``, and ``link_pool`` carry GLOBAL row / edge-slot ids;
    the global sentinel row/slot is the LAST row/slot of the last shard,
    so the single-chip sentinel-routing convention carries over unchanged.
    ``with_shadow=True`` inserts ``(q8 [rows,d] i8, scale [rows] f32)``
    row-sharded args after ``edges`` and returns them updated — the
    incremental int8 shadow maintenance riding the same dispatch.

    ``with_ivf=True`` (ISSUE 12) additionally threads the ONLINE IVF
    tables: ``cent [C, d]`` replicated, ``members [n, C, M]`` stacked
    per shard with LOCAL row indices (the same layout ``make_fused_
    sharded`` mode="ivf" serves from, so the live ingest-maintained
    tables feed the pod serving kernel directly), and ``counts [n, C]``
    REPLICATED per-(shard, cluster) occupancy — replicated so every chip
    computes identical append positions / overflow verdicts and the
    readback stays replicated arithmetic without a second collective.
    The centroid scores ride the existing grouped all_gather as one more
    candidate group (each chip scores its ``C/n`` slice of the
    replicated centroid block and contributes its local top-1; when
    ``C % n != 0`` every chip scores the full block and the merge is a
    no-op), member appends land owner-chip-local through the same OOB
    scatter routing as every other write, and the mini-batch centroid
    step is replicated arithmetic. Readback grows the same 6 trailing
    leaves as the single-chip kernel (assign, member pos, overflow,
    occupancy, appends, centroid shift).

    ``with_pq=True`` (ISSUE 16) threads the PQ pack after the IVF
    tables: ``book_cent [m, 256, dsub]`` replicated (frozen between
    re-seeds) and ``codes [rows, m]`` u8 row-sharded with the master.
    The accepted rows' codes are re-encoded against the codebook and
    scattered owner-chip-local through the same localized row vector as
    the node scatter (``_pq_scatter`` — replicated arithmetic, local
    write). No extra readback leaves, no extra collectives.

    ``dedup=False`` builds the NON-dedup program instead (ROADMAP
    residual: ``ingest_batch`` under a mesh) — the ``_ingest_fused``
    semantics composed with the mesh: explicit merge-touch rows and
    chain triples, post-add link scan, no probe group in the merge::

        ingest(arena, edges, rows [B], emb [B,d], salience, timestamp,
               type_id, shard_id, tenant_id, is_super, touch_rows [M],
               touch_sal [M], chain_slots [C], chain_src [C],
               chain_tgt [C], chain_w [C], link_pool [P+1], pool_len,
               now, tenant, link_gate, link_scale, ivf_eta)
            -> (arena, edges, outs)

    with ``outs`` bit-compatible with the single-chip ``ingest_fused``
    readback (3 leaves per shard mode + 3 trailing counters).

    ``ingest`` donates the state arguments (zero-copy shard-local
    scatters); ``ingest_copy`` is the non-donating twin."""
    from jax.sharding import PartitionSpec as P

    from lazzaro_tpu.ops.topk import sharded_grouped_topk_merge
    from jax import shard_map

    shard_modes = tuple(shard_modes)
    n_modes = len(shard_modes)
    n_shards = mesh.shape[axis]

    def _localize(idx, base, n_local):
        """Global index vector → this chip's local indices; non-owned
        entries route to ``n_local`` (one past the end — OOB scatter
        updates are dropped, never wrapped)."""
        loc = idx - base
        return jnp.where((loc >= 0) & (loc < n_local), loc, n_local)

    def _split_state(rest):
        shadow = ivf = pq = None
        if with_shadow:
            shadow, rest = (rest[0], rest[1]), rest[2:]
        if with_ivf:
            # members arrive stacked [1, C, M] inside shard_map
            ivf, rest = (rest[0], rest[1][0], rest[2]), rest[3:]
        if with_pq:
            pq, rest = (rest[0], rest[1]), rest[2:]
        return shadow, ivf, pq, rest

    def _cent_group(ivf, qf, shard):
        """This chip's centroid-slice top-1 as one more merge candidate
        group: (score [B,1], GLOBAL centroid id [B,1])."""
        cent = ivf[0]
        C = cent.shape[0]
        if C % n_shards == 0 and n_shards > 1:
            c_loc = C // n_shards
            cent_l = jax.lax.dynamic_slice_in_dim(
                cent, shard * c_loc, c_loc, 0)
            s1, i1 = jax.lax.top_k(
                jnp.dot(qf, cent_l.T, preferred_element_type=jnp.float32),
                1)
            return s1, (i1 + shard * c_loc).astype(jnp.int32)
        s1, i1 = jax.lax.top_k(
            jnp.dot(qf, cent.T, preferred_element_type=jnp.float32), 1)
        return s1, i1.astype(jnp.int32)

    def _ivf_sharded_update(ivf, rows, qf, live, assign, ivf_eta, shard,
                            local_n):
        """The mesh twin of ``_ivf_online_update``: append positions,
        overflow verdicts, occupancy counts and the centroid step are
        REPLICATED arithmetic (counts carries every shard's occupancy);
        only the member-table scatter is owner-chip-local. Member
        positions are per-(shard, cluster) — each chip's table has its
        own dense prefix, so single-chip and mesh positions differ while
        the served candidate UNION stays identical (overflow aside)."""
        cent, mem_l, counts = ivf
        C = cent.shape[0]
        M = mem_l.shape[1]
        b = rows.shape[0]
        owner = jnp.clip(rows // local_n, 0, n_shards - 1)
        a = jnp.where(live, assign, C)
        same = ((a[:, None] == a[None, :])
                & (owner[:, None] == owner[None, :]) & live[None, :])
        rank = (same & jnp.tri(b, k=-1, dtype=bool)).sum(axis=1)
        counts_pre = counts
        cnt = counts_pre[jnp.where(live, owner, 0),
                         jnp.where(live, a, 0)]
        pos = jnp.where(live, cnt + rank.astype(jnp.int32), -1)
        ok = live & (pos >= 0) & (pos < M)
        o_s = jnp.where(ok, owner, n_shards)
        a_s = jnp.where(ok, a, C)
        counts = counts_pre.at[o_s, a_s].add(ok.astype(jnp.int32))
        mine = ok & (owner == shard)
        a_m = jnp.where(mine, a, C)
        p_m = jnp.where(mine, pos, M)
        mem_l = mem_l.at[a_m, p_m].set(
            (rows - shard * local_n).astype(jnp.int32))
        # centroid step: replicated, with the GLOBAL per-cluster mass
        # (sum over shards) as the learning-rate denominator — the same
        # total the single-chip kernel uses
        sums = jnp.zeros((C, qf.shape[1]), jnp.float32
                         ).at[a].add(jnp.where(live[:, None], qf, 0.0))
        bc = jnp.zeros((C,), jnp.float32).at[a].add(live.astype(
            jnp.float32))
        tot = counts_pre.sum(axis=0).astype(jnp.float32)
        eta = jnp.clip(ivf_eta * bc / jnp.maximum(tot + bc, 1.0), 0.0, 1.0)
        mean = sums / jnp.maximum(bc[:, None], 1.0)
        prop = cent * (1.0 - eta[:, None]) + mean * eta[:, None]
        nrm = jnp.linalg.norm(prop, axis=1, keepdims=True)
        new_cent = jnp.where((bc[:, None] > 0) & (nrm > 1e-9),
                             prop / jnp.maximum(nrm, 1e-9), cent)
        shift = jnp.where(bc > 0, 1.0 - (new_cent * cent).sum(axis=1), 0.0)
        tail = (
            (live & ~ok).any().astype(jnp.int32),
            jnp.minimum(counts.sum(), jnp.int32(n_shards * C * M)
                        ).astype(jnp.int32),
            ok.sum().astype(jnp.int32),
            jnp.clip(jnp.round(shift.sum() * 1e6), 0,
                     2 ** 30).astype(jnp.int32),
        )
        return ((new_cent, mem_l, counts), jnp.where(live, assign, -1),
                jnp.where(ok, pos, -1), tail)

    def _ivf_outs(ivf_new, a_rb, p_rb, tail, b):
        return tuple(
            jnp.broadcast_to(x[:, None], (b, k)) for x in (a_rb, p_rb)
        ) + tuple(jnp.broadcast_to(t, (b, k)) for t in tail)

    def _pack_state(arena, edges, shadow, ivf, pq, outs):
        out = (arena, edges)
        if with_shadow:
            out = out + (shadow[0], shadow[1])
        if with_ivf:
            out = out + (ivf[0], ivf[1][None, :, :], ivf[2])
        if with_pq:
            out = out + (pq[0], pq[1])
        return out + (outs,)

    def _local(arena, edges, *rest):
        shadow, ivf, pq, rest = _split_state(rest)
        (rows, emb, salience, timestamp, type_id, shard_id_v, tenant_id_v,
         is_super, chain_gid, chain_slots, link_pool, pool_len, now, tenant,
         dedup_gate, chain_w, link_gate, link_scale, ivf_eta) = rest
        shard = jax.lax.axis_index(axis)
        local_n = arena.emb.shape[0]
        cap = n_shards * local_n - 1           # GLOBAL capacity / sentinel
        local_e = edges.src.shape[0]
        b = rows.shape[0]
        k_l = max(1, min(k, local_n))
        valid = rows < cap
        qf = normalize(emb)
        qd = qf.astype(arena.emb.dtype)

        # Shard-local scan: the SAME core the single-chip kernel traces,
        # over this chip's rows — exclusion masks localized (the global
        # sentinel lives on the LAST shard only).
        row_base = shard * local_n
        rows_l = _localize(rows, row_base, local_n)
        probe_excl = jnp.arange(local_n) == (cap - row_base)
        link_excl = (jnp.zeros((local_n,), bool).at[rows_l].set(True)
                     | probe_excl)
        flat = _ingest_scan_core(arena, qd, shard_id_v, probe_excl,
                                 link_excl, tenant, k_l, shard_modes)
        # ONE all_gather merges the probe AND every link mode's local
        # candidates (grouped combine; candidate ids globalized first, so
        # masked/garbage entries route to the global sentinel row) — and
        # with online IVF the centroid scores ride the SAME collective as
        # a fourth candidate group.
        cat_s = [flat[2 * g] for g in range(1 + n_modes)]
        cat_i = [_globalize_rows(flat[2 * g + 1], flat[2 * g], shard,
                                 local_n, n_shards)
                 for g in range(1 + n_modes)]
        widths = [1] + [k_l] * n_modes
        ks = [1] + [k] * n_modes
        if ivf is not None:
            c_s, c_i = _cent_group(ivf, qf, shard)
            cat_s.append(c_s)
            cat_i.append(c_i)
            widths.append(1)
            ks.append(1)
        merged = sharded_grouped_topk_merge(
            axis, jnp.concatenate(cat_s, axis=1),
            jnp.concatenate(cat_i, axis=1), widths=widths, ks=ks)
        merged = jax.lax.optimization_barrier(merged)
        p_s, p_r = merged[0][0][:, 0], merged[0][1][:, 0]
        link_flat = tuple(a for pair in merged[1 + 0:1 + n_modes]
                          for a in pair)
        assign = merged[-1][1][:, 0] if ivf is not None else None

        # Dedup resolve + gate logic are replicated arithmetic from here —
        # every chip computes identical verdicts, then scatters ONLY the
        # rows/slots it owns.
        target, dup, chain_src = _dedup_resolve(qf, rows, valid, chain_gid,
                                                p_s, p_r, dedup_gate, cap)
        live_new = valid & ~dup
        add_rows = jnp.where(live_new, rows, cap)
        add_l = _localize(add_rows, row_base, local_n)
        arena = _arena_add(arena, add_l, emb, salience, timestamp, type_id,
                           shard_id_v, tenant_id_v, is_super,
                           sentinel=_localize(cap, row_base, local_n))
        shadow = _shadow_scatter(shadow, add_l, qd)
        pq = _pq_scatter(pq, add_l, qd)
        touch_l = _localize(jnp.where(dup, target, cap), row_base, local_n)
        arena = _arena_merge_touch(arena, touch_l, salience, now)

        slot_base = shard * local_e
        chain_live = chain_src >= 0
        chain_l = _localize(chain_slots, slot_base, local_e)
        edges = _edges_add(edges, chain_l, chain_src, rows,
                           jnp.broadcast_to(chain_w, (b,)),
                           jnp.ones((b,), jnp.int32), now, tenant,
                           chain_live)
        # The compacting gated insert runs UNCHANGED — it only ever touches
        # slots through the pool array, so handing it a pool whose entries
        # are pre-localized (non-owned → OOB) makes every accepted edge an
        # owner-chip-local write while positions/readback stay global.
        pool_l = _localize(link_pool, slot_base, local_e)
        edges, outs = _gated_link_insert(edges, link_flat, pool_l, pool_len,
                                         rows, live_new, now, tenant,
                                         link_gate, link_scale, shard_modes)
        if ivf is not None:
            ivf, a_rb, p_rb, tail = _ivf_sharded_update(
                ivf, rows, qf, live_new, assign, ivf_eta, shard, local_n)
            outs = outs + _ivf_outs(ivf, a_rb, p_rb, tail, b)
        wide = tuple(jnp.broadcast_to(a[:, None], (b, k))
                     for a in (dup.astype(jnp.int32), target, chain_src))
        return _pack_state(arena, edges, shadow, ivf, pq, wide + outs)

    def _local_plain(arena, edges, *rest):
        """The non-dedup program (``ingest_batch`` under a mesh): the
        SAME semantics as the single-chip ``_ingest_fused`` — node
        scatter, explicit merge touch, POST-add link scan per shard mode,
        explicit chain triples, gated compacted link insert — shard-local
        scans, one grouped all_gather, owner-chip writes."""
        shadow, ivf, pq, rest = _split_state(rest)
        (rows, emb, salience, timestamp, type_id, shard_id_v, tenant_id_v,
         is_super, touch_rows, touch_sal, chain_slots, chain_src,
         chain_tgt, chain_w, link_pool, pool_len, now, tenant, link_gate,
         link_scale, ivf_eta) = rest
        shard = jax.lax.axis_index(axis)
        local_n = arena.emb.shape[0]
        cap = n_shards * local_n - 1
        local_e = edges.src.shape[0]
        b = rows.shape[0]
        k_l = max(1, min(k, local_n))
        qf = normalize(emb)
        qd = qf.astype(arena.emb.dtype)
        row_base = shard * local_n
        rows_l = _localize(rows, row_base, local_n)
        arena = _arena_add(arena, rows_l, emb, salience, timestamp,
                           type_id, shard_id_v, tenant_id_v, is_super,
                           sentinel=_localize(cap, row_base, local_n))
        shadow = _shadow_scatter(shadow, rows_l, qd)
        pq = _pq_scatter(pq, rows_l, qd)
        touch_l = _localize(touch_rows, row_base, local_n)
        arena = _arena_merge_touch(arena, touch_l, touch_sal, now)
        # post-add link scan, batch rows excluded as candidates — the
        # single-chip kernel's _arena_link_candidates_multi semantics
        # (no probe group, no sentinel exclusion: decode drops id-less
        # hits host-side exactly like the single-chip path)
        link_excl = jnp.zeros((local_n,), bool).at[rows_l].set(True)
        flat = _ingest_scan_core(arena, qd, shard_id_v,
                                 jnp.zeros((local_n,), bool), link_excl,
                                 tenant, k_l, shard_modes,
                                 with_probe=False)
        cat_s = [flat[2 * g] for g in range(n_modes)]
        cat_i = [_globalize_rows(flat[2 * g + 1], flat[2 * g], shard,
                                 local_n, n_shards)
                 for g in range(n_modes)]
        widths = [k_l] * n_modes
        ks = [k] * n_modes
        if ivf is not None:
            c_s, c_i = _cent_group(ivf, qf, shard)
            cat_s.append(c_s)
            cat_i.append(c_i)
            widths.append(1)
            ks.append(1)
        merged = sharded_grouped_topk_merge(
            axis, jnp.concatenate(cat_s, axis=1),
            jnp.concatenate(cat_i, axis=1), widths=widths, ks=ks)
        merged = jax.lax.optimization_barrier(merged)
        link_flat = tuple(a for pair in merged[:n_modes] for a in pair)
        assign = merged[-1][1][:, 0] if ivf is not None else None

        n_chain = chain_slots.shape[0]
        slot_base = shard * local_e
        chain_l = _localize(chain_slots, slot_base, local_e)
        edges = _edges_add(edges, chain_l, chain_src, chain_tgt, chain_w,
                           jnp.ones((n_chain,), jnp.int32), now, tenant,
                           chain_src >= 0)
        valid_q = rows < cap
        pool_l = _localize(link_pool, slot_base, local_e)
        edges, outs = _gated_link_insert(edges, link_flat, pool_l,
                                         pool_len, rows, valid_q, now,
                                         tenant, link_gate, link_scale,
                                         shard_modes)
        if ivf is not None:
            ivf, a_rb, p_rb, tail = _ivf_sharded_update(
                ivf, rows, qf, valid_q, assign, ivf_eta, shard, local_n)
            outs = outs + _ivf_outs(ivf, a_rb, p_rb, tail, b)
        return _pack_state(arena, edges, shadow, ivf, pq, outs)

    arena_specs = ArenaState(
        emb=P(axis, None), salience=P(axis), timestamp=P(axis),
        last_accessed=P(axis), access_count=P(axis), type_id=P(axis),
        shard_id=P(axis), tenant_id=P(axis), alive=P(axis),
        is_super=P(axis))
    edge_specs = EdgeState(
        src=P(axis), tgt=P(axis), weight=P(axis), co=P(axis),
        last_updated=P(axis), alive=P(axis), tenant_id=P(axis))
    shadow_specs = (P(axis, None), P(axis)) if with_shadow else ()
    # cent replicated, members stacked per shard, counts replicated
    ivf_specs = ((P(None, None), P(axis, None, None), P(None, None))
                 if with_ivf else ())
    # codebook replicated (frozen), codes row-sharded with the master
    pq_specs = ((P(None, None, None), P(axis, None)) if with_pq else ())
    if dedup:
        batch_specs = (
            P(None),        # rows
            P(None, None),  # emb
            P(None), P(None), P(None), P(None), P(None), P(None),  # per-fact
            P(None),        # chain_gid
            P(None),        # chain_slots
            P(None),        # link_pool
            P(), P(), P(), P(), P(), P(), P(), P(),  # pool_len..ivf_eta
        )
        n_out = 3 + 3 * n_modes + 3 + (IVF_INGEST_TAIL if with_ivf else 0)
        fn = _local
    else:
        batch_specs = (
            P(None),        # rows
            P(None, None),  # emb
            P(None), P(None), P(None), P(None), P(None), P(None),  # per-fact
            P(None), P(None),                  # touch_rows, touch_sal
            P(None), P(None), P(None), P(None),  # chain slot/src/tgt/w
            P(None),        # link_pool
            P(), P(), P(), P(), P(), P(),  # pool_len..ivf_eta scalars
        )
        n_out = 3 * n_modes + 3 + (IVF_INGEST_TAIL if with_ivf else 0)
        fn = _local_plain
    out_state = (arena_specs, edge_specs) + shadow_specs + ivf_specs \
        + pq_specs
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(arena_specs, edge_specs) + shadow_specs + ivf_specs
        + pq_specs + batch_specs,
        out_specs=out_state + (tuple(P(None, None) for _ in range(n_out)),),
        check_vma=False)
    donate = tuple(range(2 + len(shadow_specs) + len(ivf_specs)
                         + len(pq_specs)))
    return IngestShardedKernels(
        ingest=jax.jit(mapped, donate_argnums=donate),
        ingest_copy=jax.jit(mapped))


# ---------------------------------------------------------------------------
# Fused retrieval: the per-chat-turn serving sequence — super-node gate +
# main-arena ANN + CSR neighbor gather + neighbor/access boosts — in ONE
# donated device program with ONE packed readback (the serving-side analog
# of ingest_fused; see ISSUE 2).
#
# Request shapes are DEVICE DATA: per-query k / retrieval cap / probe width
# ride next to the query batch as int32 columns (``k_q`` / ``cap_q`` /
# ``nprobe_q``), and the static kernel constants are per-mode CEILINGS
# (``k`` = serve_k_max, ``cap_take`` = the config cap, ``nprobe`` = the
# build's probe width). The scan bodies compute to the ceiling and each
# query masks at its own top-k boundary (``_ragged_topk_mask``), its own
# retrieval cap (``_gate_and_boost_rows`` cap_c) and its own probe width
# (``_ivf_two_tier`` nprobe_c), so ONE compiled kernel per (mode ×
# geometry) serves any mix of request shapes — a k=100 request neither
# re-keys the batch's kernel nor inflates its neighbors' top-k beyond
# masked compute. The packed readback's n_live counter is the per-query
# live LENGTH: decode reads exactly k_i live entries per request out of
# the K-wide rows.
#
# Each of the seven families (exact, quant, tiered, ivf, ivf_tiered, pq,
# pq_tiered) has three entry points: ``search_fused_*_ragged`` donates the
# arena, ``*_ragged_copy`` is its non-donating twin for callers that cannot
# prove sole ownership, and ``*_ragged_read`` serves batches where no query
# wants boosts — same compute, no state mutation, no donation dance.
# ---------------------------------------------------------------------------


def _csr_neighbor_rows(state: ArenaState, csr_indptr: jax.Array,
                       csr_nbr: jax.Array, acc_rows: jax.Array,
                       tenant_c: jax.Array, max_nbr: int) -> jax.Array:
    """CSR neighbor gather for the access-boosted rows with per-query dedup
    (sentinel row's indptr slice is empty, so masked rows gather nothing).
    Shared by the exact and quantized fused serving scans."""
    cap = state.capacity
    start = csr_indptr[acc_rows]
    end = csr_indptr[acc_rows + 1]
    idx = start[:, :, None] + jnp.arange(max_nbr)[None, None, :]
    ok = idx < end[:, :, None]
    nbr = jnp.where(ok, csr_nbr[jnp.minimum(idx, csr_nbr.shape[0] - 1)],
                    -1)
    flat = nbr.reshape(nbr.shape[0], -1)                  # [C, M]
    m = flat.shape[1]
    safe = jnp.maximum(flat, 0)
    valid_n = ((flat >= 0) & state.alive[safe]
               & (state.tenant_id[safe] == tenant_c[:, None]))
    # per-query dedup (keep first occurrence): classic boosts a shared
    # neighbor ONCE per turn however many retrieved nodes touch it...
    dup = ((flat[:, :, None] == flat[:, None, :])
           & jnp.tri(m, k=-1, dtype=bool)[None, :, :]).any(-1)
    # ...and never boosts a node that was itself retrieved
    in_res = (flat[:, :, None] == acc_rows[:, None, :]).any(-1)
    return jnp.where(valid_n & ~dup & ~in_res, flat, cap)


def _ragged_topk_mask(ann_s: jax.Array, ann_r: jax.Array, k_c: jax.Array,
                      sentinel: int):
    """Per-query top-k boundary mask: the scan computed top-``K`` to the
    batch CEILING (a static kernel constant), and each query's own ``k``
    arrives as DEVICE data
    (``k_c`` [C] i32). Positions at or past a query's k are routed to
    (NEG_INF, sentinel), so decode, the live-length counter, and the boost
    tail all see exactly the per-request result — one compiled kernel per
    (mode × geometry) serves any mix of request shapes. Equivalent to a
    per-query ``top_k(k_i)`` because the ceiling top-k is score-sorted."""
    col = jnp.arange(ann_s.shape[1])[None, :]
    live = col < k_c[:, None]
    return (jnp.where(live, ann_s, NEG_INF),
            jnp.where(live, ann_r, sentinel))


def _gate_and_boost_rows(state: ArenaState, csr_indptr, csr_nbr, gate_s,
                         gate_r, ann_s, ann_r, valid_c, tenant_c, gate_c,
                         boost_c, super_gate, cap_take: int, max_nbr: int,
                         cap_c=None):
    """The post-top-k tail both serving scans share: the device-side gate
    verdict, the access-boost row list, and the CSR neighbor gather.

    The hierarchy decision happens ON DEVICE: where the gate fires the host
    serves super-node children it alone knows, so the device must NOT boost
    the ANN rows (the host falls back to the classic boost for those
    queries — exact parity on the fast path).

    ``cap_c`` (optional [C] i32) is the ragged per-query retrieval cap:
    ``cap_take`` stays the STATIC slice ceiling, and each query's own cap
    masks within it, so one kernel serves mixed per-request caps."""
    cap = state.capacity
    with jax.named_scope("lz.gate"):
        fast = gate_c & (gate_s > super_gate)
        do_boost = boost_c & valid_c & ~fast              # [C]
        take = (ann_s[:, :cap_take] > NEG_INF / 2) & do_boost[:, None]
        if cap_c is not None:
            take = take & (jnp.arange(cap_take)[None, :] < cap_c[:, None])
        acc_rows = jnp.where(take, ann_r[:, :cap_take], cap)  # [C, cap_take]
    with jax.named_scope("lz.csr"):
        nbr_rows = _csr_neighbor_rows(state, csr_indptr, csr_nbr, acc_rows,
                                      tenant_c, max_nbr)
    return fast, acc_rows, nbr_rows


def _exact_two_tier(state: ArenaState, q_c: jax.Array, tenant_c: jax.Array,
                    k_ann: int, k_c=None):
    """Masked super top-1 + masked main top-``k_ann`` of every query over
    its own tenant's rows, SELECTED WHILE THE POOL STREAMS from HBM once
    (ISSUE 26; ``ops/pallas_topk.blocked_two_tier``): per block the scores,
    the per-query mask, the gate's running top-1 and the main tier's running
    top-k stay on chip, so no ``[C, rows]`` score tile exists and the
    selection work follows each query's own k — ``k_c`` ([C] i32 device
    data; None runs every query to ``k_ann``) — not the static ceiling
    ``k_ann`` the shapes are compiled to. Slots past a
    query's k, or past its tenant's live rows, hold ``(NEG_INF, capacity)``
    (what ``_ragged_topk_mask`` writes). The shard-local core of the exact
    fused scan: single-chip callers pass the whole arena, the sharded
    program passes each chip's local slice; a paged pool scans in pool
    space and maps the survivors back.

    The trailing barrier is the PR 2 consumer-split fix: the top-k results
    feed BOTH the packed readback and the boost gather chain; without it
    XLA (CPU at least) splits the consumers and runs the core twice."""
    from lazzaro_tpu.ops.pallas_topk import ROW_DEAD, blocked_two_tier

    # The named scopes (here, in the core and in the tail below) are
    # compile-time metadata on the program's operations: a device trace can
    # then say which phase an operation belongs to. They cost nothing at
    # run time.
    with jax.named_scope("lz.norms"):
        qn = normalize(q_c).astype(state.emb.dtype)
    with jax.named_scope("lz.scan"):
        alive_p = _pool_mask(state, state.alive)
        ten_p = _pool_col(state, state.tenant_id).astype(jnp.int32)
        sup = _pool_col(state, state.is_super)
        row_main = jnp.where(alive_p & ~sup, ten_p, ROW_DEAD)
        row_gate = jnp.where(alive_p & sup, ten_p, ROW_DEAD)
    gate_s, gate_r, ann_s, ann_r = blocked_two_tier(
        state.emb, qn, row_main, row_gate, tenant_c, k_ann, k_c)
    with jax.named_scope("lz.topk"):
        gate_r = _pool_to_logical(state, gate_r)
        ann_r = _pool_to_logical(state, ann_r)
    return jax.lax.optimization_barrier((gate_s, gate_r, ann_s, ann_r))


# ---------------------------------------------------------------------------
# Semantic query cache (ISSUE 20): a SemanticRing probe riding INSIDE every
# fused serving kernel. The per-dispatch flow, all in the one program:
#
#   probe     — top-1 cosine of each (normalized) query against the ring,
#               masked by tenant / gate flag / mode / stored_k / nprobe and
#               the HOST-owned valid bits; >= threshold is a hit.
#   early-out — queries are stably sorted misses-first, and the family's
#               chunk function runs under a ``lax.while_loop`` over fixed
#               ``sem_block``-sized blocks with a DYNAMIC trip count of
#               ceil(n_miss / block): blocks past the miss prefix never
#               execute, so an 80%-hit batch pays ~20% of the scan FLOPs
#               while shapes stay static and the dispatch count stays ONE.
#   subst     — hit queries' gate/ann columns come from the cached entry
#               (re-masked at the query's own k; the gate VERDICT is
#               recomputed against the current threshold); their boost rows
#               stay at the scatter sentinel — semantic hits defer boosts to
#               the host exactly like exact-cache hits.
#   writeback — the last R misses rotate into slots (head + rank) % R in
#               the same dispatch (LIFO, like the paged arena's free stack);
#               dropped writes scatter to the ring's scratch row.
#
# The sorted order is stable, so rank j IS the j-th miss in batch order —
# the host mirrors head/slot assignment from the readback's sem column
# alone, and ships the valid bits + head back in on the next dispatch.
# With the cache disabled (``sem=None``) nothing here traces; with the
# cache cold the sort is the identity permutation and every block runs, so
# results stay bit-identical to the cache-off program.
# ---------------------------------------------------------------------------


def _semantic_probe(ring: SemanticRing, sem_valid: jax.Array, qn: jax.Array,
                    tenant_q: jax.Array, q_valid: jax.Array,
                    gate_on_q: jax.Array, k_need: jax.Array,
                    npr_need: jax.Array, mode_id: jax.Array,
                    thresh: jax.Array):
    """Top-1 cosine probe of the ring. Returns (hit [Q] bool, slot [Q]
    i32). ``sem_valid`` is the host-owned [R] validity mask; an entry is
    eligible only when tenant, gate flag, mode, and nprobe match and its
    stored depth covers the query's k."""
    r = ring.slots
    sims = nt_dot(qn, ring.emb[:r])                        # [Q, R]
    ok = (sem_valid[:r] & (ring.stored_k[:r] > 0))[None, :]
    ok = ok & (ring.mode[:r][None, :] == mode_id)
    ok = ok & (ring.tenant[:r][None, :] == tenant_q[:, None])
    ok = ok & (ring.gate_on[:r][None, :] == gate_on_q[:, None])
    ok = ok & (ring.stored_k[:r][None, :] >= k_need[:, None])
    ok = ok & (ring.nprobe[:r][None, :] == npr_need[:, None])
    s = jnp.where(ok, sims, NEG_INF)
    slot = jnp.argmax(s, axis=1).astype(jnp.int32)
    hit = q_valid & (jnp.max(s, axis=1) >= thresh)
    return hit, slot


def _semantic_blocked(chunk_fn, arrays, n_miss: jax.Array, block: int,
                      capacity: int):
    """Run ``chunk_fn`` (any family's per-chunk closure) over the sorted
    batch in static ``block``-sized pieces with a dynamic trip count —
    only ceil(n_miss / block) blocks execute. Skipped queries keep safe
    fillers that mirror a fully-masked scan: NEG_INF scores, sentinel
    rows, False flags (the boost scatter's sentinel routing and decode's
    live counters treat them exactly like masked pad queries)."""
    b = arrays[0].shape[0]
    block = max(1, min(int(block), b))
    pad = (-b) % block
    if pad:
        arrays = tuple(
            jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            for a in arrays)
    shapes = jax.eval_shape(chunk_fn, *tuple(a[:block] for a in arrays))

    def _fill(sd):
        shape = (b + pad,) + tuple(sd.shape[1:])
        if sd.dtype == jnp.bool_:
            return jnp.zeros(shape, jnp.bool_)
        if jnp.issubdtype(sd.dtype, jnp.floating):
            return jnp.full(shape, NEG_INF, sd.dtype)
        return jnp.full(shape, capacity, sd.dtype)

    outs0 = tuple(_fill(s) for s in shapes)
    n_run = (n_miss.astype(jnp.int32) + block - 1) // block

    def cond(carry):
        return carry[0] < n_run

    def body(carry):
        i, outs = carry[0], carry[1:]
        start = i * block
        sub = tuple(jax.lax.dynamic_slice_in_dim(a, start, block, 0)
                    for a in arrays)
        res = chunk_fn(*sub)
        outs = tuple(
            jax.lax.dynamic_update_slice_in_dim(o, r, start, 0)
            for o, r in zip(outs, res))
        return (i + 1,) + outs

    out = jax.lax.while_loop(cond, body,
                             (jnp.zeros((), jnp.int32),) + outs0)[1:]
    return tuple(o[:b] for o in out)


def _semantic_substitute(ring: SemanticRing, hit: jax.Array, slot: jax.Array,
                         gate_on_q: jax.Array, super_gate: jax.Array, outs,
                         k_q, rag_slack: int, capacity: int):
    """Splice cached results over the hit queries' (filler) scan outputs.
    The cached list is sliced to this kernel's static window and re-masked
    at the query's own k (+slack for the tiered window); the gate
    verdict is recomputed against the CURRENT threshold so a runtime
    super-gate change can't serve a stale verdict."""
    gate_s, gate_r, ann_s, ann_r, fast = outs[:5]
    w = ann_s.shape[1]
    if ring.width < w:
        raise ValueError(
            f"semantic ring width {ring.width} < kernel window {w}; size "
            "the ring at the serving k ceiling (+slack for tiered modes)")
    c_gs = ring.gate_s[slot]
    c_gr = ring.gate_r[slot]
    c_as = ring.ann_s[slot, :w]
    c_ar = ring.ann_r[slot, :w]
    kf = jnp.minimum(k_q + rag_slack, w) if rag_slack else k_q
    c_as, c_ar = _ragged_topk_mask(c_as, c_ar, kf, capacity)
    c_fast = gate_on_q & (c_gs > super_gate)
    h1 = hit[:, None]
    return (jnp.where(hit, c_gs, gate_s),
            jnp.where(hit, c_gr, gate_r),
            jnp.where(h1, c_as, ann_s),
            jnp.where(h1, c_ar, ann_r),
            jnp.where(hit, c_fast, fast)) + tuple(outs[5:])


def _semantic_writeback(ring: SemanticRing, head: jax.Array, qn: jax.Array,
                        tenant_q: jax.Array, gate_on_q: jax.Array,
                        gate_s: jax.Array, gate_r: jax.Array,
                        ann_s: jax.Array, ann_r: jax.Array, rank: jax.Array,
                        write_mask: jax.Array, k_need: jax.Array,
                        npr_need: jax.Array, mode_id: jax.Array,
                        capacity: int) -> SemanticRing:
    """LIFO slot rotation inside the dispatch: miss ``rank`` lands in slot
    ``(head + rank) % R``; suppressed writes scatter to the scratch row.
    Callers pass rank in BATCH order among misses (the stable sort
    preserves it), so the host can mirror the slot assignment from the
    readback alone."""
    r = ring.slots
    slot_w = jnp.where(write_mask,
                       jnp.mod(head + rank, r), r).astype(jnp.int32)
    w = ann_s.shape[1]
    if w < ring.width:
        ann_s = jnp.pad(ann_s, ((0, 0), (0, ring.width - w)),
                        constant_values=NEG_INF)
        ann_r = jnp.pad(ann_r, ((0, 0), (0, ring.width - w)),
                        constant_values=capacity)
    b = qn.shape[0]
    return ring.replace(
        emb=ring.emb.at[slot_w].set(qn),
        tenant=ring.tenant.at[slot_w].set(tenant_q.astype(jnp.int32)),
        gate_on=ring.gate_on.at[slot_w].set(gate_on_q),
        mode=ring.mode.at[slot_w].set(
            jnp.broadcast_to(mode_id, (b,)).astype(jnp.int32)),
        stored_k=ring.stored_k.at[slot_w].set(k_need.astype(jnp.int32)),
        nprobe=ring.nprobe.at[slot_w].set(npr_need.astype(jnp.int32)),
        gate_s=ring.gate_s.at[slot_w].set(gate_s),
        gate_r=ring.gate_r.at[slot_w].set(gate_r.astype(jnp.int32)),
        ann_s=ring.ann_s.at[slot_w].set(ann_s),
        ann_r=ring.ann_r.at[slot_w].set(ann_r.astype(jnp.int32)))


def _semantic_scan_core(chunk_fn, arrays, state: ArenaState, sem,
                        super_gate: jax.Array, *, block: int,
                        rag_slack: int = 0):
    """The full in-dispatch semantic-cache flow around one family's chunk
    closure: probe → miss-first stable sort → blocked early-out scan →
    unsort → substitution → ring writeback. ``arrays`` is the family's
    per-query tuple ``(q, q_valid, tenant, gate_on, boost_on, k_q, cap_q[,
    nprobe_q])`` (the dense families carry no probe width: their entries
    store 0); returns the family's output tuple (dup counter
    zeroed for skipped queries) + ``(sem_col, new_ring)`` where sem_col
    is ``1 + slot`` for hits and 0 for misses."""
    ring, sem_valid, head, thresh, mode_id = sem
    q, q_valid, tenant, gate_on = arrays[0], arrays[1], arrays[2], arrays[3]
    nq = q.shape[0]
    k_q = arrays[5]
    npr_need = (arrays[7] if len(arrays) > 7
                else jnp.zeros((nq,), jnp.int32))
    qn = normalize(q).astype(jnp.float32)
    hit, slot = _semantic_probe(ring, sem_valid, qn, tenant, q_valid,
                                gate_on, k_q, npr_need, mode_id, thresh)
    miss = q_valid & ~hit
    order = jnp.argsort((~miss).astype(jnp.int32), stable=True)
    inv = jnp.argsort(order)
    n_miss = miss.sum().astype(jnp.int32)
    sorted_arrays = tuple(a[order] for a in arrays)
    outs_s = _semantic_blocked(chunk_fn, sorted_arrays, n_miss, block,
                               state.capacity)
    rank = jnp.arange(nq, dtype=jnp.int32)
    write_mask = miss[order] & (rank >= n_miss - ring.slots)
    ring2 = _semantic_writeback(
        ring, head, qn[order], sorted_arrays[2], sorted_arrays[3],
        outs_s[0], outs_s[1], outs_s[2], outs_s[3], rank, write_mask,
        k_q[order], npr_need[order], mode_id, state.capacity)
    outs = tuple(o[inv] for o in outs_s)
    outs = _semantic_substitute(ring, hit, slot, gate_on, super_gate, outs,
                                k_q, rag_slack, state.capacity)
    if len(outs) > 7:
        # trailing dup counter (IVF/PQ): skipped queries carried the int
        # filler — a hit or pad query suppressed zero duplicates
        outs = outs[:7] + (jnp.where(miss, outs[7], 0),) + tuple(outs[8:])
    sem_col = jnp.where(hit, 1 + slot, 0).astype(jnp.int32)
    return tuple(outs) + (sem_col, ring2)


def _search_fused_scan(state: ArenaState, csr_indptr: jax.Array,
                       csr_nbr: jax.Array, q: jax.Array, q_valid: jax.Array,
                       tenant: jax.Array, gate_on: jax.Array,
                       boost_on: jax.Array, super_gate: jax.Array,
                       k: int, cap_take: int, max_nbr: int,
                       k_q: jax.Array, cap_q: jax.Array,
                       scan_chunk: int = 0, sem=None, sem_block: int = 16):
    """Per-chunk compute phase: the exact two-tier top-k core, the
    device-side gate verdict, and the CSR neighbor gather with per-query
    dedup. Returns sentinel-padded row lists for the scatter phase
    (``capacity`` is the sentinel row index).

    ``k`` and ``cap_take`` are the static ceilings the SHAPES are compiled
    to; ``k_q``/``cap_q`` ([Q] i32 device columns) carry each query's own
    k and cap. The k rides into the core as data (``_exact_two_tier``'s
    ``k_c``): the selection runs to what the batch asks, and slots past a
    query's k come back as (NEG_INF, capacity) — per-request shapes are
    data, not trace constants.

    ``scan_chunk > 0`` (ISSUE 11) overrides the default ``QUERY_CHUNK``
    streaming width. In this family it no longer bounds a ``[chunk, rows]``
    score tile — none exists since ISSUE 26 — only the queries one pass of
    the pool carries (``[chunk, block]`` scores and ``[chunk, k]`` lists at
    a time), so a throttled budget gains little from it here; results stay
    bit-identical (the per-query computation never sees the chunk
    boundary)."""
    def chunk(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c):
        gate_s, gate_r, ann_s, ann_r = _exact_two_tier(state, q_c, tenant_c,
                                                       k, k_c)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, gate_s, gate_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c, super_gate, cap_take,
            max_nbr, cap_c=cap_c)
        return gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q)
    if sem is None:
        return chunked_map_multi(chunk, arrays,
                                 chunk=(scan_chunk or QUERY_CHUNK))
    return _semantic_scan_core(chunk, arrays, state, sem, super_gate,
                               block=sem_block)


def _search_fused_ragged(
    state: ArenaState,
    csr_indptr: jax.Array,   # [cap+2] i32 neighbor-list offsets per row
    csr_nbr: jax.Array,      # [E_pad] i32 neighbor rows (bidirectional)
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,                  # STATIC k ceiling (serve_k_max)
    cap_take: int,           # STATIC cap ceiling: top rows that get boosted
    max_nbr: int,
    scan_chunk: int = 0,     # planner streaming-width override (ISSUE 11)
    sem=None,                # (ring, valid [R], head, thresh, mode_id)
    sem_block: int = 16,
) -> Tuple[ArenaState, Tuple[jax.Array, ...]]:
    """One dispatch for a padded cross-tenant query batch of mixed request
    shapes: gate + ANN + neighbor gather + both boosts. Scatter counts make
    a mega-batch exact w.r.t. serial classic turns: a row retrieved by two
    queries gets TWO access bumps (``.add``), while within one query each
    neighbor is boosted once (the per-query dedup above) — matching what
    per-turn ``update_access`` + ``_boost_neighbors`` calls would have done.

    ``sem`` threads the semantic query cache through the SAME dispatch
    (probe / early-out / substitution / ring writeback — see
    ``_semantic_scan_core``); when present the return gains the updated
    ring: ``(state, ring, packed)``."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_scan(state, csr_indptr, csr_nbr, r.q, r.q_valid,
                             r.tenant, r.gate_on, r.boost_on, r.super_gate,
                             k, cap_take, max_nbr, k_q=r.k_q, cap_q=r.cap_q,
                             scan_chunk=scan_chunk, sem=sem,
                             sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


def _boost_scatter(state: ArenaState, acc_rows: jax.Array,
                   nbr_rows: jax.Array, now: jax.Array, acc_boost: jax.Array,
                   nbr_boost: jax.Array, zero_last: bool = True
                   ) -> ArenaState:
    """Scatter phase shared by every fused serving kernel: count-weighted
    access/neighbor salience boosts, capped at 1.0, with freshness
    inheritance for every touched row. Single-chip callers route masked
    rows to the in-range sentinel row (``zero_last=True`` zeroes its
    count); the shard-local scatters route non-owned rows OUT of range
    instead — XLA drops out-of-bounds scatter updates — so they pass
    ``zero_last=False``."""
    n = _nrows(state)
    acc_cnt = jnp.zeros((n,), jnp.int32).at[acc_rows.reshape(-1)].add(1)
    nbr_cnt = jnp.zeros((n,), jnp.int32).at[nbr_rows.reshape(-1)].add(1)
    if zero_last:
        acc_cnt = acc_cnt.at[n - 1].set(0)
        nbr_cnt = nbr_cnt.at[n - 1].set(0)
    sal = (state.salience + acc_cnt.astype(jnp.float32) * acc_boost
           + nbr_cnt.astype(jnp.float32) * nbr_boost)
    touched = (acc_cnt > 0) | (nbr_cnt > 0)
    return state.replace(
        salience=jnp.where(touched, jnp.minimum(sal, 1.0), state.salience),
        access_count=state.access_count + acc_cnt,
        last_accessed=jnp.where(touched, now, state.last_accessed))


# Width of the device-counter tail _pack_retrieval appends to every fused
# serving readback (ISSUE 6): per query [n_live, n_dedup_dropped,
# n_acc_boost_rows, n_nbr_boost_rows, sem] as bitcast int32. The marginal
# cost of device-side observability is these 20 bytes per query riding the
# ONE readback that already exists — never an extra dispatch or transfer.
# ``sem`` (ISSUE 20) is the semantic-cache verdict: 0 for a miss, 1+slot
# for a ring hit — the host mirrors ring occupancy and the row→slot
# reverse index from this column alone.
RETRIEVAL_TAIL = 5


def _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast, dup=None, acc=None,
                    nbr=None, sem=None) -> jax.Array:
    """ONE [Q, 3 + 2k + RETRIEVAL_TAIL] int32 readback array:
    [gate_score(bitcast), gate_row, ann_scores(bitcast)..k, ann_rows..k,
    fast, counters..5]. Packing happens in-kernel so the host pays exactly
    one device→host transfer and zero extra dispatches (f32 scores are
    bitcast, not cast — undone with a host-side ``.view(float32)``, same
    trick and same int32 carrier as ``utils.batching.fetch_packed``).

    The counter tail carries the device-side serving counters: live top-k
    hits (host derives the top-k shortfall against each request's k),
    duplicate candidates the IVF in-kernel dedup suppressed (``dup``;
    zero for the dense paths), the access/neighbor boost-scatter row
    counts (``acc``/``nbr``; zero for read twins, whose boost masks are
    all-off), and the semantic-cache verdict (``sem``; zero when the ring
    is absent)."""
    q = gate_s.shape[0]
    with jax.named_scope("lz.pack"):
        zeros = jnp.zeros((q,), jnp.int32)
        n_live = (ann_s > NEG_INF / 2).sum(axis=-1).astype(jnp.int32)
        dup = zeros if dup is None else dup.astype(jnp.int32)
        acc = zeros if acc is None else acc.astype(jnp.int32)
        nbr = zeros if nbr is None else nbr.astype(jnp.int32)
        sem = zeros if sem is None else sem.astype(jnp.int32)
        return jnp.concatenate([
            _bitcast_i32(gate_s)[:, None], gate_r.astype(jnp.int32)[:, None],
            _bitcast_i32(ann_s), ann_r.astype(jnp.int32),
            fast.astype(jnp.int32)[:, None],
            n_live[:, None], dup[:, None], acc[:, None], nbr[:, None],
            sem[:, None]], axis=1)


def _boost_row_counts(capacity: int, acc_rows: jax.Array,
                      nbr_rows: jax.Array):
    """Per-query counts of rows the boost scatter will actually touch
    (sentinel-routed entries excluded) — the device-side 'boost-scatter
    count' rider. Shared by every single-chip fused serving kernel."""
    acc = (acc_rows != capacity).sum(axis=-1)
    nbr = (nbr_rows != capacity).sum(axis=-1)
    return acc, nbr


def _sem_finish(state: ArenaState, res, sem, now, acc_boost, nbr_boost):
    """Shared serve-twin tail across every fused serving family: unpack
    the scan result (which carries ``(sem_col, new_ring)`` extras when the
    semantic cache rode the dispatch), apply the boost scatter, pack the
    readback. With the cache on the twin returns ``(state, ring, packed)``
    — the ring is NOT donated (it is small and the caller swaps it in
    after the dispatch), the arena donation story is unchanged."""
    if sem is None:
        core, sem_col, ring2 = res, None, None
    else:
        core, sem_col, ring2 = res[:-2], res[-2], res[-1]
    gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows = core[:7]
    n_dup = core[7] if len(core) > 7 else None
    n_acc, n_nbr = _boost_row_counts(state.capacity, acc_rows, nbr_rows)
    state = _boost_scatter(state, acc_rows, nbr_rows, now, acc_boost,
                           nbr_boost)
    packed = _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast, dup=n_dup,
                             acc=n_acc, nbr=n_nbr, sem=sem_col)
    if sem is None:
        return state, packed
    return state, ring2, packed


def _sem_finish_read(res, sem):
    """Read-twin tail twin of ``_sem_finish``: no boost scatter, but the
    ring writeback still lands (read fleets warm the cache too), so with
    the cache on the read twin returns ``(ring, packed)``."""
    if sem is None:
        core, sem_col, ring2 = res, None, None
    else:
        core, sem_col, ring2 = res[:-2], res[-2], res[-1]
    gate_s, gate_r, ann_s, ann_r, fast = core[:5]
    n_dup = core[7] if len(core) > 7 else None
    packed = _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast, dup=n_dup,
                             sem=sem_col)
    if sem is None:
        return packed
    return ring2, packed


search_fused_ragged, search_fused_ragged_copy = _donated_pair(
    _search_fused_ragged, static_argnames=("k", "cap_take", "max_nbr",
                                           "scan_chunk", "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "cap_take", "max_nbr",
                                             "scan_chunk", "sem_block"))
def search_fused_ragged_read(state: ArenaState, csr_indptr: jax.Array,
                             csr_nbr: jax.Array, requests: jax.Array,
                             k: int, cap_take: int, max_nbr: int,
                             scan_chunk: int = 0,
                             sem=None, sem_block: int = 16) -> jax.Array:
    """Read-only twin of ``search_fused_ragged`` for batches where NO query
    wants boosts (pure ``search_memories`` fleets): same compute, per-query
    k as data, no state mutation, so the ownership/donation dance is
    skipped entirely. With ``sem`` the semantic ring still rides (misses
    write back — read fleets warm the cache) and the return becomes
    ``(ring, packed)``."""
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_scan(
        state, csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on,
        boost_off, r.super_gate, k, cap_take, max_nbr, k_q=r.k_q,
        cap_q=cap_q, scan_chunk=scan_chunk, sem=sem, sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# Quantized fused serving (ISSUE 3): the same single-dispatch chat-turn
# program, but the whole-arena scan streams the int8 shadow (half the HBM
# bytes, int8×int8→int32 on the MXU) for a coarse top-(k+slack), then the
# few survivors are EXACTLY rescored from the master arena with a gathered-
# row dot before the gate / CSR gather / boost scatter run unchanged. This
# is the EdgeRAG two-stage idiom fused into one program: at 1M rows the
# coarse scan is the bandwidth floor and the rescore is O(Q·(k+slack)·d).
# ---------------------------------------------------------------------------


def _quant_two_tier(state: ArenaState, q8a: jax.Array, scale_a: jax.Array,
                    q_c: jax.Array, tenant_c: jax.Array, k: int, slack: int):
    """Two-stage quantized two-tier core: an int8 coarse scan over the shadow
    (``q8a`` codes + ``scale_a`` per-row scales, ops/quant.py layout) that
    SELECTS WHILE THE SHADOW STREAMS from HBM once (ISSUE 36;
    ``ops/pallas_topk.blocked_two_tier_q8``, the int8 twin of the exact
    family's core) for BOTH retrieval tiers — the super gate's coarse
    top-(1 + slack) and the main tier's coarse top-(k + slack), each query
    over its own tenant's rows — then an exact bf16/f32 rescore of exactly
    those survivors via a gathered-row dot: no ``[C, rows]`` score tile,
    nothing sorted at the arena's width. The slack absorbs the ~1e-2 int8
    ranking error at the k boundary (ISSUE 3 satellite: config-driven, shared
    with the IVF over-fetch): a true member ranked k+3 coarsely is kept.

    Shard-local by construction (the shadow row-shards like the master, and
    the rescore gather only touches local rows): single-chip callers pass
    the whole arena + shadow, the sharded program each chip's slices.
    Returns exact-scored ``(gate_s [C,1], gate_r [C,1], ann_s [C,k],
    ann_r [C,k])``; the super gate is threshold-sensitive (0.4), so its
    VERDICT uses the exact rescored score — quantization error can only
    cost a gate candidate ranked below coarse position 1+slack, never flip
    the threshold comparison itself."""
    from lazzaro_tpu.ops.pallas_topk import ROW_DEAD, blocked_two_tier_q8
    from lazzaro_tpu.ops.quant import quantize_rows

    n = _nrows(state)
    k_fetch = min(k + slack, n)
    g_fetch = min(1 + slack, n)
    with jax.named_scope("lz.norms"):
        qn = normalize(q_c)                               # [C, d] f32
        qq, qs = quantize_rows(qn)
    with jax.named_scope("lz.scan_q8"):
        # the shadow lies in LOGICAL row space, paged or not
        ten = state.tenant_id.astype(jnp.int32)
        row_main = jnp.where(state.alive & ~state.is_super, ten, ROW_DEAD)
        row_gate = jnp.where(state.alive & state.is_super, ten, ROW_DEAD)
    cg_s, cg_r, ca_s, ca_r = blocked_two_tier_q8(
        q8a, scale_a, qq, qs, row_main, row_gate, tenant_c, k_fetch, g_fetch)
    # Same consumer-split hazard as _exact_two_tier: the survivors feed the
    # rescore gather and (via it) the readback; XLA could run the scan twice.
    cg_s, cg_r, ca_s, ca_r = jax.lax.optimization_barrier(
        (cg_s, cg_r, ca_s, ca_r))

    def rescore(rows_c, coarse_s):
        g = state.emb[_phys(state, rows_c)]               # [C, kf, d]
        ex = jnp.einsum("cd,ckd->ck", qd, g,
                        preferred_element_type=jnp.float32)
        return jnp.where(coarse_s > NEG_INF / 2, ex, NEG_INF)

    with jax.named_scope("lz.rescore"):
        qd = qn.astype(state.emb.dtype)
        ann_ex = rescore(ca_r, ca_s)
        ann_s, sel = jax.lax.top_k(ann_ex, k)
        ann_r = jnp.take_along_axis(ca_r, sel, axis=1)
        gate_ex = rescore(cg_r, cg_s)
        g_s, g_sel = jax.lax.top_k(gate_ex, 1)
        g_r = jnp.take_along_axis(cg_r, g_sel, axis=1)
    return g_s, g_r, ann_s, ann_r


def _search_fused_quant_scan(state: ArenaState, q8a: jax.Array,
                             scale_a: jax.Array, csr_indptr: jax.Array,
                             csr_nbr: jax.Array, q: jax.Array,
                             q_valid: jax.Array, tenant: jax.Array,
                             gate_on: jax.Array, boost_on: jax.Array,
                             super_gate: jax.Array, k: int, slack: int,
                             cap_take: int, max_nbr: int,
                             k_q: jax.Array, cap_q: jax.Array,
                             scan_chunk: int = 0,
                             sem=None, sem_block: int = 16):
    """Quantized per-chunk compute phase: the int8 coarse-scan + exact
    rescore core, then the shared gate/CSR/boost tail. The coarse fetch
    and the exact rescore run to the static ceiling ``k``; the boundary
    mask is per-query data (``k_q``/``cap_q``, see ``_search_fused_scan``).
    ``scan_chunk`` is the planner's streaming-width override (ISSUE 11;
    bit-identical, smaller score tile)."""
    def chunk(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c):
        g_s, g_r, ann_s, ann_r = _quant_two_tier(state, q8a, scale_a, q_c,
                                                 tenant_c, k, slack)
        gate_s, gate_r = g_s[:, 0], g_r[:, 0]
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, k_c, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, gate_s, gate_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c, super_gate, cap_take,
            max_nbr, cap_c=cap_c)
        return gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q)
    if sem is None:
        return chunked_map_multi(chunk, arrays,
                                 chunk=(scan_chunk or QUERY_CHUNK))
    return _semantic_scan_core(chunk, arrays, state, sem, super_gate,
                               block=sem_block)


def _search_fused_quant_ragged(
    state: ArenaState,
    q8a: jax.Array,          # [cap+1, d] i8 serving shadow codes
    scale_a: jax.Array,      # [cap+1] f32 per-row scales
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """``search_fused_ragged`` with the int8 coarse scan + exact rescore
    stage: one donated dispatch + one packed readback per coalesced batch,
    int8 mode included; the coarse fetch and the rescore run to the k
    ceiling, the boundary is data. Only the arena state is donated — the
    shadow is a long-lived read-only replica (boost scatters touch
    salience/access/freshness, never the embeddings, so the codes stay
    valid)."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_quant_scan(state, q8a, scale_a, csr_indptr, csr_nbr,
                                   r.q, r.q_valid, r.tenant, r.gate_on,
                                   r.boost_on, r.super_gate, k, slack,
                                   cap_take, max_nbr, k_q=r.k_q,
                                   cap_q=r.cap_q, scan_chunk=scan_chunk,
                                   sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_quant_ragged, search_fused_quant_ragged_copy = _donated_pair(
    _search_fused_quant_ragged,
    static_argnames=("k", "slack", "cap_take", "max_nbr", "scan_chunk",
                     "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "slack", "cap_take",
                                             "max_nbr", "scan_chunk",
                                             "sem_block"))
def search_fused_quant_ragged_read(state: ArenaState, q8a: jax.Array,
                                   scale_a: jax.Array,
                                   csr_indptr: jax.Array,
                                   csr_nbr: jax.Array,
                                   requests: jax.Array, k: int,
                                   slack: int, cap_take: int,
                                   max_nbr: int, scan_chunk: int = 0,
                                   sem=None,
                                   sem_block: int = 16) -> jax.Array:
    """Read-only twin of ``search_fused_quant_ragged`` (pure
    ``search_memories`` fleets in int8 mode): same coarse-scan +
    exact-rescore compute, no state mutation, no donation dance."""
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_quant_scan(
        state, q8a, scale_a, csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant,
        r.gate_on, boost_off, r.super_gate, k, slack, cap_take, max_nbr,
        k_q=r.k_q, cap_q=cap_q, scan_chunk=scan_chunk, sem=sem,
        sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# Tiered memory (ISSUE 8): HBM hot set + host-resident cold tier.
#
# Residency is a per-row device column (``cold`` [cap+1] bool, owned by
# ``tier.TierManager``): a demoted row keeps its metadata columns (alive,
# tenant, salience — decay sweeps and masks keep working) AND its int8
# shadow codes, but surrenders its full-precision embedding to the host
# ``ColdStore`` (the arena row is zeroed by the donated ``tier_demote``
# scatter; the paged-arena follow-up reclaims the physical bytes). The int8
# shadow therefore stays the FULL-CORPUS scan structure — per cold row the
# chip holds d bytes of codes instead of d codes + 2d bytes of bf16 master,
# the TF-Engram/EdgeRAG shape.
#
# Serving: ``search_fused_tiered_ragged`` is the quantized fused chat-turn
# program with a tier-aware rescore — the int8 coarse scan covers the whole corpus,
# HOT survivors rescore exactly from the master in-kernel, COLD survivors
# keep their coarse score and raise a per-query cold flag (their exact rows
# live host-side). Hot-only turns therefore stay ONE dispatch + ONE packed
# readback with exact scores and in-kernel boosts; a turn whose candidate
# set touches cold rows defers its boosts (same suppression slot as the
# gate fast path) and pays ONE bounded second dispatch
# (``tier_cold_finish``): exact rescore of the host-gathered cold vectors,
# final re-rank over the SAME k+slack candidate set, and the deferred
# gate/CSR/boost tail — never a full-arena fault-in.
# ---------------------------------------------------------------------------


def _tier_demote(state: ArenaState, rows: jax.Array) -> ArenaState:
    """Surrender the full-precision embeddings of ``rows`` (the host cold
    store holds the exact bytes; metadata columns and the int8 shadow stay).
    Sentinel-padded rows zero the scratch row, which is never scored."""
    zeros = jnp.zeros((rows.shape[0], state.emb.shape[1]), state.emb.dtype)
    return state.replace(emb=state.emb.at[rows].set(zeros))


tier_demote, tier_demote_copy = _donated_pair(_tier_demote)


def _tier_promote(state: ArenaState, rows: jax.Array,
                  vecs: jax.Array) -> ArenaState:
    """Restore promoted rows' exact embeddings (``vecs`` carries the cold
    store's bytes in the arena dtype — the round trip is bit-exact, so the
    int8 shadow codes stay valid without a requantize)."""
    return state.replace(emb=state.emb.at[rows].set(
        vecs.astype(state.emb.dtype)))


tier_promote, tier_promote_copy = _donated_pair(_tier_promote)


def _tier_demote_paged(state: ArenaState, ptable: PageTable,
                       rows: jax.Array
                       ) -> Tuple[ArenaState, PageTable, jax.Array]:
    """Paged demote: surrender the rows' pool slots back to the free
    stack (``_page_free`` zeroes the slots — the paged analogue of the
    dense zero-scatter, except the bytes become REUSABLE capacity instead
    of dead zeros). Emptied pages are real reclaimed HBM the next grow
    never has to allocate."""
    return _page_free(state, ptable, rows)


tier_demote_paged, tier_demote_paged_copy = _donated_pair(
    _tier_demote_paged, donate=(0, 1))


def _tier_promote_paged(state: ArenaState, ptable: PageTable,
                        rows: jax.Array, vecs: jax.Array
                        ) -> Tuple[ArenaState, PageTable, jax.Array]:
    """Paged promote: re-bind pool slots (prefix-sum pop; the host
    pre-checks its mirror so the stack never runs dry mid-dispatch) and
    scatter the cold store's exact bytes at the fresh physical rows."""
    valid = rows < state.capacity
    state, ptable, pops, _ = _page_alloc(state, ptable, rows, valid)
    state = state.replace(emb=state.emb.at[_phys(state, rows)].set(
        vecs.astype(state.emb.dtype)))
    return state, ptable, pops


tier_promote_paged, tier_promote_paged_copy = _donated_pair(
    _tier_promote_paged, donate=(0, 1))


def _arena_delete_paged(state: ArenaState, ptable: PageTable,
                        rows: jax.Array
                        ) -> Tuple[ArenaState, PageTable, jax.Array]:
    """Delete + free: the dense ``_arena_delete`` column scrub plus the
    pool-slot push — deleted rows' HBM is immediately reusable."""
    state = _arena_delete(state, rows)
    return _page_free(state, ptable, rows)


arena_delete_paged, arena_delete_paged_copy = _donated_pair(
    _arena_delete_paged, donate=(0, 1))


def _arena_add_paged(state: ArenaState, ptable: PageTable, rows: jax.Array,
                     emb: jax.Array, salience: jax.Array,
                     timestamp: jax.Array, type_id: jax.Array,
                     shard_id: jax.Array, tenant_id: jax.Array,
                     is_super: jax.Array
                     ) -> Tuple[ArenaState, PageTable, jax.Array]:
    """Direct (non-fused) paged add: bind slots, then the usual column
    scatters with the emb write routed through ``row_map``."""
    valid = rows < state.capacity
    state, ptable, pops, _ = _page_alloc(state, ptable, rows, valid)
    state = _arena_add(state, rows, emb, salience, timestamp, type_id,
                       shard_id, tenant_id, is_super)
    return state, ptable, pops


arena_add_paged, arena_add_paged_copy = _donated_pair(
    _arena_add_paged, donate=(0, 1))


def _tiered_two_tier(state: ArenaState, q8a: jax.Array, scale_a: jax.Array,
                     cold: jax.Array, q_c: jax.Array, tenant_c: jax.Array,
                     k: int, slack: int):
    """Tier-aware two-stage core: int8 coarse scan over the full-corpus
    shadow (both retrieval tiers, same masks as ``_quant_two_tier``), then
    a residency-split rescore — hot survivors exact from the master, cold
    survivors keep the coarse score (their exact rows are host-resident).
    Returns the candidates K+SLACK WIDE sorted by the blended score, so a
    caller whose query touched cold rows can finish (exact cold rescore +
    final re-rank) over the SAME candidate set without re-running the
    scan, plus the per-query cold flag. Super rows are pinned hot by the
    tiering policy, so the gate verdict is always exact."""
    n = _nrows(state)
    k_fetch = min(k + slack, n)
    g_fetch = min(1 + slack, n)
    qn = normalize(q_c)                                   # [C, d] f32
    from lazzaro_tpu.ops.quant import quantize_rows

    qq, qs = quantize_rows(qn)
    dots = jax.lax.dot_general(
        qq, q8a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                 # [C, rows] i32
    coarse = (dots.astype(jnp.float32)
              * qs[:, None] * scale_a[None, :])
    alive_t = state.alive[None, :] & (
        state.tenant_id[None, :] == tenant_c[:, None])
    sup = state.is_super[None, :]
    cg_s, cg_r = jax.lax.top_k(
        jnp.where(alive_t & sup, coarse, NEG_INF), g_fetch)
    ca_s, ca_r = jax.lax.top_k(
        jnp.where(alive_t & ~sup, coarse, NEG_INF), k_fetch)
    # consumer-split hazard, same as _quant_two_tier
    cg_s, cg_r, ca_s, ca_r = jax.lax.optimization_barrier(
        (cg_s, cg_r, ca_s, ca_r))
    qd = qn.astype(state.emb.dtype)

    def rescore(rows_c, coarse_s):
        # cold rows are UNBOUND under paging: _phys routes them to the
        # all-zero pool sentinel, so their exact rescore is 0 — exactly
        # the dense demote-zeroed read (the blend keeps coarse either way)
        g = state.emb[_phys(state, rows_c)]               # [C, kf, d]
        ex = jnp.einsum("cd,ckd->ck", qd, g,
                        preferred_element_type=jnp.float32)
        return jnp.where(coarse_s > NEG_INF / 2, ex, NEG_INF)

    ann_ex = rescore(ca_r, ca_s)
    live = ca_s > NEG_INF / 2
    is_cold = cold[ca_r] & live
    # cold candidates carry their COARSE score into the ranking (their
    # exact row is host-side); hot candidates are already exact
    blend = jnp.where(is_cold, ca_s, ann_ex)
    ann_s, sel = jax.lax.top_k(blend, k_fetch)            # full sort
    ann_r = jnp.take_along_axis(ca_r, sel, axis=1)
    cold_any = jnp.take_along_axis(is_cold, sel, axis=1).any(axis=-1)
    gate_ex = rescore(cg_r, cg_s)
    g_s, g_sel = jax.lax.top_k(gate_ex, 1)
    g_r = jnp.take_along_axis(cg_r, g_sel, axis=1)
    return g_s, g_r, ann_s, ann_r, cold_any


def _search_fused_tiered_scan(state: ArenaState, q8a: jax.Array,
                              scale_a: jax.Array, cold: jax.Array,
                              csr_indptr: jax.Array, csr_nbr: jax.Array,
                              q: jax.Array, q_valid: jax.Array,
                              tenant: jax.Array, gate_on: jax.Array,
                              boost_on: jax.Array, super_gate: jax.Array,
                              k: int, slack: int, cap_take: int,
                              max_nbr: int, k_q: jax.Array,
                              cap_q: jax.Array, scan_chunk: int = 0,
                              sem=None, sem_block: int = 16):
    """Tiered per-chunk compute phase: the tier-aware two-stage core, then
    the shared gate/CSR/boost tail with cold-hit queries' boosts DEFERRED
    (suppressed exactly like the gate fast path — the host applies them in
    the bounded ``tier_cold_finish`` dispatch after the exact re-rank, so
    boost rows always follow the FINAL ranking). The per-query boundary
    masks at k_i + slack so the host keeps each query's full candidate
    window for the finish."""
    def chunk(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c):
        g_s, g_r, ann_s, ann_r, cold_any = _tiered_two_tier(
            state, q8a, scale_a, cold, q_c, tenant_c, k, slack)
        gate_s, gate_r = g_s[:, 0], g_r[:, 0]
        kf = jnp.minimum(k_c + slack, ann_s.shape[1])
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, kf, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, gate_s, gate_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c & ~cold_any, super_gate,
            cap_take, max_nbr, cap_c=cap_c)
        return gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q)
    if sem is None:
        return chunked_map_multi(chunk, arrays,
                                 chunk=(scan_chunk or QUERY_CHUNK))
    # the tiered candidate window is k+slack wide and the per-query boundary
    # masks at k_i + slack — the substitution must re-mask the same way
    return _semantic_scan_core(chunk, arrays, state, sem, super_gate,
                               block=sem_block, rag_slack=slack)


def _search_fused_tiered_ragged(
    state: ArenaState,
    q8a: jax.Array,          # [cap+1, d] i8 FULL-corpus shadow codes
    scale_a: jax.Array,      # [cap+1] f32
    cold: jax.Array,         # [cap+1] bool residency column (True = cold)
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """``search_fused_quant_ragged`` with the residency column threaded
    through: ONE donated dispatch + ONE packed readback whose candidate
    block is k+slack wide, each query's window masked at its own k_i +
    slack boundary. Hot-only queries boost in-kernel; cold-hit queries come
    back unboosted with their candidate window for the finish dispatch."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_tiered_scan(state, q8a, scale_a, cold, csr_indptr,
                                    csr_nbr, r.q, r.q_valid, r.tenant,
                                    r.gate_on, r.boost_on, r.super_gate, k,
                                    slack, cap_take, max_nbr, k_q=r.k_q,
                                    cap_q=r.cap_q, scan_chunk=scan_chunk,
                                    sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_tiered_ragged, search_fused_tiered_ragged_copy = _donated_pair(
    _search_fused_tiered_ragged,
    static_argnames=("k", "slack", "cap_take", "max_nbr", "scan_chunk",
                     "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "slack", "cap_take",
                                             "max_nbr", "scan_chunk",
                                             "sem_block"))
def search_fused_tiered_ragged_read(state: ArenaState, q8a: jax.Array,
                                    scale_a: jax.Array, cold: jax.Array,
                                    csr_indptr: jax.Array,
                                    csr_nbr: jax.Array,
                                    requests: jax.Array, k: int,
                                    slack: int, cap_take: int,
                                    max_nbr: int,
                                    scan_chunk: int = 0, sem=None,
                                    sem_block: int = 16) -> jax.Array:
    """Read-only tiered twin (pure ``search_memories`` fleets)."""
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_tiered_scan(
        state, q8a, scale_a, cold, csr_indptr, csr_nbr, r.q, r.q_valid,
        r.tenant, r.gate_on, boost_off, r.super_gate, k, slack, cap_take,
        max_nbr, k_q=r.k_q, cap_q=cap_q, scan_chunk=scan_chunk, sem=sem,
        sem_block=sem_block)
    return _sem_finish_read(res, sem)


def _cold_rerank(q: jax.Array, cand_rows: jax.Array, cand_s: jax.Array,
                 cold_m: jax.Array, cold_vecs: jax.Array, k: int,
                 sentinel: int):
    """Exact re-rank of a tiered candidate window: cold positions rescore
    against the host-gathered exact vectors (same einsum shape as the
    in-kernel hot rescore, so scores are bit-identical to an all-hot
    serve), hot positions keep their already-exact scores; final top-k.
    ``cold_vecs`` carries zeros at hot positions — their lanes are
    discarded by the ``where``."""
    qd = normalize(q).astype(cold_vecs.dtype)
    ex = jnp.einsum("cd,ckd->ck", qd, cold_vecs,
                    preferred_element_type=jnp.float32)
    live = cand_s > NEG_INF / 2
    scores = jnp.where(cold_m & live, ex,
                       jnp.where(live, cand_s, NEG_INF))
    ann_s, sel = jax.lax.top_k(scores, k)
    rows_safe = jnp.where(live, cand_rows, sentinel)
    ann_r = jnp.take_along_axis(rows_safe, sel, axis=1)
    ann_r = jnp.where(ann_s > NEG_INF / 2, ann_r, sentinel)
    return jax.lax.optimization_barrier((ann_s, ann_r))


@functools.partial(jax.jit, static_argnames=("k", "sentinel"))
def tier_cold_rescore(q: jax.Array, cand_rows: jax.Array,
                      cand_s: jax.Array, cold_m: jax.Array,
                      cold_vecs: jax.Array, gate_s: jax.Array,
                      gate_r: jax.Array, fast: jax.Array, k: int,
                      sentinel: int) -> jax.Array:
    """Read-only cold finish: exact re-rank of the candidate windows, no
    state mutation (pure ``search_memories`` fleets, and the pod path's
    result finish). Gate results pass through from the first dispatch —
    super rows are pinned hot, so they were exact already."""
    ann_s, ann_r = _cold_rerank(q, cand_rows, cand_s, cold_m, cold_vecs, k,
                                int(sentinel))
    return _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast)


def _tier_cold_finish(
    state: ArenaState,
    csr_indptr: jax.Array,   # FLAT global CSR (single-chip layout)
    csr_nbr: jax.Array,
    q: jax.Array,            # [C2, d] the cold-hit queries
    tenant: jax.Array,       # [C2] i32
    cand_rows: jax.Array,    # [C2, KF] candidate window from dispatch 1
    cand_s: jax.Array,       # [C2, KF] blended scores (exact where hot)
    cold_m: jax.Array,       # [C2, KF] bool cold positions
    cold_vecs: jax.Array,    # [C2, KF, d] host-gathered exact rows
    gate_s: jax.Array,       # [C2] gate passthrough from dispatch 1
    gate_r: jax.Array,       # [C2] i32
    fast: jax.Array,         # [C2] bool device gate verdicts
    boost_on: jax.Array,     # [C2] bool
    cap_q: jax.Array,        # [C2] i32 per-query retrieval cap
    now: jax.Array,
    acc_boost: jax.Array,
    nbr_boost: jax.Array,
    k: int,
    cap_take: int,
    max_nbr: int,
) -> Tuple[ArenaState, jax.Array]:
    """The bounded second dispatch of a cold-hit turn: exact rescore of
    the host-gathered cold rows, final re-rank over the SAME k+slack
    candidate window dispatch 1 scanned, then the deferred gate/CSR/boost
    tail — ``_csr_neighbor_rows`` + ``_boost_scatter``, the same code the
    all-hot kernels run, so boost semantics are identical, just applied
    after the final ranking. O(C2 · (k+slack) · d): never a full-arena
    scan, never a fault-in."""
    cap = state.capacity
    ann_s, ann_r = _cold_rerank(q, cand_rows, cand_s, cold_m, cold_vecs, k,
                                cap)
    take = ((ann_s[:, :cap_take] > NEG_INF / 2)
            & boost_on[:, None] & ~fast[:, None]
            & (jnp.arange(cap_take)[None, :] < cap_q[:, None]))
    acc_rows = jnp.where(take, ann_r[:, :cap_take], cap)
    nbr_rows = _csr_neighbor_rows(state, csr_indptr, csr_nbr, acc_rows,
                                  tenant, max_nbr)
    n_acc, n_nbr = _boost_row_counts(cap, acc_rows, nbr_rows)
    state = _boost_scatter(state, acc_rows, nbr_rows, now, acc_boost,
                           nbr_boost)
    return state, _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast,
                                  acc=n_acc, nbr=n_nbr)


tier_cold_finish, tier_cold_finish_copy = _donated_pair(
    _tier_cold_finish, static_argnames=("k", "cap_take", "max_nbr"))


# ---------------------------------------------------------------------------
# Fused IVF serving (ISSUE 4): the same single-dispatch chat-turn program,
# but the coarse stage is the CENTROID prefilter — the query batch scores
# C ≈ √N centroids, visits the top-nprobe clusters, gathers ONLY those
# clusters' member rows (plus the exact-scan extras: sealed+fresh residual
# and the super rows), and scores just the candidates before the existing
# super-gate / CSR-gather / boost-scatter tail runs unchanged. Candidate
# HBM traffic per query drops from N·d to ~(C + nprobe·N/C)·d (~25×
# analytically at 1M rows) while keeping the ONE-dispatch + ONE-readback
# invariant the dense and int8 paths already guarantee. With the int8
# shadow on, the candidate scan itself becomes two-stage (int8 gathered
# coarse + exact f32 rescore of the k+slack survivors) — PR 3's machinery
# applied to the gathered rows instead of the whole arena.
# ---------------------------------------------------------------------------

# Candidate tensors are [q_chunk, nprobe·M + E, d]; small chunks bound the
# gather footprint the same way ops/ivf.ivf_search's q_chunk does.
IVF_SERVE_CHUNK = 8


def _dedup_topk(scores: jax.Array, rows: jax.Array, sentinel: int, k: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k over a small over-fetched candidate list keeping only the
    FIRST occurrence of each arena row. IVF candidate lists can carry
    duplicates — a reused slot sitting in both a stale member slot and the
    residual, or a super row in both its cluster and the extras — and a
    duplicate must neither consume a result slot (k-shortfall) nor get a
    double access boost (the classic path dedups host-side in
    ``decode_topk``). ``scores`` is sorted descending (a top-k output), so
    keeping the first occurrence keeps the best. Invalid entries are
    routed to the sentinel row with NEG_INF intact. Also returns the
    per-query count of live duplicates suppressed — the device-side
    'dedup hits' counter riding the packed readback (ISSUE 6)."""
    r = jnp.where(scores > NEG_INF / 2, rows, sentinel)
    m = r.shape[1]
    dup = ((r[:, :, None] == r[:, None, :])
           & jnp.tri(m, k=-1, dtype=bool)[None, :, :]).any(-1)
    n_dup = (dup & (r != sentinel)).sum(axis=-1).astype(jnp.int32)
    s = jnp.where(dup, NEG_INF, scores)
    top_s, sel = jax.lax.top_k(s, k)
    top_r = jnp.take_along_axis(r, sel, axis=1)
    return top_s, jnp.where(top_s > NEG_INF / 2, top_r, sentinel), n_dup


def _ivf_two_tier(state: ArenaState, shadow, centroids: jax.Array,
                  members: jax.Array, extras: jax.Array, q_c: jax.Array,
                  tenant_c: jax.Array, k: int, nprobe: int, slack: int,
                  nprobe_c=None):
    """IVF two-tier core: coarse centroid prefilter + member gather
    (``ops.ivf.gather_rows`` — the same candidate assembly as the classic
    IVF scan, barrier included), per-query tenant masking over the
    candidates, candidate scoring (exact bf16/f32, or int8-gathered coarse
    + exact rescore when ``shadow`` is present), and duplicate-row dedup
    at the top-k boundary. Both retrieval tiers are masks over the ONE
    candidate score matrix, same trick as the dense scans.

    Shard-local by construction when given per-shard tables whose member/
    extras entries are LOCAL row indices (``ops.ivf.shard_serve_tables``):
    the gathers then only touch the chip's own arena slice. Returns
    ``(gate_s [C], gate_r [C], ann_s [C,k], ann_r [C,k], n_dup [C])``
    with rows routed to the sentinel (``state.capacity``) where invalid;
    ``n_dup`` counts the duplicates the in-kernel dedup dropped.

    ``nprobe_c`` (optional [C] i32) makes the probe width RAGGED: the
    gather still visits the static ceiling ``nprobe`` clusters (the
    candidate tensor shape is a trace constant), but a query's candidates
    from clusters ranked at or past its own nprobe are masked invalid —
    per-query recall/latency trade as device data, one compiled kernel.
    The gather layout is cluster-rank-major (``gather_rows``), so the
    rank of a member candidate is just its position divided by the
    member-table width; extras stay valid at every probe width."""
    from lazzaro_tpu.ops.ivf import gather_rows

    cap = state.capacity
    L = nprobe * members.shape[1] + extras.shape[0]
    k_fetch = min(k + slack, L)
    g_fetch = min(1 + slack, L)
    qn = normalize(q_c)                               # [C, d] f32
    cand, safe = gather_rows(centroids, members, extras, qn, nprobe)
    valid = ((cand >= 0) & state.alive[safe]
             & (state.tenant_id[safe] == tenant_c[:, None]))
    if nprobe_c is not None:
        m_w = members.shape[1]
        pos = jnp.arange(L)
        in_members = pos < nprobe * m_w
        rank = pos // max(m_w, 1)
        valid = valid & (~in_members[None, :]
                         | (rank[None, :] < nprobe_c[:, None]))
    sup = state.is_super[safe]
    qd = qn.astype(state.emb.dtype)

    def rescore(rows_c, coarse_s):
        g = state.emb[_phys(state, rows_c)]           # [C, kf, d]
        ex = jnp.einsum("cd,ckd->ck", qd, g,
                        preferred_element_type=jnp.float32)
        return jnp.where(coarse_s > NEG_INF / 2, ex, NEG_INF)

    if shadow is None:
        vecs = state.emb[_phys(state, safe)]          # [C, L, d]
        sc = jnp.einsum("cd,cld->cl", qd, vecs,
                        preferred_element_type=jnp.float32)
        a_s0, a_pos = jax.lax.top_k(
            jnp.where(valid & ~sup, sc, NEG_INF), k_fetch)
        g_s0, g_pos = jax.lax.top_k(
            jnp.where(valid & sup, sc, NEG_INF), 1)
        # Consumer-split hazard (see _exact_two_tier): the top-k feeds
        # both the packed readback and the boost gather chain.
        a_s0, a_pos, g_s0, g_pos = jax.lax.optimization_barrier(
            (a_s0, a_pos, g_s0, g_pos))
        ann_ex = a_s0
        a_rows = jnp.take_along_axis(cand, a_pos, axis=1)
        gate_s = g_s0[:, 0]
        gate_r0 = jnp.take_along_axis(cand, g_pos, axis=1)[:, 0]
    else:
        from lazzaro_tpu.ops.quant import quantize_rows

        q8a, scale_a = shadow
        qq, qs = quantize_rows(qn)
        d8 = jnp.einsum("cd,cld->cl", qq, q8a[safe],
                        preferred_element_type=jnp.int32)
        coarse = (d8.astype(jnp.float32)
                  * qs[:, None] * scale_a[safe])      # [C, L]
        a_s0, a_pos = jax.lax.top_k(
            jnp.where(valid & ~sup, coarse, NEG_INF), k_fetch)
        g_s0, g_pos = jax.lax.top_k(
            jnp.where(valid & sup, coarse, NEG_INF), g_fetch)
        a_s0, a_pos, g_s0, g_pos = jax.lax.optimization_barrier(
            (a_s0, a_pos, g_s0, g_pos))
        # exact rescore of the few survivors from the master — scores
        # and the 0.4 gate verdict never see quantization error
        a_rows0 = jnp.take_along_axis(cand, a_pos, axis=1)
        a_rows_safe = jnp.where(a_s0 > NEG_INF / 2, a_rows0, cap)
        ann_ex = rescore(a_rows_safe, a_s0)
        g_rows0 = jnp.take_along_axis(cand, g_pos, axis=1)
        g_rows_safe = jnp.where(g_s0 > NEG_INF / 2, g_rows0, cap)
        gate_ex = rescore(g_rows_safe, g_s0)
        g_s, g_sel = jax.lax.top_k(gate_ex, 1)
        gate_s = g_s[:, 0]
        gate_r0 = jnp.take_along_axis(g_rows_safe, g_sel, axis=1)[:, 0]
        a_rows = a_rows_safe

    ann_s, ann_r, n_dup = _dedup_topk(ann_ex, a_rows, cap, k)
    gate_r = jnp.where(gate_s > NEG_INF / 2, gate_r0, cap)
    return gate_s, gate_r, ann_s, ann_r, n_dup


def _search_fused_ivf_scan(state: ArenaState, shadow, centroids: jax.Array,
                           members: jax.Array, extras: jax.Array,
                           csr_indptr: jax.Array, csr_nbr: jax.Array,
                           q: jax.Array, q_valid: jax.Array,
                           tenant: jax.Array, gate_on: jax.Array,
                           boost_on: jax.Array, super_gate: jax.Array,
                           k: int, nprobe: int, slack: int, cap_take: int,
                           max_nbr: int, k_q: jax.Array,
                           cap_q: jax.Array, nprobe_q: jax.Array,
                           scan_chunk: int = 0,
                           sem=None, sem_block: int = 16):
    """IVF per-chunk compute phase: the coarse-prefilter two-tier core,
    then the shared gate/CSR/boost tail. The gather and candidate scan run
    to the static ceilings; each query masks at its own k / cap /
    probe-width boundary (``k_q``/``cap_q``/``nprobe_q``, see
    ``_search_fused_scan`` / ``_ivf_two_tier``)."""
    def body(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c, nprobe_c):
        gate_s, gate_r, ann_s, ann_r, n_dup = _ivf_two_tier(
            state, shadow, centroids, members, extras, q_c, tenant_c, k,
            nprobe, slack, nprobe_c=nprobe_c)
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, k_c, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, gate_s, gate_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c, super_gate, cap_take,
            max_nbr, cap_c=cap_c)
        return (gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows,
                n_dup)

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q, nprobe_q)
    if sem is None:
        return chunked_map_multi(body, arrays,
                                 chunk=min(scan_chunk or IVF_SERVE_CHUNK,
                                           IVF_SERVE_CHUNK))
    return _semantic_scan_core(body, arrays, state, sem, super_gate,
                               block=sem_block)


def _search_fused_ivf_ragged(
    state: ArenaState,
    shadow,                  # (q8 [cap+1, d] i8, scale [cap+1] f32) or None
    centroids: jax.Array,    # [C, d] f32 L2-normalized (ops/ivf.py build)
    members: jax.Array,      # [C, M] i32 arena rows, -1 padded
    extras: jax.Array,       # [E] i32 residual + fresh + super rows, -1 pad
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    nprobe: int,             # STATIC probe ceiling (the build's width)
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """``search_fused_ragged`` with the IVF centroid prefilter + member
    gather as the coarse stage: ONE donated dispatch + ONE packed readback
    per coalesced batch in IVF mode. The member gather visits the ceiling
    probe width, each query masks candidates past its own — recall/latency
    per request, one kernel. Only the arena state is donated — the
    centroid/member/extras tables and the optional int8 shadow are
    long-lived read-only replicas (the boost scatter touches salience/
    access/freshness, never embeddings or routing)."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_ivf_scan(state, shadow, centroids, members, extras,
                                 csr_indptr, csr_nbr, r.q, r.q_valid,
                                 r.tenant, r.gate_on, r.boost_on,
                                 r.super_gate, k, nprobe, slack, cap_take,
                                 max_nbr, k_q=r.k_q, cap_q=r.cap_q,
                                 nprobe_q=r.nprobe_q, scan_chunk=scan_chunk,
                                 sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_ivf_ragged, search_fused_ivf_ragged_copy = _donated_pair(
    _search_fused_ivf_ragged,
    static_argnames=("k", "nprobe", "slack", "cap_take", "max_nbr",
                     "scan_chunk", "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "slack",
                                             "cap_take", "max_nbr",
                                             "scan_chunk", "sem_block"))
def search_fused_ivf_ragged_read(state: ArenaState, shadow,
                                 centroids: jax.Array, members: jax.Array,
                                 extras: jax.Array, csr_indptr: jax.Array,
                                 csr_nbr: jax.Array, requests: jax.Array,
                                 k: int, nprobe: int,
                                 slack: int, cap_take: int, max_nbr: int,
                                 scan_chunk: int = 0, sem=None,
                                 sem_block: int = 16) -> jax.Array:
    """Read-only twin of ``search_fused_ivf_ragged`` (pure
    ``search_memories`` fleets in IVF mode): same coarse prefilter +
    candidate scan, no state mutation, no donation dance."""
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_ivf_scan(
        state, shadow, centroids, members, extras, csr_indptr, csr_nbr,
        r.q, r.q_valid, r.tenant, r.gate_on, boost_off, r.super_gate, k,
        nprobe, slack, cap_take, max_nbr, k_q=r.k_q, cap_q=cap_q,
        nprobe_q=r.nprobe_q, scan_chunk=scan_chunk, sem=sem,
        sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# IVF × tiering (ISSUE 12): the coarse stage when BOTH a published IVF build
# and demoted rows exist — the dense-scan fallback PR 8 shipped with is gone.
# Hot candidates come from the IVF member gather (exact in-kernel rescore
# from the master, whose hot rows are intact), COLD rows come from the
# full-corpus int8 shadow restricted to the cold residency mask (demoted
# rows drop out of the member tables on demotion, and their master row is
# zeroed, so the shadow coarse path is the one structure that still covers
# them). The two candidate streams merge at the k+slack boundary with the
# same in-kernel row dedup as the IVF kernel, cold survivors keep their
# coarse score and ride the EXISTING bounded tier_cold_finish dispatch —
# the packed readback is layout-identical to the tiered kernels, so the
# host finish path is unchanged.
# ---------------------------------------------------------------------------


def _ivf_tiered_two_tier(state: ArenaState, q8a: jax.Array,
                         scale_a: jax.Array, cold: jax.Array,
                         centroids: jax.Array, members: jax.Array,
                         extras: jax.Array, q_c: jax.Array,
                         tenant_c: jax.Array, k: int, nprobe: int,
                         slack: int, nprobe_c=None):
    """Tier-aware IVF core: centroid prefilter + member gather for the hot
    tier (exact master rescore — members hold hot rows only; a cold row
    that slipped a member scrub is masked by the residency column, never
    exactly rescored against its zeroed master row), int8 coarse scan over
    the COLD rows only, blended top-(k+slack) with row dedup. The gate
    tier stays IVF-gathered (supers are pinned hot and every super row
    rides the extras). Returns ``(g_s, g_r, ann_s [C, k+slack], ann_r,
    n_dup, cold_any)`` — the tiered candidate-window contract."""
    from lazzaro_tpu.ops.ivf import gather_rows
    from lazzaro_tpu.ops.quant import quantize_rows

    cap = state.capacity
    n = _nrows(state)
    L = nprobe * members.shape[1] + extras.shape[0]
    k_fetch = min(k + slack, L + n)
    k_hot = min(k + slack, L)
    k_cold = min(k + slack, n)
    qn = normalize(q_c)                                   # [C, d] f32
    qd = qn.astype(state.emb.dtype)
    cand, safe = gather_rows(centroids, members, extras, qn, nprobe)
    valid = ((cand >= 0) & state.alive[safe] & ~cold[safe]
             & (state.tenant_id[safe] == tenant_c[:, None]))
    if nprobe_c is not None:
        m_w = members.shape[1]
        pos = jnp.arange(L)
        in_members = pos < nprobe * m_w
        rank = pos // max(m_w, 1)
        valid = valid & (~in_members[None, :]
                         | (rank[None, :] < nprobe_c[:, None]))
    sup = state.is_super[safe]
    vecs = state.emb[_phys(state, safe)]                  # [C, L, d]
    sc = jnp.einsum("cd,cld->cl", qd, vecs,
                    preferred_element_type=jnp.float32)
    h_s, h_pos = jax.lax.top_k(jnp.where(valid & ~sup, sc, NEG_INF), k_hot)
    g_s0, g_pos = jax.lax.top_k(jnp.where(valid & sup, sc, NEG_INF), 1)
    # cold tier: int8 coarse over the residency-masked full-corpus shadow
    qq, qs = quantize_rows(qn)
    dots = jax.lax.dot_general(
        qq, q8a, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                 # [C, rows]
    coarse = dots.astype(jnp.float32) * qs[:, None] * scale_a[None, :]
    cold_m = (cold[None, :] & state.alive[None, :]
              & ~state.is_super[None, :]
              & (state.tenant_id[None, :] == tenant_c[:, None]))
    c_s, c_r = jax.lax.top_k(jnp.where(cold_m, coarse, NEG_INF), k_cold)
    h_s, h_pos, g_s0, g_pos, c_s, c_r = jax.lax.optimization_barrier(
        (h_s, h_pos, g_s0, g_pos, c_s, c_r))
    h_rows = jnp.take_along_axis(cand, h_pos, axis=1)
    # blended window: hot exact ++ cold coarse, one more top-k + dedup
    all_s = jnp.concatenate([h_s, c_s], axis=1)
    all_r = jnp.concatenate([h_rows, c_r], axis=1)
    ann_s, ann_r, n_dup = _dedup_topk(all_s, all_r, cap, k_fetch)
    is_cold = cold[jnp.minimum(ann_r, n - 1)] & (ann_s > NEG_INF / 2)
    cold_any = is_cold.any(axis=-1)
    gate_s = g_s0[:, 0]
    gate_r0 = jnp.take_along_axis(cand, g_pos, axis=1)[:, 0]
    gate_r = jnp.where(gate_s > NEG_INF / 2, gate_r0, cap)
    return gate_s, gate_r, ann_s, ann_r, n_dup, cold_any


def _search_fused_ivf_tiered_scan(state: ArenaState, q8a: jax.Array,
                                  scale_a: jax.Array, cold: jax.Array,
                                  centroids: jax.Array, members: jax.Array,
                                  extras: jax.Array, csr_indptr: jax.Array,
                                  csr_nbr: jax.Array, q: jax.Array,
                                  q_valid: jax.Array, tenant: jax.Array,
                                  gate_on: jax.Array, boost_on: jax.Array,
                                  super_gate: jax.Array, k: int,
                                  nprobe: int, slack: int, cap_take: int,
                                  max_nbr: int, k_q: jax.Array,
                                  cap_q: jax.Array, nprobe_q: jax.Array,
                                  scan_chunk: int = 0,
                                  sem=None, sem_block: int = 16):
    """IVF×tiered per-chunk compute: the tier-aware IVF core, then the
    shared gate/CSR/boost tail with cold-hit queries' boosts deferred to
    the bounded finish dispatch — exactly the tiered scan's contract, so
    ``tier.serve.tiered_decode_and_finish`` decodes this readback
    unchanged."""
    def chunk(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c, np_c):
        g_s, g_r, ann_s, ann_r, n_dup, cold_any = _ivf_tiered_two_tier(
            state, q8a, scale_a, cold, centroids, members, extras, q_c,
            tenant_c, k, nprobe, slack, nprobe_c=np_c)
        kf = jnp.minimum(k_c + slack, ann_s.shape[1])
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, kf, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, g_s, g_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c & ~cold_any, super_gate,
            cap_take, max_nbr, cap_c=cap_c)
        return g_s, g_r, ann_s, ann_r, fast, acc_rows, nbr_rows, n_dup

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q, nprobe_q)
    if sem is None:
        return chunked_map_multi(chunk, arrays,
                                 chunk=(scan_chunk or IVF_SERVE_CHUNK))
    return _semantic_scan_core(chunk, arrays, state, sem, super_gate,
                               block=sem_block, rag_slack=slack)


def _search_fused_ivf_tiered_ragged(
    state: ArenaState,
    q8a: jax.Array,
    scale_a: jax.Array,
    cold: jax.Array,
    centroids: jax.Array,
    members: jax.Array,
    extras: jax.Array,
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    nprobe: int,
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """ONE donated dispatch + ONE packed readback: IVF coarse stage for the
    hot tier, cold-masked int8 coarse for the demoted rows, tiered
    candidate window (k+slack wide) for the bounded finish."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_ivf_tiered_scan(
        state, q8a, scale_a, cold, centroids, members, extras,
        csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on,
        r.boost_on, r.super_gate, k, nprobe, slack, cap_take, max_nbr,
        k_q=r.k_q, cap_q=r.cap_q, nprobe_q=r.nprobe_q,
        scan_chunk=scan_chunk, sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_ivf_tiered_ragged, search_fused_ivf_tiered_ragged_copy = \
    _donated_pair(_search_fused_ivf_tiered_ragged,
                  static_argnames=("k", "nprobe", "slack", "cap_take",
                                   "max_nbr", "scan_chunk", "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "slack",
                                             "cap_take", "max_nbr",
                                             "scan_chunk", "sem_block"))
def search_fused_ivf_tiered_ragged_read(
        state: ArenaState, q8a: jax.Array, scale_a: jax.Array,
        cold: jax.Array, centroids: jax.Array, members: jax.Array,
        extras: jax.Array, csr_indptr: jax.Array, csr_nbr: jax.Array,
        requests: jax.Array, k: int, nprobe: int, slack: int,
        cap_take: int, max_nbr: int, scan_chunk: int = 0,
        sem=None, sem_block: int = 16) -> jax.Array:
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_ivf_tiered_scan(
        state, q8a, scale_a, cold, centroids, members, extras,
        csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on,
        boost_off, r.super_gate, k, nprobe, slack, cap_take, max_nbr,
        k_q=r.k_q, cap_q=cap_q, nprobe_q=r.nprobe_q, scan_chunk=scan_chunk,
        sem=sem, sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# Fused IVF-PQ serving (ISSUE 16): the last serving mode leaves the classic
# multi-dispatch path. The ADC table build (query × codebook sub-distances),
# the m-byte PQ scan over the top-nprobe clusters' LIVE member tables (the
# PR 12 donated tables — PQ finally sees online IVF), the exact f32
# shortlist rescore from gathered master rows at the coarse_fetch_slack
# window, and the super-gate/CSR-gather/boost-scatter tail all fuse into
# ONE donated dispatch + ONE packed readback. Structurally this is the int8
# branch of ``_ivf_two_tier`` with the coarse stage swapped: instead of a
# d-byte int8 row the candidate costs m bytes (m·1-byte code gather + m LUT
# adds), so the coarse tier reads ~d/m× less HBM per candidate — the
# substrate for the billion-row full-corpus scan (ROADMAP item 5). The gate
# verdict and every returned score come from the exact rescore, so ADC
# error never leaks past the shortlist boundary.
# ---------------------------------------------------------------------------


def _pq_flat_lut(book_cent: jax.Array, qn: jax.Array) -> jax.Array:
    """ADC lookup tables for a query chunk: each query's inner product
    with every subspace centroid, flattened to ``[C, m·256]`` so a row's
    score is an m-gather + sum over its byte codes (offset by subspace).
    The build is tiny — m gemms of [C, dsub]×[dsub, 256] — and amortizes
    over every candidate the chunk touches (same LUT layout as the
    classic ``ops.pq.ivf_pq_search``, traced into the fused program)."""
    m, _, dsub = book_cent.shape
    lut = jnp.einsum("cmd,mkd->cmk", qn.reshape(qn.shape[0], m, dsub),
                     book_cent, preferred_element_type=jnp.float32)
    return lut.reshape(qn.shape[0], m * 256)


def _pq_adc_scores(flat_lut: jax.Array, codes_g: jax.Array) -> jax.Array:
    """Asymmetric-distance scores for per-query gathered codes: ``codes_g
    [C, L, m]`` u8 → ``[C, L]`` f32 approximate inner products. One take
    per (candidate, subspace) against the query's flat LUT."""
    m = codes_g.shape[-1]
    offs = (jnp.arange(m) * 256).astype(jnp.int32)
    idx = codes_g.astype(jnp.int32) + offs[None, None, :]
    return jax.vmap(
        lambda fl, ix: jnp.take(fl, ix, axis=0).sum(-1))(flat_lut, idx)


def _pq_two_tier(state: ArenaState, book_cent: jax.Array, codes: jax.Array,
                 centroids: jax.Array, members: jax.Array,
                 extras: jax.Array, q_c: jax.Array, tenant_c: jax.Array,
                 k: int, nprobe: int, slack: int, nprobe_c=None):
    """IVF-PQ two-tier core: coarse centroid prefilter + member gather
    (``ops.ivf.gather_rows`` — identical candidate assembly to the IVF
    kernels, extras included, so fresh/residual/super rows are always in
    the window), ADC coarse scoring from the m-byte codes, exact f32
    rescore of the k+slack shortlist from the master arena, duplicate-row
    dedup at the top-k boundary. The incremental ``_pq_scatter`` keeps
    every live row's codes current, so no candidate needs a staleness
    escape hatch. Shard-local by construction when given per-shard tables
    with LOCAL row indices (the codes slab row-shards with the master).
    Returns the ``(gate_s, gate_r, ann_s, ann_r, n_dup)`` contract of
    ``_ivf_two_tier``; ``nprobe_c`` raggedness is identical."""
    from lazzaro_tpu.ops.ivf import gather_rows

    cap = state.capacity
    L = nprobe * members.shape[1] + extras.shape[0]
    k_fetch = min(k + slack, L)
    g_fetch = min(1 + slack, L)
    qn = normalize(q_c)                               # [C, d] f32
    cand, safe = gather_rows(centroids, members, extras, qn, nprobe)
    valid = ((cand >= 0) & state.alive[safe]
             & (state.tenant_id[safe] == tenant_c[:, None]))
    if nprobe_c is not None:
        m_w = members.shape[1]
        pos = jnp.arange(L)
        in_members = pos < nprobe * m_w
        rank = pos // max(m_w, 1)
        valid = valid & (~in_members[None, :]
                         | (rank[None, :] < nprobe_c[:, None]))
    sup = state.is_super[safe]
    qd = qn.astype(state.emb.dtype)

    # coarse tier: m bytes per candidate — the LUT gather, not a matmul
    flat_lut = _pq_flat_lut(book_cent, qn)
    coarse = _pq_adc_scores(flat_lut, codes[safe])    # [C, L]
    a_s0, a_pos = jax.lax.top_k(
        jnp.where(valid & ~sup, coarse, NEG_INF), k_fetch)
    g_s0, g_pos = jax.lax.top_k(
        jnp.where(valid & sup, coarse, NEG_INF), g_fetch)
    a_s0, a_pos, g_s0, g_pos = jax.lax.optimization_barrier(
        (a_s0, a_pos, g_s0, g_pos))

    # exact rescore of the few survivors from the master — scores and the
    # gate verdict never see ADC error (same contract as the int8 path)
    def rescore(rows_c, coarse_s):
        g = state.emb[_phys(state, rows_c)]           # [C, kf, d]
        ex = jnp.einsum("cd,ckd->ck", qd, g,
                        preferred_element_type=jnp.float32)
        return jnp.where(coarse_s > NEG_INF / 2, ex, NEG_INF)

    a_rows0 = jnp.take_along_axis(cand, a_pos, axis=1)
    a_rows_safe = jnp.where(a_s0 > NEG_INF / 2, a_rows0, cap)
    ann_ex = rescore(a_rows_safe, a_s0)
    g_rows0 = jnp.take_along_axis(cand, g_pos, axis=1)
    g_rows_safe = jnp.where(g_s0 > NEG_INF / 2, g_rows0, cap)
    gate_ex = rescore(g_rows_safe, g_s0)
    g_s, g_sel = jax.lax.top_k(gate_ex, 1)
    gate_s = g_s[:, 0]
    gate_r0 = jnp.take_along_axis(g_rows_safe, g_sel, axis=1)[:, 0]
    ann_s, ann_r, n_dup = _dedup_topk(ann_ex, a_rows_safe, cap, k)
    gate_r = jnp.where(gate_s > NEG_INF / 2, gate_r0, cap)
    return gate_s, gate_r, ann_s, ann_r, n_dup


def _search_fused_pq_scan(state: ArenaState, book_cent: jax.Array,
                          codes: jax.Array, centroids: jax.Array,
                          members: jax.Array, extras: jax.Array,
                          csr_indptr: jax.Array, csr_nbr: jax.Array,
                          q: jax.Array, q_valid: jax.Array,
                          tenant: jax.Array, gate_on: jax.Array,
                          boost_on: jax.Array, super_gate: jax.Array,
                          k: int, nprobe: int, slack: int, cap_take: int,
                          max_nbr: int, k_q: jax.Array, cap_q: jax.Array,
                          nprobe_q: jax.Array, scan_chunk: int = 0,
                          sem=None, sem_block: int = 16):
    """PQ per-chunk compute phase: the ADC two-tier core, then the shared
    gate/CSR/boost tail. The per-query columns behave exactly as in
    ``_search_fused_ivf_scan``."""
    def body(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c, nprobe_c):
        gate_s, gate_r, ann_s, ann_r, n_dup = _pq_two_tier(
            state, book_cent, codes, centroids, members, extras, q_c,
            tenant_c, k, nprobe, slack, nprobe_c=nprobe_c)
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, k_c, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, gate_s, gate_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c, super_gate, cap_take,
            max_nbr, cap_c=cap_c)
        return (gate_s, gate_r, ann_s, ann_r, fast, acc_rows, nbr_rows,
                n_dup)

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q, nprobe_q)
    if sem is None:
        return chunked_map_multi(body, arrays,
                                 chunk=min(scan_chunk or IVF_SERVE_CHUNK,
                                           IVF_SERVE_CHUNK))
    return _semantic_scan_core(body, arrays, state, sem, super_gate,
                               block=sem_block)


def _search_fused_pq_ragged(
    state: ArenaState,
    book_cent: jax.Array,    # [m, 256, dsub] f32 frozen PQ codebook
    codes: jax.Array,        # [cap+1, m] u8 live codes (incrementally kept)
    centroids: jax.Array,    # [C, d] f32 L2-normalized (ops/ivf.py build)
    members: jax.Array,      # [C, M] i32 arena rows, -1 padded
    extras: jax.Array,       # [E] i32 residual + fresh + super rows, -1 pad
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    nprobe: int,             # STATIC probe ceiling (the build's width)
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """``search_fused_ivf_ragged`` with the m-byte ADC scan as the coarse
    stage: ONE donated dispatch + ONE packed readback per coalesced batch
    in PQ mode. The member gather and ADC scan run to the ceilings, each
    query masks at its own boundaries — one compiled PQ kernel for
    mixed-shape traffic. Only the arena state is donated — the codebook,
    codes slab, and coarse tables are long-lived read-only replicas (the
    boost scatter touches salience/access/freshness, never embeddings or
    codes)."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_pq_scan(state, book_cent, codes, centroids, members,
                                extras, csr_indptr, csr_nbr, r.q, r.q_valid,
                                r.tenant, r.gate_on, r.boost_on,
                                r.super_gate, k, nprobe, slack, cap_take,
                                max_nbr, k_q=r.k_q, cap_q=r.cap_q,
                                nprobe_q=r.nprobe_q, scan_chunk=scan_chunk,
                                sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_pq_ragged, search_fused_pq_ragged_copy = _donated_pair(
    _search_fused_pq_ragged,
    static_argnames=("k", "nprobe", "slack", "cap_take", "max_nbr",
                     "scan_chunk", "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "slack",
                                             "cap_take", "max_nbr",
                                             "scan_chunk", "sem_block"))
def search_fused_pq_ragged_read(state: ArenaState, book_cent: jax.Array,
                                codes: jax.Array, centroids: jax.Array,
                                members: jax.Array, extras: jax.Array,
                                csr_indptr: jax.Array, csr_nbr: jax.Array,
                                requests: jax.Array, k: int, nprobe: int,
                                slack: int, cap_take: int, max_nbr: int,
                                scan_chunk: int = 0, sem=None,
                                sem_block: int = 16) -> jax.Array:
    """Read-only twin of ``search_fused_pq_ragged`` (pure
    ``search_memories`` fleets in PQ mode): same ADC scan + exact rescore,
    no state mutation, no donation dance."""
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_pq_scan(
        state, book_cent, codes, centroids, members, extras, csr_indptr,
        csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on, boost_off,
        r.super_gate, k, nprobe, slack, cap_take, max_nbr, k_q=r.k_q,
        cap_q=cap_q, nprobe_q=r.nprobe_q, scan_chunk=scan_chunk, sem=sem,
        sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# PQ × tiering (ISSUE 16): lifts the last tiering incompatibility. Hot
# candidates come from the IVF member gather with exact in-kernel rescore —
# unchanged from the IVF×tiered kernel — and COLD rows come from the
# full-corpus ADC scan restricted to the cold residency mask (a demoted
# row's master embedding is zeroed, but its m-byte codes stay valid: the
# incremental scatter only touches written rows, and the re-seed full
# encode patches cold rows from the host ColdStore). The blended k+slack
# window, the deferred boosts, and the packed readback are layout-identical
# to the tiered kernels, so ``tier.serve.tiered_decode_and_finish`` —
# including the bounded exact-rescore finish dispatch for cold survivors —
# runs unchanged.
# ---------------------------------------------------------------------------


def _pq_tiered_two_tier(state: ArenaState, book_cent: jax.Array,
                        codes: jax.Array, cold: jax.Array,
                        centroids: jax.Array, members: jax.Array,
                        extras: jax.Array, q_c: jax.Array,
                        tenant_c: jax.Array, k: int, nprobe: int,
                        slack: int, nprobe_c=None):
    """Tier-aware PQ core: exact member gather for the hot tier, ADC
    coarse over the COLD rows only (m bytes per cold row — the cheapest
    full-corpus coverage any mode has), blended top-(k+slack) with row
    dedup. Contract identical to ``_ivf_tiered_two_tier``."""
    from lazzaro_tpu.ops.ivf import gather_rows

    cap = state.capacity
    n = _nrows(state)
    L = nprobe * members.shape[1] + extras.shape[0]
    k_fetch = min(k + slack, L + n)
    k_hot = min(k + slack, L)
    k_cold = min(k + slack, n)
    qn = normalize(q_c)                                   # [C, d] f32
    qd = qn.astype(state.emb.dtype)
    cand, safe = gather_rows(centroids, members, extras, qn, nprobe)
    valid = ((cand >= 0) & state.alive[safe] & ~cold[safe]
             & (state.tenant_id[safe] == tenant_c[:, None]))
    if nprobe_c is not None:
        m_w = members.shape[1]
        pos = jnp.arange(L)
        in_members = pos < nprobe * m_w
        rank = pos // max(m_w, 1)
        valid = valid & (~in_members[None, :]
                         | (rank[None, :] < nprobe_c[:, None]))
    sup = state.is_super[safe]
    vecs = state.emb[_phys(state, safe)]                  # [C, L, d]
    sc = jnp.einsum("cd,cld->cl", qd, vecs,
                    preferred_element_type=jnp.float32)
    h_s, h_pos = jax.lax.top_k(jnp.where(valid & ~sup, sc, NEG_INF), k_hot)
    g_s0, g_pos = jax.lax.top_k(jnp.where(valid & sup, sc, NEG_INF), 1)
    # cold tier: ADC coarse over the residency-masked full-corpus codes
    flat_lut = _pq_flat_lut(book_cent, qn)
    m = book_cent.shape[0]
    offs = (jnp.arange(m) * 256).astype(jnp.int32)
    idx_full = codes.astype(jnp.int32) + offs[None, :]    # [rows, m]
    coarse = jax.vmap(
        lambda fl: jnp.take(fl, idx_full, axis=0).sum(-1))(flat_lut)
    cold_m = (cold[None, :] & state.alive[None, :]
              & ~state.is_super[None, :]
              & (state.tenant_id[None, :] == tenant_c[:, None]))
    c_s, c_r = jax.lax.top_k(jnp.where(cold_m, coarse, NEG_INF), k_cold)
    h_s, h_pos, g_s0, g_pos, c_s, c_r = jax.lax.optimization_barrier(
        (h_s, h_pos, g_s0, g_pos, c_s, c_r))
    h_rows = jnp.take_along_axis(cand, h_pos, axis=1)
    # blended window: hot exact ++ cold coarse, one more top-k + dedup
    all_s = jnp.concatenate([h_s, c_s], axis=1)
    all_r = jnp.concatenate([h_rows, c_r], axis=1)
    ann_s, ann_r, n_dup = _dedup_topk(all_s, all_r, cap, k_fetch)
    is_cold = cold[jnp.minimum(ann_r, n - 1)] & (ann_s > NEG_INF / 2)
    cold_any = is_cold.any(axis=-1)
    gate_s = g_s0[:, 0]
    gate_r0 = jnp.take_along_axis(cand, g_pos, axis=1)[:, 0]
    gate_r = jnp.where(gate_s > NEG_INF / 2, gate_r0, cap)
    return gate_s, gate_r, ann_s, ann_r, n_dup, cold_any


def _search_fused_pq_tiered_scan(state: ArenaState, book_cent: jax.Array,
                                 codes: jax.Array, cold: jax.Array,
                                 centroids: jax.Array, members: jax.Array,
                                 extras: jax.Array, csr_indptr: jax.Array,
                                 csr_nbr: jax.Array, q: jax.Array,
                                 q_valid: jax.Array, tenant: jax.Array,
                                 gate_on: jax.Array, boost_on: jax.Array,
                                 super_gate: jax.Array, k: int,
                                 nprobe: int, slack: int, cap_take: int,
                                 max_nbr: int, k_q: jax.Array,
                                 cap_q: jax.Array, nprobe_q: jax.Array,
                                 scan_chunk: int = 0,
                                 sem=None, sem_block: int = 16):
    """PQ×tiered per-chunk compute: the tier-aware PQ core, then the
    shared gate/CSR/boost tail with cold-hit queries' boosts deferred to
    the bounded finish dispatch — the tiered scan's contract."""
    def chunk(q_c, valid_c, tenant_c, gate_c, boost_c, k_c, cap_c, np_c):
        g_s, g_r, ann_s, ann_r, n_dup, cold_any = _pq_tiered_two_tier(
            state, book_cent, codes, cold, centroids, members, extras,
            q_c, tenant_c, k, nprobe, slack, nprobe_c=np_c)
        kf = jnp.minimum(k_c + slack, ann_s.shape[1])
        ann_s, ann_r = _ragged_topk_mask(ann_s, ann_r, kf, state.capacity)
        fast, acc_rows, nbr_rows = _gate_and_boost_rows(
            state, csr_indptr, csr_nbr, g_s, g_r, ann_s, ann_r,
            valid_c, tenant_c, gate_c, boost_c & ~cold_any, super_gate,
            cap_take, max_nbr, cap_c=cap_c)
        return g_s, g_r, ann_s, ann_r, fast, acc_rows, nbr_rows, n_dup

    arrays = (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q, nprobe_q)
    if sem is None:
        return chunked_map_multi(chunk, arrays,
                                 chunk=(scan_chunk or IVF_SERVE_CHUNK))
    return _semantic_scan_core(chunk, arrays, state, sem, super_gate,
                               block=sem_block, rag_slack=slack)


def _search_fused_pq_tiered_ragged(
    state: ArenaState,
    book_cent: jax.Array,
    codes: jax.Array,
    cold: jax.Array,
    centroids: jax.Array,
    members: jax.Array,
    extras: jax.Array,
    csr_indptr: jax.Array,
    csr_nbr: jax.Array,
    requests: jax.Array,     # [Q, d + REQUEST_COLS] i32: _unpack_requests
    k: int,
    nprobe: int,
    slack: int,
    cap_take: int,
    max_nbr: int,
    scan_chunk: int = 0,
    sem=None,
    sem_block: int = 16,
) -> Tuple[ArenaState, jax.Array]:
    """ONE donated dispatch + ONE packed readback: IVF member gather for
    the hot tier, cold-masked ADC coarse for the demoted rows, tiered
    candidate window (k+slack wide) for the bounded finish."""
    r = _unpack_requests(requests, state.dim)
    res = _search_fused_pq_tiered_scan(
        state, book_cent, codes, cold, centroids, members, extras,
        csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on,
        r.boost_on, r.super_gate, k, nprobe, slack, cap_take, max_nbr,
        k_q=r.k_q, cap_q=r.cap_q, nprobe_q=r.nprobe_q,
        scan_chunk=scan_chunk, sem=sem, sem_block=sem_block)
    return _sem_finish(state, res, sem, r.now, r.acc_boost, r.nbr_boost)


search_fused_pq_tiered_ragged, search_fused_pq_tiered_ragged_copy = \
    _donated_pair(_search_fused_pq_tiered_ragged,
                  static_argnames=("k", "nprobe", "slack", "cap_take",
                                   "max_nbr", "scan_chunk", "sem_block"))


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "slack",
                                             "cap_take", "max_nbr",
                                             "scan_chunk", "sem_block"))
def search_fused_pq_tiered_ragged_read(
        state: ArenaState, book_cent: jax.Array, codes: jax.Array,
        cold: jax.Array, centroids: jax.Array, members: jax.Array,
        extras: jax.Array, csr_indptr: jax.Array, csr_nbr: jax.Array,
        requests: jax.Array, k: int, nprobe: int, slack: int,
        cap_take: int, max_nbr: int, scan_chunk: int = 0,
        sem=None, sem_block: int = 16) -> jax.Array:
    r = _unpack_requests(requests, state.dim)
    boost_off = jnp.zeros(r.q_valid.shape, bool)
    cap_q = jnp.zeros(r.q_valid.shape, jnp.int32)
    res = _search_fused_pq_tiered_scan(
        state, book_cent, codes, cold, centroids, members, extras,
        csr_indptr, csr_nbr, r.q, r.q_valid, r.tenant, r.gate_on,
        boost_off, r.super_gate, k, nprobe, slack, cap_take, max_nbr,
        k_q=r.k_q, cap_q=cap_q, nprobe_q=r.nprobe_q, scan_chunk=scan_chunk,
        sem=sem, sem_block=sem_block)
    return _sem_finish_read(res, sem)


# ---------------------------------------------------------------------------
# Pod-scale fused serving (ISSUE 5): the SAME chat-turn program — two-tier
# scan, super gate, CSR neighbor gather, boost scatters — composed with the
# device mesh as ONE distributed shard_map dispatch + ONE packed readback.
#
# Geometry: every arena column (and the int8 shadow / per-shard IVF tables /
# per-shard CSR) is row-sharded over the mesh axis; queries and per-query
# metadata are replicated. Each chip runs the shard-local two-tier core
# over its own rows (exact, int8-coarse + exact rescore, or IVF centroid
# prefilter over LOCAL member tables), produces local top-(k[+slack])
# candidates, and the ONLY cross-chip traffic is (a) the k-candidate
# all_gather + global top-k merge (ops.topk.sharded_topk_merge — the
# make_sharded_topk combine) and (b) a small pmax that replicates the
# owner-gathered CSR neighbor windows. The gate verdict and the boost ROW
# LISTS are then replicated computation, and each chip scatters boosts for
# exactly the rows it owns (non-owned rows route out of range — XLA drops
# OOB scatter updates), so the whole tail is shard-local writes.
#
# Parity with the single-chip kernels is structural: the per-row score
# computation, mask arithmetic, neighbor dedup, and capped boost adds are
# the same code (_exact_two_tier / _quant_two_tier / _ivf_two_tier /
# _boost_scatter); only the partitioning differs.
# ---------------------------------------------------------------------------


class FusedShardedKernels(NamedTuple):
    """The jit entry points one ``make_fused_sharded`` call builds: the
    donated serving program, its copy-on-write twin (for callers that
    cannot prove sole ownership of the state), and the read-only twin for
    batches with no boosts requested. Tests and bench wrap the factory to
    count calls — each call is exactly ONE distributed dispatch."""

    serve: Callable
    serve_copy: Callable
    read: Callable


def _globalize_rows(rows: jax.Array, scores: jax.Array, shard: jax.Array,
                    local_n: int, n_shards: int) -> jax.Array:
    """Local candidate rows → global row ids; NEG_INF (masked/garbage)
    entries route to the GLOBAL sentinel row so they can never collide
    with a real row after the cross-chip merge."""
    sent = n_shards * local_n - 1
    return jnp.where(scores > NEG_INF / 2, rows + shard * local_n, sent)


def make_fused_sharded(mesh, axis: str, *, k: int, cap_take: int,
                       max_nbr: int, mode: str = "exact", slack: int = 0,
                       nprobe: int = 0, scan_chunk: int = 0,
                       sem: bool = False) -> FusedShardedKernels:
    """Build the distributed fused chat-turn serving program for ``mesh``.

    ``mode`` picks the shard-local coarse stage:

    - ``"exact"``     — bf16/f32 whole-shard scan (``_exact_two_tier``)
    - ``"quant"``     — int8 shadow coarse top-(k+slack) + exact rescore
                        (``_quant_two_tier``); extra tables ``(q8, scale)``
                        row-sharded like the master
    - ``"ivf"``       — centroid prefilter + LOCAL member gather
                        (``_ivf_two_tier``); tables ``(centroids [C,d]
                        replicated, members [n,C,M], extras [n,E])`` with
                        member/extras entries as LOCAL row indices per
                        shard (``ops.ivf.shard_serve_tables``)
    - ``"ivf_quant"`` — IVF prefilter + int8-gathered coarse + exact
                        rescore; tables ``(q8, scale, centroids, members,
                        extras)``
    - ``"pq"``        — IVF prefilter + m-byte ADC coarse + exact rescore
                        (``_pq_two_tier``, ISSUE 16); tables ``(book_cent
                        [m,256,dsub] replicated, codes [rows,m] row-
                        sharded with the master, centroids, members,
                        extras)`` — the ADC LUT build is replicated
                        arithmetic, candidates ride the existing merge

    Call signatures (tables is the mode's tuple above, ``()`` for exact):

    ``serve(state, tables, csr_indptr [n,L+1], csr_nbr [n,E], requests
    [Q, d + REQUEST_COLS]) -> (state, packed [Q, 3+2k])`` — donates the
    state (ONE distributed dispatch, shard-local boost scatters in place);
    ``serve_copy`` is the non-donating twin; ``read``, the same operands
    ``-> packed``, skips the mutation entirely. ``requests`` is the
    dispatch's ONE request carrier (``_unpack_requests``), REPLICATED: the
    one host operand reaches every chip.

    ``k`` / ``cap_take`` / ``nprobe`` are static CEILINGS; the carrier's
    ``k`` / ``cap`` / ``nprobe`` columns carry each query's own shape, so
    ONE compiled distributed program serves any mix of request shapes (the
    shard-local scans and the all_gather merge run to the ceiling; each
    query masks at its own boundaries, ``ops.topk.sharded_topk_merge``
    applying the k mask at the merge). The dense modes ignore the probe
    column, so every mode shares one ABI.

    The per-shard CSR carries each chip's OWN rows' neighbor lists with
    GLOBAL neighbor ids; Q is bounded by the scheduler's padded batch
    (≤ ``QUERY_CHUNK`` — the local cores stream bigger fleets through the
    usual chunked tiles, IVF at ``IVF_SERVE_CHUNK`` to bound the gather
    footprint).

    ``scan_chunk > 0`` (ISSUE 17 satellite — the pod twin of the ISSUE 11
    single-chip override) narrows every chip's shard-local streaming tile:
    the planner can fit an over-budget pod geometry by shrinking the
    ``[chunk, local_rows]`` score transient of the quant / tiered cores
    (the exact core holds no such tile since ISSUE 26) instead of
    splitting the turn into extra dispatches. Bit-identical results —
    only the streaming granularity changes — and still ONE distributed
    dispatch.

    ``sem=True`` (ISSUE 20) threads the semantic query-cache ring through
    the distributed program: every call signature gains a trailing
    ``sem_state = (ring, valid, head, thresh, mode_id)`` pytree
    (REPLICATED — the ring rides every chip identically) and the serve
    twins return ``(state, ring, packed)`` / read returns ``(ring,
    packed)``. The mesh variant is substitution-only: the probe,
    result substitution, and writeback are replicated arithmetic after
    the merge (the shard-local scans still run — skipping blocks would
    desynchronize the all_gather), so pod hits save the readback-side
    work and keep the ring warm for the single-chip replicas, and the
    packed layout still carries the per-query sem verdict column."""
    from jax.sharding import PartitionSpec as P

    from lazzaro_tpu.ops.topk import sharded_topk_merge
    from jax import shard_map

    if mode not in ("exact", "quant", "ivf", "ivf_quant", "tiered", "pq"):
        raise ValueError(f"unknown fused-sharded mode {mode!r}")
    if cap_take > k:
        raise ValueError("cap_take must not exceed k")
    n_shards = mesh.shape[axis]
    chunk = scan_chunk or (IVF_SERVE_CHUNK
                           if mode.startswith("ivf") or mode == "pq"
                           else QUERY_CHUNK)
    # Tiered mode (ISSUE 8): the merged candidate block stays k+slack wide
    # so the host can finish cold-hit queries (exact rescore of host-
    # gathered rows + final re-rank) over the same window.
    k_merge = k + slack if mode == "tiered" else k

    def _scan_merge(arena, tables, q, tenant, k_q, nprobe_q):
        """Shard-local two-tier candidates → globalize → ONE all_gather +
        global top-k per tier. Returns replicated (gate_s [Q], gate_r [Q],
        ann_s [Q,k], ann_r [Q,k], n_dup [Q]) with GLOBAL row ids; the dup
        counter (IVF in-kernel dedup hits, per-shard counts summed with a
        tiny psum riding the same dispatch) is zero for the dense modes.
        Local scans run to the ceiling, the merge masks each query at its
        own k boundary."""
        shard = jax.lax.axis_index(axis)
        local_n = arena.emb.shape[0]
        k_l = max(1, min(k, local_n))
        if mode == "quant":
            q8_l, scale_l = tables
        elif mode == "tiered":
            q8_l, scale_l, cold_l = tables
        elif mode == "ivf":
            cent, mem2, ext2 = tables
            mem_l, ext_l, shadow_l = mem2[0], ext2[0], None
        elif mode == "ivf_quant":
            q8_l, scale_l, cent, mem2, ext2 = tables
            mem_l, ext_l, shadow_l = mem2[0], ext2[0], (q8_l, scale_l)
        elif mode == "pq":
            book_l, codes_l, cent, mem2, ext2 = tables
            mem_l, ext_l = mem2[0], ext2[0]

        def core(q_c, tenant_c, *col):
            # ``col``: the one per-query column the mode's core reads —
            # each query's k (exact), its probe width (ivf / pq), none
            # (quant / tiered)
            zeros = jnp.zeros((q_c.shape[0],), jnp.int32)
            off = jnp.zeros((q_c.shape[0],), bool)
            if mode == "exact":
                g_s, g_r, a_s, a_r = _exact_two_tier(
                    arena, q_c, tenant_c, k_l, col[0])
                return g_s[:, None], g_r[:, None], a_s, a_r, zeros, off
            if mode == "quant":
                g_s, g_r, a_s, a_r = _quant_two_tier(
                    arena, q8_l, scale_l, q_c, tenant_c, k_l, slack)
                return g_s, g_r, a_s, a_r, zeros, off
            if mode == "tiered":
                g_s, g_r, a_s, a_r, cold_c = _tiered_two_tier(
                    arena, q8_l, scale_l, cold_l, q_c, tenant_c, k_l,
                    slack)
                return g_s, g_r, a_s, a_r, zeros, cold_c
            if mode == "pq":
                g_s, g_r, a_s, a_r, n_dup = _pq_two_tier(
                    arena, book_l, codes_l, cent, mem_l, ext_l, q_c,
                    tenant_c, k_l, nprobe, slack, nprobe_c=col[0])
                return g_s[:, None], g_r[:, None], a_s, a_r, n_dup, off
            g_s, g_r, a_s, a_r, n_dup = _ivf_two_tier(
                arena, shadow_l, cent, mem_l, ext_l, q_c, tenant_c, k_l,
                nprobe, slack, nprobe_c=col[0])
            return g_s[:, None], g_r[:, None], a_s, a_r, n_dup, off

        arrays = (q, tenant)
        if mode == "exact":
            arrays = arrays + (k_q,)
        elif mode.startswith("ivf") or mode == "pq":
            arrays = arrays + (nprobe_q,)
        g_s, g_r, a_s, a_r, dup_l, cold_l_q = chunked_map_multi(
            core, arrays, chunk=chunk)
        n_dup = jax.lax.psum(dup_l, axis)
        # a query is a cold hit if ANY shard's candidate window touched a
        # cold row — the psum rides the same dispatch
        cold_any = jax.lax.psum(cold_l_q.astype(jnp.int32), axis) > 0
        sent = n_shards * local_n - 1          # the global sentinel row
        k_q_eff = k_q + slack if mode == "tiered" else k_q
        km = min(k_merge, n_shards * a_s.shape[1])
        ann_s, ann_r = sharded_topk_merge(
            axis, a_s, _globalize_rows(a_r, a_s, shard, local_n, n_shards),
            km, k_q=k_q_eff, sentinel=sent)
        g_ms, g_mr = sharded_topk_merge(
            axis, g_s, _globalize_rows(g_r, g_s, shard, local_n, n_shards),
            1)
        # The PR 2 consumer-split fix applies at the merge boundary too:
        # the merged top-k feeds both the packed readback and (in the
        # serve twins) the boost gather tail.
        return jax.lax.optimization_barrier(
            (g_ms[:, 0], g_mr[:, 0], ann_s, ann_r, n_dup, cold_any))

    def _boost_tail(arena, indptr_l, nbr_l, ann_s, ann_r, fast, q_valid,
                    tenant, boost_on, now, acc_boost, nbr_boost, cap_q):
        """The gate/CSR/boost tail against the row-sharded edge arena:
        owner chips gather their rows' CSR neighbor windows (merged to all
        chips with one small pmax), the per-query dedup / in-result masks
        are replicated arithmetic on the merged id lists (exactly
        ``_csr_neighbor_rows``'s), and each chip scatters boosts ONLY for
        rows it owns — non-owned rows route out of range and XLA drops
        the updates, so no boost ever crosses a chip boundary."""
        shard = jax.lax.axis_index(axis)
        local_n = arena.emb.shape[0]
        sent = n_shards * local_n - 1          # == the global sentinel row
        do_boost = boost_on & q_valid & ~fast
        take = ((ann_s[:, :cap_take] > NEG_INF / 2) & do_boost[:, None]
                & (jnp.arange(cap_take)[None, :] < cap_q[:, None]))
        acc_rows = jnp.where(take, ann_r[:, :cap_take], sent)  # global rows
        base = shard * local_n
        loc = acc_rows - base
        mine = (loc >= 0) & (loc < local_n) & (acc_rows != sent)
        safe_loc = jnp.clip(loc, 0, local_n - 1)
        start = jnp.where(mine, indptr_l[safe_loc], 0)
        end = jnp.where(mine, indptr_l[safe_loc + 1], 0)
        idx = start[:, :, None] + jnp.arange(max_nbr)[None, None, :]
        ok = idx < end[:, :, None]
        nbrw = jnp.where(ok, nbr_l[jnp.minimum(idx, nbr_l.shape[0] - 1)],
                         -1)
        # exactly one chip owns each accessed row; everyone else holds -1,
        # so a pmax replicates the true windows — the only tail collective
        nbrw = jax.lax.pmax(nbrw, axis)
        flat = nbrw.reshape(nbrw.shape[0], -1)              # [Q, M]
        m = flat.shape[1]
        dup = ((flat[:, :, None] == flat[:, None, :])
               & jnp.tri(m, k=-1, dtype=bool)[None, :, :]).any(-1)
        in_res = (flat[:, :, None] == acc_rows[:, None, :]).any(-1)
        nloc = flat - base
        nmine = (nloc >= 0) & (nloc < local_n) & (flat >= 0)
        nsafe = jnp.clip(nloc, 0, local_n - 1)
        nvalid = (nmine & arena.alive[nsafe]
                  & (arena.tenant_id[nsafe] == tenant[:, None]))
        nbr_idx = jnp.where(nvalid & ~dup & ~in_res, nloc, local_n)
        acc_idx = jnp.where(mine, loc, local_n)
        # Device-side boost counters for the readback tail: the access
        # rows are replicated arithmetic (count once, identically on every
        # chip); the neighbor validity checks are per-owner, so the
        # per-chip counts sum with one tiny psum inside the same dispatch.
        n_acc = (acc_rows != sent).sum(axis=-1).astype(jnp.int32)
        n_nbr = jax.lax.psum(
            (nbr_idx != local_n).sum(axis=-1).astype(jnp.int32), axis)
        return _boost_scatter(arena, acc_idx, nbr_idx, now, acc_boost,
                              nbr_boost, zero_last=False), n_acc, n_nbr

    def _sem_apply(sem_state, sent, q, q_valid, tenant, gate_on,
                   super_gate, merged, k_q, nprobe_q):
        """Replicated probe → substitute → writeback after the merge.
        Every chip computes the identical verdicts and the identical next
        ring (replicated inputs, replicated arithmetic), so the ring's
        out-spec stays P(None...) with zero extra collectives."""
        ring, sem_valid, head, thresh, mode_id = sem_state
        gate_s, gate_r, ann_s, ann_r, n_dup, cold_any = merged
        qn = normalize(q).astype(jnp.float32)
        hit, slot = _semantic_probe(ring, sem_valid, qn, tenant, q_valid,
                                    gate_on, k_q, nprobe_q, mode_id, thresh)
        miss = q_valid & ~hit
        rank = jnp.cumsum(miss.astype(jnp.int32)) - 1
        n_miss = miss.sum().astype(jnp.int32)
        write_mask = miss & (rank >= n_miss - ring.slots)
        ring2 = _semantic_writeback(ring, head, qn, tenant, gate_on,
                                    gate_s, gate_r, ann_s, ann_r, rank,
                                    write_mask, k_q, nprobe_q, mode_id, sent)
        fast0 = gate_on & (gate_s > super_gate)
        rag_slack = slack if mode == "tiered" else 0
        gate_s, gate_r, ann_s, ann_r, fast = _semantic_substitute(
            ring, hit, slot, gate_on, super_gate,
            (gate_s, gate_r, ann_s, ann_r, fast0), k_q, rag_slack, sent)
        n_dup = jnp.where(hit, 0, n_dup)
        sem_col = jnp.where(hit, 1 + slot, 0).astype(jnp.int32)
        return (gate_s, gate_r, ann_s, ann_r, fast, n_dup,
                cold_any & ~hit, hit, sem_col, ring2)

    def _serve_local(arena, tables, indptr2, nbr2, requests,
                     sem_state=None):
        (q, q_valid, tenant, gate_on, boost_on, k_q, cap_q, nprobe_q,
         super_gate, now, acc_boost, nbr_boost) = _unpack_requests(
             requests, arena.emb.shape[1])
        merged = _scan_merge(arena, tables, q, tenant, k_q, nprobe_q)
        if sem_state is None:
            gate_s, gate_r, ann_s, ann_r, n_dup, cold_any = merged
            fast = gate_on & (gate_s > super_gate)
            arena, n_acc, n_nbr = _boost_tail(
                arena, indptr2[0], nbr2[0], ann_s, ann_r, fast, q_valid,
                tenant, boost_on & ~cold_any, now, acc_boost, nbr_boost,
                cap_q)
            packed = _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast,
                                     dup=n_dup, acc=n_acc, nbr=n_nbr)
            return arena, packed
        sent = n_shards * arena.emb.shape[0] - 1
        (gate_s, gate_r, ann_s, ann_r, fast, n_dup, cold_eff, hit,
         sem_col, ring2) = _sem_apply(sem_state, sent, q, q_valid, tenant,
                                      gate_on, super_gate, merged, k_q,
                                      nprobe_q)
        arena, n_acc, n_nbr = _boost_tail(
            arena, indptr2[0], nbr2[0], ann_s, ann_r, fast, q_valid,
            tenant, boost_on & ~cold_eff & ~hit, now, acc_boost,
            nbr_boost, cap_q)
        packed = _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast,
                                 dup=n_dup, acc=n_acc, nbr=n_nbr,
                                 sem=sem_col)
        return arena, ring2, packed

    def _read_local(arena, tables, indptr2, nbr2, requests,
                    sem_state=None):
        r = _unpack_requests(requests, arena.emb.shape[1])
        q, q_valid, tenant, gate_on = r.q, r.q_valid, r.tenant, r.gate_on
        k_q, nprobe_q, super_gate = r.k_q, r.nprobe_q, r.super_gate
        merged = _scan_merge(arena, tables, q, tenant, k_q, nprobe_q)
        if sem_state is None:
            gate_s, gate_r, ann_s, ann_r, n_dup, _cold = merged
            fast = gate_on & (gate_s > super_gate)
            return _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast,
                                   dup=n_dup)
        sent = n_shards * arena.emb.shape[0] - 1
        (gate_s, gate_r, ann_s, ann_r, fast, n_dup, _cold, _hit,
         sem_col, ring2) = _sem_apply(sem_state, sent, q, q_valid, tenant,
                                      gate_on, super_gate, merged, k_q,
                                      nprobe_q)
        return ring2, _pack_retrieval(gate_s, gate_r, ann_s, ann_r, fast,
                                      dup=n_dup, sem=sem_col)

    state_specs = ArenaState(
        emb=P(axis, None), salience=P(axis), timestamp=P(axis),
        last_accessed=P(axis), access_count=P(axis), type_id=P(axis),
        shard_id=P(axis), tenant_id=P(axis), alive=P(axis),
        is_super=P(axis))
    tables_specs = {
        "exact": (),
        "quant": (P(axis, None), P(axis)),
        "tiered": (P(axis, None), P(axis), P(axis)),
        "ivf": (P(None, None), P(axis, None, None), P(axis, None)),
        "ivf_quant": (P(axis, None), P(axis), P(None, None),
                      P(axis, None, None), P(axis, None)),
        "pq": (P(None, None, None), P(axis, None), P(None, None),
               P(axis, None, None), P(axis, None)),
    }[mode]
    # arena, tables, the per-shard CSR, and the REPLICATED request carrier
    common = (state_specs, tables_specs, P(axis, None), P(axis, None),
              P(None, None))
    # Semantic ring (ISSUE 20): REPLICATED on every chip — the probe /
    # substitute / writeback are replicated arithmetic after the merge.
    ring_specs = SemanticRing(
        emb=P(None, None), tenant=P(None), gate_on=P(None), mode=P(None),
        stored_k=P(None), nprobe=P(None), gate_s=P(None), gate_r=P(None),
        ann_s=P(None, None), ann_r=P(None, None))
    sem_in = ((ring_specs, P(None), P(), P(), P()),) if sem else ()
    serve_out = ((state_specs, ring_specs, P(None, None)) if sem
                 else (state_specs, P(None, None)))
    read_out = (ring_specs, P(None, None)) if sem else P(None, None)
    mapped_serve = shard_map(
        _serve_local, mesh=mesh, in_specs=common + sem_in,
        out_specs=serve_out, check_vma=False)
    mapped_read = shard_map(
        _read_local, mesh=mesh, in_specs=common + sem_in,
        out_specs=read_out, check_vma=False)
    return FusedShardedKernels(
        serve=jax.jit(mapped_serve, donate_argnums=(0,)),
        serve_copy=jax.jit(mapped_serve),
        read=jax.jit(mapped_read))


class LifecycleShardedKernels(NamedTuple):
    """The jit entry points one ``make_lifecycle_sharded`` call builds:
    the donated all-tenant sweep, its copy-on-write twin, and the
    read-only payload twin. Each call is exactly ONE distributed
    dispatch — the jit-counter tests wrap the factory to pin that."""

    sweep: Callable
    sweep_copy: Callable
    read: Callable


def make_lifecycle_sharded(mesh, axis: str, *, prune_cap: int,
                           archive_k: int) -> LifecycleShardedKernels:
    """Distributed twin of ``lifecycle_sweep``: the decay scatters and the
    importance arithmetic are element-wise over the row-sharded columns
    (shard-local, zero traffic), weak-edge compaction runs shard-local
    with victim slots globalized before ONE all_gather re-compaction, and
    the per-tenant bottom-k verdicts merge through ``sharded_topk_merge``
    (replicated verdict arithmetic — every chip holds the identical
    payload, so the host reads ONE replicated buffer).

    Call signature mirrors the single-chip jit: ``sweep(arena, edges,
    passes [Tc], verdict_tids [Tv], rate, floor, threshold, now, w_sal,
    w_acc, w_rec) -> (arena, edges, payload)`` with ``prune_cap`` /
    ``archive_k`` baked in at build time (the host caches one program per
    (prune_cap, archive_k) bucket, same discipline as the ingest
    factory). The payload's pruned-slot and verdict-row sections carry
    GLOBAL ids, so the host decode is identical to single-chip."""
    from jax.sharding import PartitionSpec as P

    from lazzaro_tpu.ops.topk import sharded_topk_merge
    from jax import shard_map

    n_shards = mesh.shape[axis]

    def _local(arena, edges, passes, verdict_tids, rate, floor, threshold,
               now, w_sal, w_acc, w_rec):
        shard = jax.lax.axis_index(axis)
        local_n = arena.salience.shape[0]
        local_e = edges.src.shape[0]
        # full prune_cap per shard: skew-proof (one shard may hold every
        # weak edge) and still tiny — [prune_cap] i32 per chip
        arena, edges, v_imps_l, v_rows_l, slots_l, counters = \
            _lifecycle_core(arena, edges, passes, verdict_tids, rate,
                            floor, threshold, now, w_sal, w_acc, w_rec,
                            prune_cap, archive_k)
        # pruned slots: local → global ids, ONE all_gather, re-compact.
        # Shard-major flatten of ascending local slots IS globally
        # ascending, so the merged list keeps single-chip slot order.
        g_slots = jnp.where(slots_l >= 0, slots_l + shard * local_e, -1)
        flat = jax.lax.all_gather(g_slots, axis).reshape(-1)
        okg = flat >= 0
        posg = jnp.cumsum(okg.astype(jnp.int32)) - 1
        buf = jnp.full((prune_cap + 1,), -1, jnp.int32)
        buf = buf.at[jnp.where(okg & (posg < prune_cap),
                               jnp.minimum(posg, prune_cap - 1),
                               prune_cap)].set(flat)
        over_g = (okg & (posg >= prune_cap)).any().astype(jnp.int32)
        # verdicts: local bottom-k per tenant → globalize → merged bottom-k
        # (merge runs on negated importances so descending == bottom)
        neg_l = -v_imps_l
        g_rows = _globalize_rows(v_rows_l, neg_l, shard, local_n, n_shards)
        neg_m, rows_m = sharded_topk_merge(
            axis, neg_l, g_rows, archive_k,
            sentinel=n_shards * local_n - 1)
        cg = jax.lax.psum(counters, axis)
        cg = jnp.concatenate([
            cg[:4], jnp.maximum(jnp.minimum(cg[4:5], 1), over_g[None])])
        payload = _lifecycle_payload(-neg_m, rows_m, buf[:prune_cap], cg)
        return arena, edges, payload

    def _read_local(*args):
        return _local(*args)[2]

    state_specs = ArenaState(
        emb=P(axis, None), salience=P(axis), timestamp=P(axis),
        last_accessed=P(axis), access_count=P(axis), type_id=P(axis),
        shard_id=P(axis), tenant_id=P(axis), alive=P(axis),
        is_super=P(axis))
    edge_specs = EdgeState(
        src=P(axis), tgt=P(axis), weight=P(axis), co=P(axis),
        last_updated=P(axis), alive=P(axis), tenant_id=P(axis))
    in_specs = (state_specs, edge_specs, P(None), P(None),
                P(), P(), P(), P(), P(), P(), P())
    mapped = shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=(state_specs, edge_specs, P(None)),
                       check_vma=False)
    mapped_read = shard_map(_read_local, mesh=mesh, in_specs=in_specs,
                            out_specs=P(None), check_vma=False)
    return LifecycleShardedKernels(
        sweep=jax.jit(mapped, donate_argnums=(0, 1)),
        sweep_copy=jax.jit(mapped),
        read=jax.jit(mapped_read))


def _arena_apply_boosts(state: ArenaState, rows: jax.Array,
                        acc_cnt: jax.Array, nbr_cnt: jax.Array,
                        now_vals: jax.Array, acc_boost: jax.Array,
                        nbr_boost: jax.Array) -> ArenaState:
    """Deferred boost flush: cache-hit chat turns accumulate (access,
    neighbor) boost COUNTS on the host instead of paying a device dispatch
    per turn; this scatter applies many turns' worth in one program.
    Positive capped adds commute, so applying the summed counts equals the
    serial per-turn sequence. ``now_vals`` carries each row's latest
    queue-time timestamp (padding rows use -inf so ``.max`` is a no-op)."""
    sal = state.salience.at[rows].add(
        acc_cnt.astype(jnp.float32) * acc_boost
        + nbr_cnt.astype(jnp.float32) * nbr_boost)
    return state.replace(
        salience=jnp.minimum(sal, 1.0),
        access_count=state.access_count.at[rows].add(acc_cnt),
        last_accessed=state.last_accessed.at[rows].max(now_vals))


arena_apply_boosts, arena_apply_boosts_copy = _donated_pair(
    _arena_apply_boosts)


@functools.partial(jax.jit, static_argnames=("max_neighbors",))
def edges_neighbors(state: EdgeState, rows: jax.Array, min_weight: jax.Array,
                    max_neighbors: int = 32) -> Tuple[jax.Array, jax.Array]:
    """Bidirectional neighbor lookup for a batch of node rows.

    Returns (neighbor_rows [B, max_neighbors] sentinel=-1, weights). Replaces
    the O(E) per-node scan in ``memory_shard.py:54-62``."""
    src, tgt = state.src, state.tgt
    live = state.alive & (state.weight >= min_weight)

    def one(row):
        out_mask = live & (src == row)
        in_mask = live & (tgt == row)
        cand = jnp.where(out_mask, tgt, jnp.where(in_mask, src, -1))
        w = jnp.where(out_mask | in_mask, state.weight, NEG_INF)
        top_w, idx = jax.lax.top_k(w, max_neighbors)
        neigh = jnp.where(top_w > NEG_INF / 2, cand[idx], -1)
        return neigh, jnp.where(top_w > NEG_INF / 2, top_w, 0.0)

    return jax.vmap(one)(rows)
