"""MemoryIndex: the HBM-resident replacement for LanceDB.

The reference delegates ANN search, persistence, and tenant filtering to
LanceDB (``core/vector_store.py``). Here the index is a device-resident arena
(``core.state``): search is one masked matvec + ``lax.top_k`` on the MXU,
tenant isolation is a vectorized mask on the ``tenant_id`` column, and decay /
pruning / importance sweeps are whole-arena elementwise kernels. Durability is
a separate concern (``core.store.ArrowStore``).

This class is the host-side bookkeeping wrapper: string id ↔ row maps, free
lists, capacity growth, and sentinel padding. Everything numeric stays on
device; host transfers are bulk and infrequent.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.paging import PageAllocator
from lazzaro_tpu.ops import graphops
from lazzaro_tpu.plan import Geometry, HbmPlanner
from lazzaro_tpu.reliability import faults
from lazzaro_tpu.reliability.errors import (ArenaPoisoned, DeviceOom,
                                            PlanInfeasible)
from lazzaro_tpu.reliability.guard import (check_not_poisoned,
                                           is_resource_exhausted,
                                           run_guarded)
from lazzaro_tpu.utils.batching import (LRUKernelCache, bucket_size,
                                        decode_topk, empty_results,
                                        fetch_packed, next_pow2, pad_to_pow2,
                                        RequestCarrier, unpack_retrieval)
from lazzaro_tpu.utils.telemetry import (default_registry, peak_bytes,
                                         record_device_counters)


def build_host_csr(edge_keys, id_to_row: Dict[str, int], n: int,
                   min_pad: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side CSR build shared by the single-chip and pod serving paths:
    ``(indptr [n+1] i32, nbr [E_pad] i32)`` over ``n`` arena rows from an
    iterable of ``(src_id, tgt_id)`` edge keys (bidirectional, -1 padded to
    a pow2 bucket, never below ``min_pad`` — callers pass their previous
    pad so a pruned-down edge set can't shrink the bucket and recompile
    the serving program). Built entirely from host bookkeeping — no device
    readback."""
    src_l, dst_l = [], []
    for qsrc, qtgt in edge_keys:
        s = id_to_row.get(qsrc)
        t = id_to_row.get(qtgt)
        if s is None or t is None:
            continue
        src_l.append(s)
        dst_l.append(t)
    if src_l:
        a = np.asarray(src_l, np.int64)
        b = np.asarray(dst_l, np.int64)
        src = np.concatenate([a, b])
        dst = np.concatenate([b, a])
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
    else:
        src = dst = np.zeros((0,), np.int64)
    indptr = np.zeros((n + 1,), np.int32)
    indptr[1:] = np.cumsum(np.bincount(src, minlength=n))
    nbr = np.full((max(8, int(min_pad), next_pow2(len(dst))),), -1,
                  np.int32)
    nbr[:len(dst)] = dst
    return indptr, nbr


def split_csr(indptr: np.ndarray, nbr: np.ndarray, n_shards: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Row-shard a global CSR for the distributed fused serving kernel
    (``state.make_fused_sharded``): shard ``p`` gets the neighbor lists of
    its OWN rows (``[p·L, (p+1)·L)``) with offsets rebased to its slice —
    neighbor ids stay GLOBAL (a neighbor may live on any chip; the kernel
    merges the gathered windows and each owner scatters its own rows).
    Returns ``(indptr_sh [n, L+1] i32, nbr_sh [n, E_max] i32)`` with every
    shard's neighbor array padded to one common pow2 bucket."""
    n_rows = indptr.shape[0] - 1
    assert n_rows % n_shards == 0
    L = n_rows // n_shards
    indptr_sh = np.zeros((n_shards, L + 1), np.int32)
    parts = []
    for p in range(n_shards):
        lo, hi = indptr[p * L], indptr[(p + 1) * L]
        indptr_sh[p] = indptr[p * L:(p + 1) * L + 1] - lo
        parts.append(np.asarray(nbr[lo:hi], np.int32))
    e_max = max(8, next_pow2(max(len(x) for x in parts)))
    nbr_sh = np.full((n_shards, e_max), -1, np.int32)
    for p, x in enumerate(parts):
        nbr_sh[p, :len(x)] = x
    return indptr_sh, nbr_sh


def link_pool_size(worst: int, hint: float) -> int:
    """Edge-slot pool sizing for the compacting fused ingest (ROADMAP
    ceiling #2), shared by the single-chip and pod indexes:
    ``ceil(hint · worst)`` real slots instead of the worst case — a huge
    mostly-rejected batch no longer transiently drains the free list —
    floored at one slot so the overflow machinery (not an empty gather)
    handles a zero hint."""
    h = float(hint)
    if h >= 1.0 or worst <= 0:
        return worst
    return min(worst, max(1, int(np.ceil(max(0.0, h) * worst))))


def link_pool_dev(pool: Sequence[int], padded_len: int, ecap: int):
    """Device view of the link-slot pool for the compacting fused ingest:
    real slots first, sentinel (``ecap``) padding up to the jit-bucketed
    length, and one trailing sentinel entry the kernel routes every
    rejected candidate through."""
    arr = np.full((padded_len + 1,), ecap, np.int32)
    arr[:len(pool)] = pool
    return jnp.asarray(arr)


class _EdgeSlotMap(dict):
    """``(qsrc, qtgt) -> device slot`` edge map with an inline ``by_slot``
    reverse index (ISSUE 19): the prune kernels now return the COMPACTED
    pruned-slot list, and decoding it through ``by_slot`` makes host
    cleanup O(pruned) — the old path re-scanned every live edge's dict
    entry per prune. All single-key mutation funnels through
    ``__setitem__`` / ``__delitem__`` / ``pop``; wholesale replacement
    (checkpoint load, replica hydration) rebuilds the reverse index in
    ``__init__``."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.by_slot: Dict[int, Tuple[str, str]] = {
            slot: key for key, slot in self.items()}

    def __setitem__(self, key, slot) -> None:
        old = super().get(key)
        if old is not None:
            self.by_slot.pop(old, None)
        super().__setitem__(key, slot)
        self.by_slot[slot] = key

    def __delitem__(self, key) -> None:
        slot = dict.pop(self, key)
        self.by_slot.pop(slot, None)

    def pop(self, key, *default):
        if key in self:
            slot = dict.pop(self, key)
            self.by_slot.pop(slot, None)
            return slot
        if default:
            return default[0]
        raise KeyError(key)

    def clear(self) -> None:
        super().clear()
        self.by_slot.clear()


class SemanticCacheHost:
    """Host mirror of the device-resident semantic query-cache ring
    (ISSUE 20), shared by ``MemoryIndex`` and the pod
    ``ShardedMemoryIndex``.

    The DEVICE side is the ``state.SemanticRing`` the fused serving
    kernels probe/substitute/write in-dispatch; this mirror owns
    everything the kernels must NOT pay a readback for:

    - ``valid`` / ``head`` — the slot validity bits and LIFO cursor that
      ride into every dispatch as data. The kernel's writeback contract
      is derivable from the packed readback alone (rank j = the j-th
      miss in batch order, kept = the last R misses, slot =
      ``(head + rank) % R``, head' = ``(head + n_miss) % R``), so
      ``note_readback`` replays it exactly — no extra transfer.
    - the row→slot reverse index — every arena row a cached result
      references maps to the slots caching it, so ingest dedup-merges,
      deletes, tier demotions/promotions and lifecycle prunes can flip
      exactly the stale slots' validity bits (``invalidate_rows``)
      instead of flushing the ring.
    - per-slot tenant ids, so ``invalidate_tenant`` scopes a flush the
      way ``QueryCache.invalidate_results(tenant=...)`` does.

    Invalidation is host-state only: the device ring keeps its (now
    unreachable) entry until the LIFO rotation overwrites it, because
    validity is an input column, not device state.
    """

    def __init__(self, slots: int, dim: int, width: int, threshold: float,
                 block: int, telemetry=None):
        self.slots = max(1, int(slots))
        self.dim = int(dim)
        self.width = max(1, int(width))
        self.threshold = float(threshold)
        self.block = max(1, int(block))
        self.ring = S.init_semantic_ring(self.slots, self.dim, self.width)
        self.valid = np.zeros((self.slots,), bool)
        self.head = 0
        self.slot_tenant = np.full((self.slots,), -1, np.int32)
        self.slot_rows: List[set] = [set() for _ in range(self.slots)]
        self.row_slots: Dict[int, set] = {}
        self.telemetry = telemetry
        self._lock = threading.Lock()

    # ------------------------------------------------------------ dispatch
    def tuple_for(self, mode: str):
        """The ``sem`` kernel operand for one dispatch of serving-family
        ``mode`` — ``(ring, valid, head, threshold, mode_id)`` — or None
        when the family has no semantic id (entries never cross
        families, so a mode flip is an automatic miss)."""
        mid = S.SEM_MODE_IDS.get(mode)
        if mid is None:
            return None
        with self._lock:
            return (self.ring, jnp.asarray(self.valid),
                    jnp.int32(self.head), jnp.float32(self.threshold),
                    jnp.int32(mid))

    def note_readback(self, ring2, sem_col, valid_q, tenants, gate_s,
                      gate_r, ann_s, ann_r) -> None:
        """Replay one dispatch's in-kernel writeback onto the mirror.
        ``sem_col`` is the packed readback's semantic counter (0 = miss,
        1 + slot on a hit); the written slots and the head advance follow
        from it and the batch order alone. Miss queries' result rows
        (live ANN rows + the gate row) feed the row→slot reverse
        index."""
        with self._lock:
            self.ring = ring2
            miss = np.asarray(valid_q, bool) & (np.asarray(sem_col) == 0)
            midx = np.nonzero(miss)[0]
            n_miss = len(midx)
            R = self.slots
            for rank, qi in enumerate(midx):
                if rank < n_miss - R:
                    continue               # rotated over inside the batch
                slot = (self.head + rank) % R
                self._clear_slot(slot)
                live = ann_s[qi] > S.NEG_INF / 2
                rows = {int(r) for r in ann_r[qi][live]}
                if gate_s[qi] > S.NEG_INF / 2:
                    rows.add(int(gate_r[qi]))
                self.slot_rows[slot] = rows
                for r in rows:
                    self.row_slots.setdefault(r, set()).add(slot)
                self.slot_tenant[slot] = int(tenants[qi])
                self.valid[slot] = True
            self.head = (self.head + n_miss) % R
            occ = float(self.valid.sum()) / R
        if self.telemetry is not None:
            self.telemetry.gauge("serve.semantic_ring_occupancy", occ)

    # -------------------------------------------------------- invalidation
    def _clear_slot(self, slot: int) -> None:
        for r in self.slot_rows[slot]:
            s = self.row_slots.get(r)
            if s is not None:
                s.discard(slot)
                if not s:
                    del self.row_slots[r]
        self.slot_rows[slot] = set()
        self.valid[slot] = False
        self.slot_tenant[slot] = -1

    def invalidate_rows(self, rows: Iterable[int]) -> int:
        """Flip validity off for every slot whose cached result touches
        any of ``rows`` (the mutation hooks' entry point: ingest
        dedup-merge targets, deleted rows, tier moves, lifecycle
        prunes). Returns the number of slots evicted."""
        with self._lock:
            hit: set = set()
            for r in rows:
                hit |= self.row_slots.get(int(r), set())
            for s in hit:
                self._clear_slot(s)
        if hit and self.telemetry is not None:
            self.telemetry.bump("serve.semantic_stale_evictions", len(hit))
        return len(hit)

    def invalidate_tenant(self, tid: Optional[int]) -> int:
        """Flip validity off for one tenant's slots (None = all slots):
        the semantic twin of ``QueryCache.invalidate_results``, and the
        new-row ingest hook — a fresh fact can change its tenant's
        top-k, which no row-level index can see."""
        with self._lock:
            if tid is None:
                hit = [s for s in range(self.slots) if self.valid[s]]
            else:
                hit = [s for s in range(self.slots)
                       if self.valid[s] and self.slot_tenant[s] == tid]
            for s in hit:
                self._clear_slot(s)
        if hit and self.telemetry is not None:
            self.telemetry.bump("serve.semantic_stale_evictions", len(hit))
        return len(hit)

    # --------------------------------------------------------- persistence
    def export_arrays(self) -> Dict[str, np.ndarray]:
        """Checkpoint payload: the device ring's leaves plus the mirror's
        validity/tenant columns (the reverse index is derivable — see
        ``import_arrays``)."""
        out = {f"sem_{name}": np.asarray(getattr(self.ring, name))
               for name in ("emb", "tenant", "mode", "stored_k", "nprobe",
                            "gate_on", "gate_s", "gate_r", "ann_s",
                            "ann_r")}
        out["sem_valid"] = self.valid.copy()
        out["sem_slot_tenant"] = self.slot_tenant.copy()
        out["sem_head"] = np.asarray([self.head], np.int32)
        return out

    def import_arrays(self, data) -> bool:
        """Restore from ``export_arrays``. Geometry must match the
        configured ring (slots/dim/width) — a mismatch keeps the fresh
        empty ring (a cold cache, never a wrong one). The row→slot
        reverse index rebuilds from the ring's own ann/gate rows."""
        emb = np.asarray(data["sem_emb"])
        ann_s = np.asarray(data["sem_ann_s"])
        if (emb.shape != (self.slots + 1, self.dim)
                or ann_s.shape != (self.slots + 1, self.width)):
            return False
        self.ring = S.SemanticRing(
            emb=jnp.asarray(emb, jnp.float32),
            tenant=jnp.asarray(np.asarray(data["sem_tenant"], np.int32)),
            mode=jnp.asarray(np.asarray(data["sem_mode"], np.int32)),
            stored_k=jnp.asarray(np.asarray(data["sem_stored_k"],
                                            np.int32)),
            nprobe=jnp.asarray(np.asarray(data["sem_nprobe"], np.int32)),
            gate_on=jnp.asarray(np.asarray(data["sem_gate_on"], bool)),
            gate_s=jnp.asarray(np.asarray(data["sem_gate_s"], np.float32)),
            gate_r=jnp.asarray(np.asarray(data["sem_gate_r"], np.int32)),
            ann_s=jnp.asarray(np.asarray(data["sem_ann_s"], np.float32)),
            ann_r=jnp.asarray(np.asarray(data["sem_ann_r"], np.int32)))
        self.valid = np.asarray(data["sem_valid"], bool).copy()
        self.slot_tenant = np.asarray(data["sem_slot_tenant"],
                                      np.int32).copy()
        self.head = int(np.asarray(data["sem_head"]).reshape(-1)[0])
        ann_s_np = np.asarray(data["sem_ann_s"])
        ann_r_np = np.asarray(data["sem_ann_r"], np.int64)
        gate_s_np = np.asarray(data["sem_gate_s"])
        gate_r_np = np.asarray(data["sem_gate_r"], np.int64)
        self.slot_rows = [set() for _ in range(self.slots)]
        self.row_slots = {}
        for s in range(self.slots):
            if not self.valid[s]:
                continue
            rows = {int(r) for r, sc in zip(ann_r_np[s], ann_s_np[s])
                    if sc > S.NEG_INF / 2}
            if gate_s_np[s] > S.NEG_INF / 2:
                rows.add(int(gate_r_np[s]))
            self.slot_rows[s] = rows
            for r in rows:
                self.row_slots.setdefault(r, set()).add(s)
        return True

    def stats(self) -> Dict[str, object]:
        with self._lock:
            return {
                "slots": self.slots,
                "width": self.width,
                "threshold": self.threshold,
                "occupied": int(self.valid.sum()),
            }


class _StagedSharded(NamedTuple):
    """A pod serving dispatch, staged: the compiled twins and every device
    operand but the state (and the tables made against it at the gate)."""

    kern: S.FusedShardedKernels
    sargs: tuple                  # per-shard CSR, the request carrier
    boosting: bool                # a request asked for boosts: serve twins
    sem_tail: tuple               # the semantic ring's operand, or ()


# The single-chip serving families and their ``core.state`` entry points:
# (donated, copy, read). Names, resolved on ``S`` at every dispatch — the
# dispatch-count tests wrap the module attributes.
_SERVE_KERNELS = {
    "exact": ("search_fused_ragged", "search_fused_ragged_copy",
              "search_fused_ragged_read"),
    "quant": ("search_fused_quant_ragged", "search_fused_quant_ragged_copy",
              "search_fused_quant_ragged_read"),
    "tiered": ("search_fused_tiered_ragged",
               "search_fused_tiered_ragged_copy",
               "search_fused_tiered_ragged_read"),
    "ivf": ("search_fused_ivf_ragged", "search_fused_ivf_ragged_copy",
            "search_fused_ivf_ragged_read"),
    "ivf_tiered": ("search_fused_ivf_tiered_ragged",
                   "search_fused_ivf_tiered_ragged_copy",
                   "search_fused_ivf_tiered_ragged_read"),
    "pq": ("search_fused_pq_ragged", "search_fused_pq_ragged_copy",
           "search_fused_pq_ragged_read"),
    "pq_tiered": ("search_fused_pq_tiered_ragged",
                  "search_fused_pq_tiered_ragged_copy",
                  "search_fused_pq_tiered_ragged_read"),
}


class _ServeRoute(NamedTuple):
    """What one serving dispatch runs — ``MemoryIndex._serve_route``'s
    answer, the only place that decides it."""

    mode: str          # a _SERVE_KERNELS family; "sharded_<base>" under a mesh
    k_bucket: int      # the static k ceiling every request clamps to
    tiered: bool       # cold rows exist: the tier-aware programs serve
    coarse_tabs: Optional[tuple]   # the PQ pack (pq*) or IVF pack (ivf*)


class MemoryIndex:
    """Single-chip by default; pass ``mesh`` to row-shard every arena column
    over a mesh axis — the scaling-book recipe: annotate the shardings, let
    XLA insert the collectives. All kernels (search matmul, scatter
    mutations, decay sweeps, link matmuls) are plain jnp under jit, so GSPMD
    partitions them automatically; the state setters re-constrain outputs so
    a kernel can never silently replicate the arena. This scales the FULL
    orchestrator (edges, decay, linking included) — ``ShardedMemoryIndex``
    remains the lean retrieval-only variant with tenant→partition affinity."""

    def __init__(self, dim: int, capacity: int = 1024, edge_capacity: int = 8192,
                 dtype=jnp.float32, epoch: Optional[float] = None,
                 mesh=None, shard_axis: str = "data",
                 int8_serving: bool = False, ivf_nprobe: int = 0,
                 ivf_online: bool = True, ivf_member_cap_factor: int = 4,
                 ivf_online_eta: float = 1.0,
                 pq_serving: bool = False, coarse_slack: int = 8,
                 paged: bool = False, page_rows: int = 4096,
                 telemetry=None, telemetry_hbm: bool = False,
                 serve_k_max: int = 128,
                 serve_pad_granularity: int = 8,
                 serve_kernel_cache_max: int = 8,
                 ingest_sharded: bool = True,
                 dispatch_retry_max: int = 2,
                 dispatch_retry_backoff_s: float = 0.005,
                 hbm_budget_bytes: int = 0,
                 hbm_headroom_fraction: float = 0.1,
                 plan_max_splits: int = 16,
                 plan_calibration_path: Optional[str] = None,
                 planner: Optional[HbmPlanner] = None,
                 semantic_cache: bool = False,
                 semantic_cache_slots: int = 64,
                 semantic_cache_threshold: float = 0.985,
                 semantic_cache_block: int = 16):
        self.dim = dim
        self.dtype = dtype
        # Donation-safe recovery (ISSUE 10): a failed donated dispatch
        # whose input survived retries through the non-donating *_copy
        # twin (bounded, with backoff); one whose input was consumed
        # marks the index poisoned and every later touch raises the
        # typed ArenaPoisoned instead of XLA's "Array has been deleted".
        self.dispatch_retry_max = max(0, int(dispatch_retry_max))
        self.dispatch_retry_backoff_s = float(dispatch_retry_backoff_s)
        self._poisoned = False
        # Serving telemetry (ISSUE 6): spans + device counters land in this
        # registry (the process-wide default unless the owner — typically
        # MemorySystem — injects its own). ``telemetry_hbm=True``
        # additionally AOT-lowers each fused serving geometry's read twin
        # once to record its ``memory_analysis()`` peak-HBM gauge — one
        # extra compile per (mode × k-bucket) key, zero extra dispatches,
        # so it's opt-in (bench and the HBM-budget CI gate turn it on).
        self.telemetry = telemetry if telemetry is not None \
            else default_registry()
        self.telemetry_hbm = bool(telemetry_hbm)
        self._hbm_recorded: set = set()
        # Admission-time HBM planner (ISSUE 11): every fused serving/
        # ingest geometry clears it BEFORE compiling — admit fused, chunk
        # the arena scan inside the one dispatch, split the query batch
        # into PLANNED sub-dispatches, or reject typed (PlanInfeasible).
        # hbm_budget_bytes == 0 (default) disables it entirely.
        self.planner = planner if planner is not None else HbmPlanner(
            budget_bytes=hbm_budget_bytes,
            headroom_fraction=hbm_headroom_fraction,
            telemetry=self.telemetry,
            granularity=max(1, int(serve_pad_granularity)),
            max_splits=plan_max_splits,
            calibration_path=plan_calibration_path)
        # Coarse-stage over-fetch slack, shared by every two-stage serving
        # path (ISSUE 3 satellite): the IVF member scan over-fetches
        # k + slack before the host dedup trims (a reused slot can sit in
        # both a stale member slot and the residual), and the int8 fused
        # path over-fetches k + slack coarse candidates before the exact
        # rescore (absorbing the ~1e-2 quantization ranking error at the
        # k boundary). One knob, one guarantee: neither path can return
        # fewer than k live rows.
        self.coarse_slack = max(0, int(coarse_slack))
        # Int8 serving shadow (ops/quant.py): half the HBM bytes per scan.
        # Exact-path callers (dedup/merge thresholds) bypass it. The shadow
        # re-quantizes lazily, invalidated ONLY by embedding-mutating ops
        # (add / grow) — metadata sweeps (decay, boost, access counts,
        # delete's alive flip) leave the vectors untouched, and the alive/
        # tenant mask is taken fresh from the master at every search, so
        # they must not trigger a ~3 GB full-arena requant. Composes with
        # the mesh: the per-row shadow shards exactly like the master, so
        # each chip scans its local int8 rows and only the k-candidate
        # combine crosses ICI (ops/topk.py make_sharded_int8_topk).
        self.int8_serving = bool(int8_serving)
        self._int8_shadow = None           # (q [N,d] i8, scale [N] f32)
        self._shadow_quantize = None       # the mesh's build program
        self._int8_dirty = True
        # IVF coarse stage (ops/ivf.py): nprobe > 0 routes serving searches
        # through centroid prefilter + member gather. Rows added after a
        # build serve EXACTLY from a residual list until the next rebuild
        # (sealed/fresh split). delete() un-routes freed MEMBER slots, so a
        # re-used slot joins the fresh residual (scanned exactly with its
        # new vector) instead of inheriting the dead vector's cluster;
        # sealed-residual slots stay routed (the residual already scans the
        # current vector). Nothing is ever dropped. Coarse routing is
        # geometry-global; tenant isolation is the fine-stage mask.
        if ivf_nprobe and mesh is not None:
            import warnings
            warnings.warn(
                "ivf_serving is single-chip only (the mesh path searches "
                "the exact arena through shard_map); the flag is ignored "
                "under a mesh", stacklevel=3)
        self.ivf_nprobe = int(ivf_nprobe) if mesh is None else 0
        # Concurrency contract (advisor r4): writers (add/delete/
        # ivf_maintenance, all on the single-writer side) publish the build
        # and its fresh-row list as ONE immutable tuple, so a concurrent
        # reader can never pair a new member table with an old residual (or
        # vice versa). ``_ivf_routed``/``_ivf_stale`` are writer-side
        # bookkeeping only — readers never touch them.
        self._ivf_pack: Optional[tuple] = None  # (IvfIndex, fresh_rows tuple)
        self._ivf_routed = None            # np bool [rows]: in members/residual
        self._ivf_in_residual = None       # np bool [rows]: in SEALED residual
        self._ivf_stale = 0                # member slots invalidated by delete
        self._ivf_res_cache = None         # (ivf, fresh, residual buf, dev)
        # Online IVF maintenance (ISSUE 12): with a seeded build published,
        # the LIVE coarse tables — ``(cent [C,d] f32, members [C,M] i32,
        # counts [C] i32)`` — are device state the fused ingest kernels
        # donate and update in the SAME dispatch that scores the batch
        # (assignment, member append, mini-batch centroid step). Serving
        # reads these live tables directly; the sealed residual shrinks to
        # build-overflow + add()-path rows + member-capacity spills.
        # ``ivf_maintenance`` demotes to a rare re-seed.
        self.ivf_online = bool(ivf_online) and self.ivf_nprobe > 0
        self.ivf_member_cap_factor = max(1, int(ivf_member_cap_factor))
        self.ivf_online_eta = float(ivf_online_eta)
        self._ivf_dev: Optional[tuple] = None  # (cent, members, counts)
        # Fused IVF serving tables (search_fused_requests): the exact-scan
        # extras array (sealed residual + fresh rows + super rows) cached
        # by snapshot identity like the residual cache.
        self._ivf_serve_cache = None
        # Super-node rows by host bookkeeping, so the fused IVF kernel's
        # extras always carry EVERY super row (exact gate verdicts even
        # when no centroid routes near a super node). The frozen tuple is
        # rebuilt on change only — cache keys compare it by identity.
        self._super_rows: set = set()
        self._super_rows_frozen: tuple = ()
        # Observability: fused-ingest batches whose accepted links overflowed
        # the hinted edge-slot pool (each costs one host-side retry insert).
        self.link_pool_overflows = 0
        # IVF-PQ member storage (ops/pq.py): the member scan reads m-byte
        # codes instead of d·2-byte rows and the shortlist is re-scored
        # exactly from the master. Codebook trains in ivf_maintenance,
        # which also runs the ONE full encode (ISSUE 16) — from then on
        # the published pack is complete and self-maintaining: the fused
        # ingest's in-dispatch ``_pq_scatter`` encodes every accepted
        # batch, non-fused writers patch exactly their own rows via
        # ``_pq_encode_rows``, and grow pads the slab in place. The old
        # ``_pq_dirty`` offline full re-encode is gone. Book and codes
        # are published as ONE tuple — codes are meaningless against any
        # other book, so a reader must never pair them across a retrain.
        self.pq_serving = bool(pq_serving) and self.ivf_nprobe > 0
        self._pq_pack: Optional[tuple] = None  # (PQCodebook, codes | None)
        self.mesh = mesh
        self.shard_axis = shard_axis
        self._n_parts = int(mesh.shape[shard_axis]) if mesh is not None else 1
        # Pod-scale fused ingest (ISSUE 9): under a mesh the whole
        # ``ingest_dedup_fused`` program runs as ONE distributed shard_map
        # dispatch (state.make_ingest_fused_sharded) — shard-local dedup/
        # link scans, one all_gather merge, owner-chip-local scatters —
        # instead of letting GSPMD partition the plain jit kernel (which
        # re-replicates the candidate tensors chip-to-chip every batch).
        # Write throughput then scales with the mesh the way read
        # throughput has since PR 5.
        self.ingest_sharded = bool(ingest_sharded) and mesh is not None
        self._ingest_sharded_cache = LRUKernelCache(serve_kernel_cache_max)
        # Device dispatches on the ingest path (fused or classic mutation
        # kernels) — the measured ``dispatches_per_conversation`` counter
        # bench and the jit-counter tests read.
        self.ingest_dispatch_count = 0
        # Lifecycle-sweep dispatch counter (ISSUE 19) — one call == one
        # device program (single chip or distributed); the jit-counter
        # tests and bench_lifecycle read ``dispatches_per_sweep`` off it.
        self.lifecycle_dispatch_count = 0
        # Compaction-bucket high-water mark (see _prune_cap): grows-only
        # so a draining edge pool never recompile-thrashes the sweep.
        self._prune_cap_hwm = 0
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._row_sharding = NamedSharding(mesh, P(shard_axis))
            self._mat_sharding = NamedSharding(mesh, P(shard_axis, None))
        # Zero-copy mutation gate (see _apply_arena): readers snapshot the
        # state under this lock, writers check sole ownership and dispatch
        # under it. Never held across a device readback.
        self._state_lock = threading.RLock()
        # Timestamps are stored relative to this epoch so f32 keeps sub-second
        # precision (raw unix seconds ~1.7e9 would quantize to ~2 minutes).
        self.epoch = float(epoch if epoch is not None else time.time())
        capacity = self._round_capacity(capacity)
        edge_capacity = self._round_capacity(edge_capacity, block=False)
        # Paged embedding arena (ISSUE 17): the master emb becomes a
        # fixed-size-page HBM pool behind an int32 ``row_map`` indirection
        # with a device-side free list — delete/demote push slots back
        # (real reclaimed capacity), logical growth is O(metadata) and
        # never copies the pool. Single-chip only for the DEVICE layout:
        # the pod path keeps the dense per-chip arena (ROADMAP residual).
        if paged and mesh is not None:
            import warnings
            warnings.warn(
                "paged arena is single-chip only (the pod path keeps the "
                "dense per-chip device layout); the flag is ignored under "
                "a mesh", stacklevel=3)
            paged = False
        self.paged = bool(paged)
        self.page_rows = max(1, int(page_rows))
        if self.paged:
            # initial pool = logical capacity rounded up to whole pages
            # (dense-equivalent HBM at t0; the pool only grows when the
            # LIVE set outgrows it, so paged peak ≤ dense peak by design)
            pool_slots = -(-capacity // self.page_rows) * self.page_rows
            self.state, self._ptable = S.init_arena_paged(
                capacity, dim, pool_slots, dtype)
            self._pager = PageAllocator(capacity, pool_slots,
                                        self.page_rows)
        else:
            self.state = self._init_rows(self._make_arena, capacity)
            self._ptable = None
            self._pager = None
        self.edge_state = self._init_rows(S.init_edges, edge_capacity)
        self._free_rows: List[int] = list(range(capacity - 1, -1, -1))
        self._free_edge_slots: List[int] = list(range(edge_capacity - 1, -1, -1))
        self.id_to_row: Dict[str, int] = {}
        self.row_to_id: Dict[int, str] = {}
        self.edge_slots: _EdgeSlotMap = _EdgeSlotMap()
        self._tenants: Dict[str, int] = {}
        self._shards: Dict[str, int] = {}
        self.tenant_nodes: Dict[str, set] = {}
        # Fused serving: per-query k/cap/nprobe ride as int32 device
        # columns, the kernels compute to the serve_k_max ceiling and mask
        # per query — one compiled kernel per (mode × geometry), any mix
        # of request shapes.
        self.serve_k_max = max(1, int(serve_k_max))
        self.serve_pad_granularity = max(1, int(serve_pad_granularity))
        # Semantic query cache (ISSUE 20): the device ring + host mirror.
        # Ring width = the widest candidate window any family substitutes
        # (the k ceiling + the tiered slack), so ONE ring serves every
        # kernel family; a batch whose window overflows it (cap_take above
        # serve_k_max) just skips the probe for that dispatch.
        self._sem_host = None
        if semantic_cache:
            self._sem_host = SemanticCacheHost(
                semantic_cache_slots, dim,
                self.serve_k_max + self.coarse_slack,
                semantic_cache_threshold, semantic_cache_block,
                telemetry=self.telemetry)
        # Distinct fused serving-kernel keys this index has dispatched
        # (mode + statics: the ceilings are fixed, so one per mode); the
        # bench's compile_cache_entries measurement and the
        # kernel.cache_entries{surface="single_fused"} gauge read it.
        self._serve_kernel_keys: set = set()
        # What two READ dispatches in flight together share on the host
        # (ISSUE 30: the scheduler overlaps a full batch's host path with
        # the other batch's pass): the CSR cache, the kernel-key set, the
        # once-keys of the HBM gauge, the distributed program cache.
        self._serve_shared_lock = threading.Lock()
        self._mesh_topk_cache = LRUKernelCache(serve_kernel_cache_max)
        # Distributed fused serving programs (ISSUE 5): under a mesh the
        # whole chat-turn program runs as ONE shard_map dispatch
        # (state.make_fused_sharded), cached per (mode, k ceiling, take,
        # nbr) — one entry per mode while the ceilings stand. LRU-capped
        # (ISSUE 7 satellite) so a changed ceiling evicts, never grows.
        self._fused_sharded_cache = LRUKernelCache(serve_kernel_cache_max)
        # Distributed lifecycle-sweep programs (ISSUE 19): one per
        # (prune_cap bucket, archive_k bucket), same LRU discipline as
        # the serving/ingest factories.
        self._lifecycle_sharded_cache = LRUKernelCache(serve_kernel_cache_max)
        # CSR adjacency shadow for the fused retrieval kernel: a device
        # (indptr, neighbors) pair built from the HOST edge map (edge_slots
        # + id_to_row — no device readback needed), invalidated by edge
        # topology changes only (reinforce/decay touch weights, which the
        # neighbor-boost semantics don't read).
        self._csr_cache = None             # (rows, indptr_dev, nbr_dev)
        self._csr_dirty = True
        # Grows-only nbr pad bucket (see build_host_csr): a maintenance
        # sweep pruning edges must never shrink the serve program's CSR
        # shape mid-flight — that recompile stalls live serving.
        self._csr_pad_hwm = 0
        # Tiered memory (ISSUE 8): None until ``enable_tiering`` attaches a
        # ``tier.TierManager`` (residency column + host cold stores + the
        # watermark pump policy). ``_emb_gen`` is the embedding-write
        # generation counter the pump's gather→scatter window checks so a
        # racing add/ingest can never be clobbered by a stale demotion.
        self.tiering = None
        self._emb_gen = 0
        self._csr_flat_cache = None        # replicated flat CSR (cold finish)

    # Compat views over the atomic pack (tests/bench poke these; assigning
    # ``_ivf = None`` drops the whole build, freeing members + residual).
    @property
    def _ivf(self):
        pack = self._ivf_pack
        return pack[0] if pack is not None else None

    @_ivf.setter
    def _ivf(self, v) -> None:
        # Drop ALL per-build state — the residual cache in particular pins
        # the members table and the padded device residual, so leaving it
        # would defeat the setter's freeing purpose. A non-None assignment
        # reconstructs the routed/in-residual bitmaps from the build
        # (ADVICE r5: leaving them None loses the "never append the same
        # row twice" guard, so repeated add()s of routed rows would grow
        # the fresh residual with duplicates).
        self._ivf_res_cache = None
        self._ivf_serve_cache = None
        self._ivf_stale = 0
        self._pq_pack = None
        if v is None:
            self._ivf_routed = None
            self._ivf_in_residual = None
            self._ivf_pack = None
            self._ivf_dev = None
            return
        self._ivf_routed, self._ivf_in_residual = self._routed_bitmaps(v)
        self._ivf_pack = (v, ())
        self._publish_online_tables(v)

    def _publish_online_tables(self, ivf) -> None:
        """Seed the LIVE device coarse tables from a build (ISSUE 12): the
        build's centroids/members become the arrays the fused ingest
        kernels append through and serving gathers from; ``counts`` is the
        per-cluster append cursor (builds pack members as a dense
        prefix)."""
        if not self.ivf_online:
            self._ivf_dev = None
            return
        from lazzaro_tpu.ops.ivf import online_counts
        # jnp.array COPIES: the live tables must be solely owned so the
        # fused ingest can donate them — aliasing the build's arrays would
        # trip the refcount gate onto the copying twin forever
        self._ivf_dev = (jnp.array(ivf.centroids, jnp.float32),
                         jnp.array(ivf.members, jnp.int32),
                         online_counts(ivf.members))

    def _routed_bitmaps(self, ivf) -> Tuple[np.ndarray, np.ndarray]:
        """(routed, in_sealed_residual) bool bitmaps over arena rows for a
        build — the writer-side bookkeeping ``ivf_maintenance`` and the
        ``_ivf`` compat setter both publish."""
        n = self.state.salience.shape[0]
        routed = np.zeros((n,), bool)
        m = np.asarray(ivf.members).ravel()
        routed[m[(m >= 0) & (m < n)]] = True
        r = np.asarray(ivf.residual)
        in_res = np.zeros((n,), bool)
        in_res[r[(r >= 0) & (r < n)]] = True
        routed |= in_res
        return routed, in_res

    @property
    def _ivf_fresh(self) -> List[int]:
        pack = self._ivf_pack
        return list(pack[1]) if pack is not None else []

    # Compat views over the PQ pack (bench/tests poke these).
    @property
    def _pq_book(self):
        pack = self._pq_pack
        return pack[0] if pack is not None else None

    @_pq_book.setter
    def _pq_book(self, v) -> None:
        self._pq_pack = None if v is None else (v, None)

    @property
    def _pq_codes(self):
        pack = self._pq_pack
        return pack[1] if pack is not None else None

    @_pq_codes.setter
    def _pq_codes(self, v) -> None:
        pack = self._pq_pack
        if pack is not None:
            self._pq_pack = (pack[0], v)

    # -------------------------------------------------------------- sharding
    def _round_capacity(self, capacity: int, block: bool = True) -> int:
        """Row counts include the +1 sentinel. Two alignment rules, BOTH
        satisfied by rounding capacity+1 up to a multiple of
        ``lcm(TOPK_BLOCK, n_parts)`` when both apply: TOPK_BLOCK multiples
        let ``arena_search`` take the blocked Pallas top-k without ever
        padding the embedding matrix (extra rows are ordinary free capacity;
        node arena only — edges never go through the blocked kernel), and
        under a mesh the TOTAL must divide evenly across the axis. The lcm
        (not sequential rounding, which could break block alignment for a
        part count that doesn't divide the block) keeps both invariants."""
        import math

        total = capacity + 1
        multiple = 1
        if block and total >= S.TOPK_BLOCK:
            multiple = S.TOPK_BLOCK
        if self._n_parts > 1:
            multiple = math.lcm(multiple, self._n_parts)
        if multiple > 1:
            total = -(-total // multiple) * multiple
        return total - 1

    def _grown_capacity(self, old_capacity: int, block: bool = True) -> int:
        """Doubling that preserves block and mesh alignment of capacity+1."""
        return self._round_capacity((old_capacity + 1) * 2 - 1, block=block)

    def _make_arena(self, capacity: int) -> S.ArenaState:
        return S.init_arena(capacity, self.dim, self.dtype)

    def _init_rows(self, make, capacity: int):
        """A fresh arena or edge pool (``make(capacity)``); under a mesh
        every column is created in its shards, never whole on one chip."""
        if self.mesh is None:
            return make(capacity)
        return S.sharded_init(make, capacity, self.mesh, self.shard_axis)()

    def _grow_rows(self, make, grow, state, new_capacity: int):
        """``grow(state, new_capacity)``; under a mesh the sharded twin,
        which moves rows between chips shard by shard (rows keep their global
        numbers, so the contiguous blocks are cut anew) and is REFUSED, typed,
        where a chip's free memory cannot hold its new shard beside the old
        one: a pod deployment preallocates (``initial_capacity``)."""
        if self.mesh is None:
            return grow(state, new_capacity)
        leaves = jax.tree_util.tree_leaves(state)
        per_row = sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
                      for a in leaves)
        old_rows = leaves[0].shape[0] // self._n_parts
        # the new shard, one old shard in transit and its shifted copy
        need = per_row * ((new_capacity + 1) // self._n_parts + 2 * old_rows)
        for dev in self.mesh.devices.flat:
            stats = dev.memory_stats() or {}
            if "bytes_limit" not in stats:      # a backend that reports none
                continue
            free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            if need > free:
                raise DeviceOom(
                    f"growing {type(state).__name__} from {leaves[0].shape[0]} "
                    f"to {new_capacity + 1} rows over {self._n_parts} chips "
                    f"needs {need} bytes beside the old shard on every chip; "
                    f"{dev} has {free} free. Preallocate the deployment "
                    f"(initial_capacity) or shard it over more chips")
        return S.grow_sharded(make, state, new_capacity, self.mesh,
                              self.shard_axis)

    def _reshard(self, pytree):
        """Constrain every column to its row sharding (the only 2-D leaf,
        ``emb``, gets P(axis, None)). Shardings are built once in __init__;
        device_put is a no-op when the leaf is already placed correctly."""
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a, self._mat_sharding if a.ndim == 2 else self._row_sharding),
            pytree)

    @property
    def state(self) -> S.ArenaState:
        # The lock makes the snapshot atomic w.r.t. the donation gate: a
        # reader either raises the refcount BEFORE a writer's ownership
        # check (forcing the copying kernel) or blocks for the few µs of
        # the dispatch and sees the new state — never a donated-dead one.
        with self._state_lock:
            return self._state

    @state.setter
    def state(self, s: S.ArenaState) -> None:
        self._state = s if self.mesh is None else self._reshard(s)

    @property
    def edge_state(self) -> S.EdgeState:
        with self._state_lock:
            return self._edge_state

    @edge_state.setter
    def edge_state(self, s: S.EdgeState) -> None:
        self._edge_state = s if self.mesh is None else self._reshard(s)

    # ------------------------------------------------- zero-copy mutations
    # Mutation kernels donate their input state (core/state.py) so XLA
    # scatters in place instead of copying the full HBM arena per small
    # write. Donation deletes EVERY live reference to the old buffers, so
    # the writer must prove it holds the only one: under _state_lock it
    # counts the references to the state pytree and falls back to the
    # non-donating ``*_copy`` twin whenever a concurrent reader (search /
    # sweep / checkpoint snapshot) still holds it. Single-writer hot paths
    # therefore run zero-copy; racing readers cost one classic copy.
    #
    # References to the pytree at the gate when this index is the sole
    # owner: the ``_state`` attribute, the ``cur`` local, and
    # ``sys.getrefcount``'s own argument.
    _SOLE_REFS = 3

    @property
    def poisoned(self) -> bool:
        """True once a donated dispatch consumed this index's state and
        then failed — the HBM arena is unrecoverable in-process. Restore
        from checkpoint and replay the ingest journal."""
        return self._poisoned

    def _guarded(self, call, donated, copying, sole, states, mode):
        """Donation-safe dispatch executor (ISSUE 10): snapshot of the
        refcount-gated handoff goes through ``reliability.run_guarded`` —
        a transient failure retries via the non-donating twin (bounded,
        ``serve.dispatch_retries{mode,reason}`` counted), a consumed
        input poisons the index and raises typed."""
        check_not_poisoned(self._poisoned)
        try:
            return run_guarded(call, donated, copying, sole, states,
                               telemetry=self.telemetry, mode=mode,
                               retries=self.dispatch_retry_max,
                               backoff_s=self.dispatch_retry_backoff_s)
        except ArenaPoisoned:
            self._poisoned = True
            raise

    def _apply_arena(self, donated, copying, *args, **kwargs) -> None:
        with self._state_lock:
            cur = self._state
            sole = sys.getrefcount(cur) <= self._SOLE_REFS
            out = self._guarded(lambda fn: fn(cur, *args, **kwargs),
                                donated, copying, sole, (cur,), "arena")
            del cur
            self.state = out

    def _apply_edges(self, donated, copying, *args, **kwargs) -> None:
        with self._state_lock:
            cur = self._edge_state
            sole = sys.getrefcount(cur) <= self._SOLE_REFS
            out = self._guarded(lambda fn: fn(cur, *args, **kwargs),
                                donated, copying, sole, (cur,), "edges")
            del cur
            self.edge_state = out

    # ------------------------------------------------------- paged arena
    def _ptable_sole(self, pt) -> bool:
        # the PageTable's slot in ``_ptable`` plus getrefcount's argument;
        # a checkpoint snapshot holding the stack forces the copying twin
        return (pt is None
                or sys.getrefcount(pt.free_slots) <= self._SOLE_SHADOW_REFS)

    def _apply_arena_paged(self, donated, copying, *args, replay=None):
        """Paged twin of ``_apply_arena``: dispatch a ``(state, ptable,
        *args) -> (state, ptable, count)`` kernel under the ownership
        gate, store both, and REPLAY the same free-list op on the host
        mirror inside the same critical section (device ops execute in
        dispatch order; replaying under the lock keeps the mirror's order
        identical). Returns ``replay``'s result (the mirror's pop/push
        count)."""
        with self._state_lock:
            cur, pt = self._state, self._ptable
            sole = (sys.getrefcount(cur) <= self._SOLE_REFS
                    and self._ptable_sole(pt))
            out = self._guarded(lambda fn: fn(cur, pt, *args),
                                donated, copying, sole, (cur, pt), "arena")
            del cur, pt
            self.state = out[0]
            self._ptable = out[1]
            mirror = replay(self._pager) if replay is not None else None
        self._page_gauges()
        return mirror

    def _page_gauges(self) -> None:
        """Refresh the ``arena.pages_*`` occupancy gauges from the host
        mirror — pure bookkeeping, no device readback."""
        pager = self._pager
        if pager is None or not self.telemetry.enabled:
            return
        total, free, frag = pager.page_stats()
        tel = self.telemetry
        tel.gauge("arena.pages_total", total)
        tel.gauge("arena.pages_free", free)
        tel.gauge("arena.fragmentation", frag)

    def _ensure_pool(self, rows: Sequence[int]) -> None:
        """Pre-dispatch pool-capacity check: count the batch's NEW slot
        bindings against the mirror's free stack and grow the pool (by
        whole pages, at least doubling — amortized O(1)) BEFORE the
        dispatch, so the in-kernel prefix-sum pop can never run dry."""
        pager = self._pager
        if pager is None:
            return
        need, seen = 0, set()
        for r in rows:
            r = int(r)
            if r >= pager.capacity or r in seen:
                continue
            seen.add(r)
            if pager.slot_of(r) < 0:
                need += 1
        target = pager.need_grow(need)
        if not target:
            return
        with self._state_lock:
            new_state, new_pt = S.grow_pool(self._state, self._ptable,
                                            target)
            self.state = new_state
            self._ptable = new_pt
            pager.grow_pool(target)
            # physical emb buffer moved: abort racing pump windows (slot
            # BINDINGS are preserved, but the gather address changed)
            self._emb_gen += 1
        self.telemetry.bump("arena.pool_grows")
        self._page_gauges()

    def _note_page_tail(self, page_host, mirror) -> None:
        """Account the free-list leaves riding the packed ingest readback
        (ISSUE 17): pop count, post-pop stack depth, overflow flag. The
        host mirror replayed the same op at dispatch time, so the device
        values are a parity ASSERTION, not a sync — a mismatch is counted
        and pinned to zero by the parity tests."""
        tel = self.telemetry
        pops = int(page_host[0][0, 0])
        tel.bump("arena.page_pops", pops)
        if int(page_host[2][0, 0]):
            tel.bump("arena.page_overflows")
        if mirror is not None and mirror != (pops, int(page_host[1][0, 0])):
            tel.bump("arena.page_mirror_mismatches")
        self._page_gauges()

    def _emb_logical(self, st: S.ArenaState):
        """Logical ``[cap+1, d]`` view of the embeddings for the non-fused
        maintenance paths (IVF build, PQ full encode, fallback coarse
        search) — a gather through ``row_map`` when paged, the master
        itself when dense. The fused kernels never call this; they route
        each row access through ``S._phys`` instead."""
        return st.emb if st.row_map is None else st.emb[st.row_map]

    def _ingest_shadow_arg(self, sharded_ok: bool = False):
        """Int8 shadow to thread through the fused ingest program for
        incremental code maintenance, or None when there is nothing valid
        to maintain (int8 off, shadow dirty/absent, or the arena grew
        since the shadow was built). Under a mesh only the SHARDED ingest
        program maintains the shadow (``sharded_ok=True`` — the shadow
        row-shards with the master and the scatter is owner-chip-local);
        the GSPMD fallback marks it dirty instead. Caller holds
        _state_lock."""
        mesh_blocked = self.mesh is not None and not sharded_ok
        if not self.int8_serving or mesh_blocked or self._int8_dirty:
            return None
        shadow = self._int8_shadow
        if (shadow is None
                or shadow[0].shape[0] != self._state.salience.shape[0]):
            return None
        return shadow

    # References to a shadow ARRAY at the gate when no serve holds it: the
    # ``(q8, scale)`` tuple's slot plus getrefcount's own argument. A
    # reader that snapshotted the shadow (``_int8_shadow_for`` hands out
    # refs under the lock) raises this and forces the copying twin.
    _SOLE_SHADOW_REFS = 2

    def _shadow_sole(self, shadow) -> bool:
        return (shadow is None
                or (sys.getrefcount(shadow[0]) <= self._SOLE_SHADOW_REFS
                    and sys.getrefcount(shadow[1]) <= self._SOLE_SHADOW_REFS))

    def _ivf_online_arg(self):
        """The live ``(cent, members, counts)`` coarse tables to thread
        through the fused ingest program for in-dispatch maintenance, or
        None when there is nothing to maintain (online IVF off, no seeded
        build yet, or the pod-index mesh path — ``ivf_serving`` is
        single-chip). Caller holds ``_state_lock``."""
        if not self.ivf_online or self.mesh is not None:
            return None
        return self._ivf_dev

    def _ivf_sole(self, ivf) -> bool:
        # the _ivf_dev tuple's slot + getrefcount's argument; a serving
        # dispatch holding the members/centroids forces the copying twin
        # (indexing, not iteration — a loop variable would inflate the
        # count and pin the gate on the copying twin forever)
        return (ivf is None
                or (sys.getrefcount(ivf[0]) <= self._SOLE_SHADOW_REFS
                    and sys.getrefcount(ivf[1]) <= self._SOLE_SHADOW_REFS
                    and sys.getrefcount(ivf[2]) <= self._SOLE_SHADOW_REFS))

    def _store_ivf_dev(self, new_ivf) -> None:
        if new_ivf is not None:
            self._ivf_dev = tuple(new_ivf)

    def _pq_ingest_arg(self):
        """The live ``(book_cent, codes)`` PQ pack to thread through the
        fused ingest program for in-dispatch code maintenance (ISSUE 16,
        the PQ twin of ``_ingest_shadow_arg``), or None when there is
        nothing to maintain (PQ off, no published pack yet — the first
        ``ivf_maintenance`` trains AND fully encodes — or a mesh, where
        the pod index threads its own row-sharded pack). Caller holds
        ``_state_lock``."""
        if not self.pq_serving or self.mesh is not None:
            return None
        pack = self._pq_pack
        if pack is None or pack[1] is None:
            return None
        if pack[1].shape[0] != self._state.salience.shape[0]:
            return None
        return (pack[0].centroids, pack[1])

    def _pq_sole(self, pq) -> bool:
        # book_cent is held by the PQCodebook field + the threaded tuple,
        # codes by the pack tuple + the threaded tuple — one slot more
        # than the shadow's gate counts, hence the +1. A serving dispatch
        # holding either array forces the copying twin.
        return (pq is None
                or (sys.getrefcount(pq[0]) <= self._SOLE_SHADOW_REFS + 1
                    and sys.getrefcount(pq[1]) <= self._SOLE_SHADOW_REFS + 1))

    def _store_pq_dev(self, new_pq) -> None:
        """Republish the ingest-maintained PQ pack. The donated dispatch
        consumed the old buffers, so the kernel's returned arrays REPLACE
        them under the SAME book object (the kernel passes the codebook
        through unchanged — codes stay paired with the book they were
        encoded against)."""
        if new_pq is None:
            return
        pack = self._pq_pack
        if pack is not None:
            pack[0].centroids = new_pq[0]
            self._pq_pack = (pack[0], new_pq[1])

    def _pq_encode_rows(self, rows: Sequence[int]) -> None:
        """Patch exactly ``rows``' codes in the published pack from the
        CURRENT master (the non-fused writers' twin of the in-kernel
        ``_pq_scatter``): one small encode + scatter, never the offline
        full re-encode. No-op without a complete published pack — the
        next ``ivf_maintenance`` full encode covers those rows."""
        pack = self._pq_pack
        if pack is None or pack[1] is None or not rows:
            return
        st = self.state
        codes = pack[1]
        if codes.shape[0] != st.salience.shape[0]:
            return
        from lazzaro_tpu.ops.pq import encode_pq
        r = jnp.asarray(np.asarray(rows, np.int32))
        new = encode_pq(pack[0].centroids, st.emb[S._phys(st, r)])
        self._pq_pack = (pack[0], codes.at[r].set(new))
        self.telemetry.bump("pq.rows_encoded", len(rows))

    def _apply_fused(self, *args, **kwargs):
        """Dispatch ``S.ingest_fused`` over BOTH states (plus the int8
        shadow when it is being incrementally maintained, plus the live
        online-IVF coarse tables, plus the PQ pack — ISSUE 16), donating
        only when this index holds the sole reference to each; returns
        ``(link_flat, shadow_maintained, ivf_maintained, pq_maintained,
        page_mirror)`` — the kernel's non-state outputs, which sidecars
        stayed fresh in-kernel, and the host free-list mirror's
        ``(pops, free_top)`` after replaying the batch (None when
        dense)."""
        sharded = self.ingest_sharded and self.mesh is not None
        mirror_rows = kwargs.pop("mirror_rows", None)
        with self._state_lock:
            arena, edges = self._state, self._edge_state
            shadow = self._ingest_shadow_arg(sharded_ok=sharded)
            ivf = self._ivf_online_arg()
            pq = self._pq_ingest_arg()
            pt = None if sharded else self._ptable
            sole = (sys.getrefcount(arena) <= self._SOLE_REFS
                    and sys.getrefcount(edges) <= self._SOLE_REFS
                    and self._shadow_sole(shadow) and self._ivf_sole(ivf)
                    and self._pq_sole(pq) and self._ptable_sole(pt))
            if sharded:
                # Non-dedup ingest under a mesh (ISSUE 12 satellite): the
                # distributed plain-ingest program replaces the GSPMD
                # fallback — ONE distributed dispatch, owner-chip writes.
                k = kwargs.pop("k")
                shard_modes = tuple(kwargs.pop("shard_modes"))
                kern = self._ingest_sharded_kernels(
                    k, shard_modes, shadow is not None, dedup=False)
                state_args = (arena, edges) + (
                    shadow if shadow is not None else ())
                got = self._guarded(
                    lambda fn: self._ingest_dispatch(fn, *state_args,
                                                     *args),
                    kern.ingest, kern.ingest_copy, sole,
                    (arena, edges, shadow), "ingest_sharded")
                if shadow is not None:
                    new_arena, new_edges, q8n, sn, link_flat = got
                    new_shadow = (q8n, sn)
                else:
                    new_arena, new_edges, link_flat = got
                    new_shadow = None
                new_ivf = new_pq = new_pt = None
            else:
                (new_arena, new_edges, new_shadow, new_ivf, new_pq,
                 new_pt, link_flat) = self._guarded(
                    lambda fn: self._ingest_dispatch(fn, arena, edges,
                                                     shadow, ivf, pq, pt,
                                                     *args, **kwargs),
                    S.ingest_fused, S.ingest_fused_copy, sole,
                    (arena, edges, shadow, ivf, pq, pt), "ingest")
            del arena, edges, shadow, ivf, pq, pt
            self.state = new_arena
            self.edge_state = new_edges
            if new_shadow is not None:
                self._int8_shadow = new_shadow
            self._store_ivf_dev(new_ivf)
            self._store_pq_dev(new_pq)
            if new_pt is not None:
                self._ptable = new_pt
            mirror = None
            if self._pager is not None and mirror_rows is not None:
                mirror = (self._pager.alloc(mirror_rows),
                          self._pager.free_top)
        return (link_flat, new_shadow is not None, new_ivf is not None,
                new_pq is not None, mirror)

    # ------------------------------------------------------------------ ids
    def tenant_id(self, name: str) -> int:
        if name not in self._tenants:
            self._tenants[name] = len(self._tenants)
        return self._tenants[name]

    def shard_id(self, name: str) -> int:
        if name not in self._shards:
            self._shards[name] = len(self._shards)
        return self._shards[name]

    @property
    def capacity(self) -> int:
        return self.state.capacity

    def __len__(self) -> int:
        return len(self.id_to_row)

    def stats(self) -> Dict[str, object]:
        """Public observability surface (keeps dashboards off private
        bookkeeping)."""
        return {
            "rows": len(self.id_to_row),
            "capacity": self.state.capacity,
            "edge_capacity": self.edge_state.capacity,
            "edges": len(self.edge_slots),
            "dim": self.dim,
            "dtype": str(np.dtype(self.dtype)),
            "tenants": len(self._tenants),
            "link_pool_overflows": self.link_pool_overflows,
            "int8_serving": self.int8_serving,
            "ivf": (f"nprobe={self.ivf_nprobe}, "
                    f"{'built' if self._ivf is not None else 'pending'}"
                    + (", online" if self.ivf_online
                       and self._ivf_dev is not None else "")
                    + (", pq" if self.pq_serving else "")
                    if self.ivf_nprobe else None),
            "mesh": (f"{self._n_parts}x {self.shard_axis}"
                     if self.mesh is not None else None),
            "tier": (self.tiering.stats() if self.tiering is not None
                     else None),
            "paged": (self._page_block() if self._pager is not None
                      else None),
            "semantic_cache": (self._sem_host.stats()
                               if self._sem_host is not None else None),
        }

    def semantic_invalidate(self, tenant: Optional[str] = None) -> int:
        """Evict the semantic query cache's entries for ``tenant`` (None
        = every tenant): the device-ring twin of
        ``QueryCache.invalidate_results``. Host-mutation paths that
        bypass the index's own hooks (external edits, manual repair)
        should call this; the built-in mutators (``add``, ingest,
        ``delete``, tier moves) already invalidate exactly. Returns the
        number of ring slots evicted."""
        if self._sem_host is None:
            return 0
        if tenant is None:
            return self._sem_host.invalidate_tenant(None)
        tid = self._tenants.get(tenant)
        if tid is None:
            return 0
        return self._sem_host.invalidate_tenant(tid)

    def _page_block(self) -> Dict[str, object]:
        pager = self._pager
        pages_total, pages_free, frag = pager.page_stats()
        return {
            "page_rows": pager.page_rows,
            "pool_rows": pager.pool_slots,
            "pages_total": pages_total,
            "pages_free": pages_free,
            "fragmentation": round(frag, 4),
            "pops_total": pager.pops_total,
            "pushes_total": pager.pushes_total,
        }

    # ------------------------------------------------------- tiered memory
    def enable_tiering(self, hot_budget_rows: int, **kw):
        """Attach a :class:`tier.TierManager`: a per-row residency column,
        host cold stores (one per mesh partition), and the watermark/
        hysteresis demotion policy. Serving switches to the tiered fused
        program the moment any row is cold: the coarse scan covers the
        whole corpus from the (always-maintained) shadow — int8 codes,
        or the m-byte PQ slab under ``pq_serving`` (ISSUE 16 lifted the
        old incompatibility: a demoted row's PQ codes stay valid because
        the incremental scatter never touches them, and the rare re-seed
        re-encode patches them from the host cold store) — hot-only turns
        stay ONE dispatch, cold-hit turns pay one bounded finish
        dispatch. Returns the manager (also at ``self.tiering``)."""
        from lazzaro_tpu.tier import TierManager

        self.tiering = TierManager(self, hot_budget_rows, **kw)
        return self.tiering

    def _tiered_active(self) -> bool:
        return self.tiering is not None and self.tiering.cold_count > 0

    def _flat_csr_for(self):
        """FLAT (single-chip layout) device CSR for the tiered cold-finish
        kernel. Single-chip this IS ``_csr_for``'s cache; under a mesh the
        per-shard split the distributed kernel wants is useless to the
        finish (plain jnp under jit, GSPMD-partitioned), so a replicated
        flat pair is built and cached against the split cache's identity."""
        st = self.state
        if self.mesh is None:
            return self._csr_for(st)
        self._csr_for(st)                  # refresh the split cache first
        key = id(self._csr_cache)
        cache = self._csr_flat_cache
        if cache is not None and cache[0] == key:
            return cache[1], cache[2]
        indptr, nbr = build_host_csr(list(self.edge_slots.keys()),
                                     self.id_to_row, st.salience.shape[0])
        dev = (jnp.asarray(indptr), jnp.asarray(nbr))
        self._csr_flat_cache = (key, dev[0], dev[1])
        return dev

    # ---------------------------------------------------------------- nodes
    def _alloc_rows(self, n: int) -> List[int]:
        while len(self._free_rows) < n:
            old_cap = self.state.capacity
            new_cap = self._grown_capacity(old_cap)
            if self._pager is not None:
                # copy-free growth (ISSUE 17): metadata-only realloc; the
                # emb pool is untouched and grows separately, by pages,
                # only when the LIVE set needs the slots (_ensure_pool)
                self.state = S.grow_arena_paged(self.state, new_cap)
                self._pager.grow_capacity(new_cap)
            else:
                self.state = self._grow_rows(self._make_arena, S.grow_arena,
                                             self.state, new_cap)
            self._int8_dirty = True        # logical emb shape changed
            pack = self._pq_pack
            if pack is not None and pack[1] is not None:
                # pad the code slab in place of a full re-encode: grown
                # rows are free (not alive) until written, and every
                # writer patches its own rows' codes
                codes = pack[1]
                grown = jnp.zeros((new_cap + 1, codes.shape[1]), jnp.uint8)
                self._pq_pack = (pack[0],
                                 grown.at[:codes.shape[0]].set(codes))
            self._emb_gen += 1
            if self.tiering is not None:
                self.tiering.on_grow(new_cap + 1)
            self._free_rows = list(range(new_cap - 1, old_cap - 1, -1)) + self._free_rows
        return [self._free_rows.pop() for _ in range(n)]

    def add(self, ids: Sequence[str], embeddings: np.ndarray,
            saliences: Sequence[float], timestamps: Sequence[float],
            types: Sequence[str], shard_keys: Sequence[str],
            tenant: str, is_super: Optional[Sequence[bool]] = None) -> List[int]:
        """Batch insert; returns arena rows. Re-adding an existing id updates
        its row in place."""
        n = len(ids)
        if n == 0:
            return []
        if is_super is None:
            is_super = [False] * n
        rows: List[int] = []
        fresh_needed = sum(1 for i in ids if i not in self.id_to_row)
        fresh = self._alloc_rows(fresh_needed)
        fi = 0
        for node_id in ids:
            if node_id in self.id_to_row:
                rows.append(self.id_to_row[node_id])
            else:
                r = fresh[fi]; fi += 1
                self.id_to_row[node_id] = r
                self.row_to_id[r] = node_id
                rows.append(r)

        cap = self.state.capacity
        padded = S.pad_rows(np.asarray(rows, np.int32), cap)
        b = len(padded)

        def pad(vals, fill=0.0, dt=np.float32):
            out = np.full((b,), fill, dt)
            out[:n] = vals
            return out

        emb = np.zeros((b, self.dim), np.float32)
        emb[:n] = np.asarray(embeddings, np.float32).reshape(n, self.dim)
        emb[n:, 0] = 1.0  # sentinel rows get a unit vector (normalizable)

        tid = self.tenant_id(tenant)
        self.tenant_nodes.setdefault(tenant, set()).update(ids)
        add_args = (
            jnp.asarray(padded),
            jnp.asarray(emb),
            jnp.asarray(pad([float(s) for s in saliences])),
            jnp.asarray(pad([float(t) - self.epoch for t in timestamps])),
            jnp.asarray(pad([S.TYPE_IDS.get(t, 0) for t in types], 0, np.int32)),
            jnp.asarray(pad([self.shard_id(k or "default") for k in shard_keys], -1, np.int32)),
            jnp.asarray(pad([tid] * n, -1, np.int32)),
            jnp.asarray(pad([bool(x) for x in is_super], False, bool)),
        )
        if self._pager is not None:
            self._ensure_pool(rows)
            pops = self._apply_arena_paged(
                S.arena_add_paged, S.arena_add_paged_copy, *add_args,
                replay=lambda p: p.alloc(rows))
            self.telemetry.bump("arena.page_pops", pops)
        else:
            self._apply_arena(S.arena_add, S.arena_add_copy, *add_args)
        self._int8_dirty = True            # emb rows written
        self._pq_encode_rows(rows)         # codes patched, never re-encoded
        self._emb_gen += 1
        self._note_super(rows, [bool(x) for x in is_super])
        self._ivf_note_added(rows)
        if self.tiering is not None:       # a re-added cold row is hot again
            self.tiering.on_rows_written(rows)
        if self._sem_host is not None:     # new facts change tenant top-k
            self._sem_host.invalidate_tenant(tid)
        return rows

    def _note_super(self, rows: Sequence[int], flags: Sequence[bool]) -> None:
        """Track super-node rows from host bookkeeping (``add``/
        ``ingest_batch`` flags, ``delete``). The fused IVF serving kernel
        appends these rows to its exact-scan extras so the in-kernel
        super-gate top-1 sees every super node regardless of centroid
        routing. The frozen tuple is replaced only on a real change —
        serve caches key on its identity."""
        changed = False
        for r, f in zip(rows, flags):
            if f:
                if r not in self._super_rows:
                    self._super_rows.add(r)
                    changed = True
            elif r in self._super_rows:
                self._super_rows.discard(r)
                changed = True
        if changed:
            self._super_rows_frozen = tuple(sorted(self._super_rows))

    def _ivf_note_added(self, rows: Sequence[int]) -> None:
        """Record freshly-written rows in the fresh residual (shared by
        ``add`` and the fused ingest path)."""
        pack = self._ivf_pack
        if not self.ivf_nprobe or pack is None:
            return
        ivf, ivf_fresh = pack
        routed = self._ivf_routed
        if routed is not None and len(routed) < self.state.salience.shape[0]:
            # arena grew since the build: extend the routed bitmap so
            # grown rows can be marked and never double-append to the
            # residual (duplicate rows would surface twice in one top-k)
            grown = np.zeros((self.state.salience.shape[0],), bool)
            grown[:len(routed)] = routed
            self._ivf_routed = routed = grown
        appended = []
        for r in rows:
            if routed is None or not routed[r]:
                appended.append(r)
                if routed is not None:
                    routed[r] = True       # never append the same row twice
        if appended:
            # ONE tuple swap: a concurrent reader sees either the old
            # or the new (build, fresh) pair, never a torn mix
            self._ivf_pack = (ivf, ivf_fresh + tuple(appended))

    def _ivf_note_online(self, rows: Sequence[int], live: Sequence[bool],
                         ivf_host) -> None:
        """Host bookkeeping after an in-dispatch online-IVF update
        (ISSUE 12): rows the kernel appended to their cluster's member
        table are marked routed (they serve from the coarse tables
        immediately — never stale, no residual growth); rows whose
        cluster was FULL (readback position -1) re-insert host-side into
        the exact-scan extras, exactly like link-pool overflow. The
        trailing counters ride the same readback — zero added
        dispatches."""
        pos_w = ivf_host[1]
        appended, spilled = [], []
        for i, (r, lv) in enumerate(zip(rows, live)):
            if not lv:
                continue
            (appended if int(pos_w[i, 0]) >= 0 else spilled).append(r)
        if appended:
            routed = self._ivf_routed
            if routed is not None:
                if len(routed) < self.state.salience.shape[0]:
                    grown = np.zeros((self.state.salience.shape[0],), bool)
                    grown[:len(routed)] = routed
                    self._ivf_routed = routed = grown
                routed[appended] = True
        if spilled:
            self.telemetry.bump("ivf.member_overflows", len(spilled))
            self._ivf_note_added(spilled)
        tel = self.telemetry
        dev = self._ivf_dev
        if dev is not None:
            slots = int(dev[1].shape[0]) * int(dev[1].shape[1])
            tel.gauge("ivf.member_pool_occupancy",
                      float(ivf_host[3][0, 0]) / max(slots, 1))
        tel.bump("ivf.appends", int(ivf_host[4][0, 0]))
        tel.bump("ivf.centroid_shift_ppm", int(ivf_host[5][0, 0]))

    def _ivf_on_demoted(self, rows: Sequence[int]) -> None:
        """Tier-demotion hook (ISSUE 12): demoted rows DROP out of the
        live member tables — their master embedding was just zeroed by
        the commit-then-zero demote, so a member slot pointing at them
        must never feed the exact in-kernel rescore again (the ivf_tiered
        kernel also masks members by the residency column, so this device
        scrub is capacity hygiene plus defense in depth, on the
        background demote path — never a serving dispatch). Member-routed
        rows count toward the re-seed trigger like delete churn; rows
        living in the extras (fresh / sealed residual) stay routed —
        their entries are residency-masked while cold and become valid
        again the moment a promote restores the master row."""
        pack = self._ivf_pack
        if (not self.ivf_online or self._ivf_dev is None or pack is None
                or not rows):
            return
        with self._state_lock:
            dev = self._ivf_dev
            drop = np.zeros((self.state.salience.shape[0],), bool)
            drop[[r for r in rows if r < len(drop)]] = True
            members = dev[1]
            fn = (S.ivf_members_drop
                  if sys.getrefcount(members) <= 3
                  else S.ivf_members_drop_copy)
            new_members = fn(members, jnp.asarray(drop))
            del members
            self._ivf_dev = (dev[0], new_members, dev[2])
        routed = self._ivf_routed
        fresh_set = set(pack[1])
        in_res = self._ivf_in_residual
        for r in rows:
            if routed is None or r >= len(routed) or not routed[r]:
                continue
            if r in fresh_set:
                continue
            if in_res is not None and r < len(in_res) and in_res[r]:
                continue
            routed[r] = False
            self._ivf_stale += 1

    def _ivf_on_promoted(self, rows: Sequence[int]) -> None:
        """Tier-promotion hook (ISSUE 12): a promoted row's exact master
        embedding is back, but its member slot was scrubbed on demotion —
        it re-enters coverage through the exact-scan extras (the next
        ingest-time re-seed folds it back into a cluster). Rows that were
        never scrubbed (extras-resident) are already routed — no-op."""
        if not self.ivf_online or self._ivf_dev is None:
            return
        self._ivf_note_added(rows)

    def ingest_batch(self, ids: Sequence[str], embeddings: np.ndarray,
                     saliences: Sequence[float], timestamps: Sequence[float],
                     types: Sequence[str], shard_keys: Sequence[str],
                     tenant: str, is_super: Optional[Sequence[bool]] = None,
                     merge_ids: Sequence[str] = (),
                     merge_saliences: Sequence[float] = (),
                     chain_pairs: Sequence[Tuple[str, str]] = (),
                     chain_weight: float = 0.5,
                     link_k: int = 3, link_gate: float = 0.5,
                     link_scale: float = 0.8,
                     shard_modes: Sequence[int] = (1, 0),
                     now: Optional[float] = None,
                     link_accept_hint: float = 1.0):
        """Fused zero-copy conversation ingest: insert ``ids``, merge-touch
        ``merge_ids``, link-scan every new row per shard mode, and insert
        the chain edges plus every gate-passing similarity edge — ONE
        donated device dispatch plus ONE packed readback (the unfused
        sequence pays four dispatches and the same readback).

        Edge slots are pre-allocated as a compaction POOL sized by
        ``link_accept_hint`` (ROADMAP ceiling #2): ``ceil(hint · modes·B·k)``
        slots instead of the worst case, the device prefix-sum packs
        accepted links into the pool head, and on the rare batch whose
        acceptance rate beats the hint the overflowed edges — identified
        exactly by their readback positions plus the in-kernel overflow
        flag — are re-inserted host-side (``add_edges``; one extra
        dispatch for that batch only, counted in
        ``link_pool_overflows``). ``hint=1.0`` (default) keeps the
        overflow-free worst case. ``ids`` should be fresh (the
        consolidation contract) — a (src, tgt) link key that already
        exists is skipped host-side defensively, but its pre-written slot
        is only reclaimed, not cleared, until the next write lands on it.

        Returns ``(rows, candidates, created)``:
          rows        — arena rows of ``ids``, insert order
          candidates  — {mode: {id: [(cand_id, score), ...]}} — the full
                        (ungated) lists, same shape as
                        ``link_candidates_multi``
          created     — {mode: [(src_id, tgt_id, weight), ...]} edges the
                        device inserted, already registered in
                        ``edge_slots`` (chain edges are registered too but
                        reported by the caller's own list, not here)
        """
        n = len(ids)
        shard_modes = tuple(shard_modes)
        if n == 0:
            if merge_ids:
                self.merge_touch(merge_ids, merge_saliences, now)
            return [], {sm: {} for sm in shard_modes}, {sm: [] for sm in shard_modes}
        if is_super is None:
            is_super = [False] * n
        rows: List[int] = []
        fresh_needed = sum(1 for i in ids if i not in self.id_to_row)
        fresh = self._alloc_rows(fresh_needed)
        fi = 0
        for node_id in ids:
            if node_id in self.id_to_row:
                rows.append(self.id_to_row[node_id])
            else:
                r = fresh[fi]; fi += 1
                self.id_to_row[node_id] = r
                self.row_to_id[r] = node_id
                rows.append(r)
        tid = self.tenant_id(tenant)
        self.tenant_nodes.setdefault(tenant, set()).update(ids)
        self._ensure_pool(rows)

        t_rows, t_sals = [], []
        for mid, msal in zip(merge_ids, merge_saliences):
            r = self.id_to_row.get(mid)
            if r is not None:
                t_rows.append(r)
                t_sals.append(float(msal))

        # One up-front slot allocation: chains + a worst-case POOL for the
        # gated links. The device prefix-sum compacts accepted links into
        # the pool's leading slots, so the arena only ever sees accepted
        # writes and the unused suffix comes back as one slice. Growth (if
        # any) happens HERE, before sentinel indices are baked into the
        # padded arrays below.
        k_eff = min(link_k, self.state.capacity)
        n_modes = len(shard_modes)
        chain_keys = [(s, t) for s, t in chain_pairs
                      if s in self.id_to_row and t in self.id_to_row]
        pool_need = self._link_pool_size(n_modes * n * k_eff,
                                         link_accept_hint)
        slots = self._alloc_edge_slots(len(chain_keys) + pool_need)
        chain_slot_list = slots[:len(chain_keys)]
        link_pool_list = slots[len(chain_keys):]

        cap = self.state.capacity
        ecap = self.edge_state.capacity
        padded = S.pad_rows(np.asarray(rows, np.int32), cap)
        b = len(padded)

        def pad(vals, fill=0.0, dt=np.float32):
            out = np.full((b,), fill, dt)
            out[:n] = vals
            return out

        emb = np.zeros((b, self.dim), np.float32)
        emb[:n] = np.asarray(embeddings, np.float32).reshape(n, self.dim)
        emb[n:, 0] = 1.0  # sentinel rows get a unit vector (normalizable)

        touch_padded = S.pad_rows(np.asarray(t_rows, np.int32), cap)
        touch_sal = np.zeros((len(touch_padded),), np.float32)
        touch_sal[:len(t_sals)] = t_sals

        c_padded = S.pad_rows(np.asarray(chain_slot_list, np.int32), ecap)
        cb = len(c_padded)
        c_src = np.full((cb,), -1, np.int32)
        c_tgt = np.full((cb,), -1, np.int32)
        c_w = np.zeros((cb,), np.float32)
        for i, (s, t) in enumerate(chain_keys):
            c_src[i] = self.id_to_row[s]
            c_tgt[i] = self.id_to_row[t]
            c_w[i] = chain_weight
        link_pool = self._link_pool_dev(link_pool_list, n_modes * b * k_eff,
                                        ecap)

        now_rel = (now if now is not None else time.time()) - self.epoch
        kind = ("sharded_fused"
                if self.ingest_sharded and self.mesh is not None
                else "fused")
        with self.telemetry.span("ingest." + kind, timer="ingest.dispatch_ms",
                                 labels={"kind": kind}):
            (link_flat, shadow_fresh, ivf_fresh, pq_fresh,
             page_mirror) = self._apply_fused(
                jnp.asarray(padded), jnp.asarray(emb),
                jnp.asarray(pad([float(s) for s in saliences])),
                jnp.asarray(pad([float(t) - self.epoch
                                 for t in timestamps])),
                jnp.asarray(pad([S.TYPE_IDS.get(t, 0) for t in types], 0,
                                np.int32)),
                jnp.asarray(pad([self.shard_id(sk or "default")
                                 for sk in shard_keys], -1, np.int32)),
                jnp.asarray(pad([tid] * n, -1, np.int32)),
                jnp.asarray(pad([bool(x) for x in is_super], False, bool)),
                jnp.asarray(touch_padded), jnp.asarray(touch_sal),
                jnp.asarray(c_padded), jnp.asarray(c_src),
                jnp.asarray(c_tgt),
                jnp.asarray(c_w), link_pool, jnp.int32(len(link_pool_list)),
                jnp.float32(now_rel), jnp.int32(tid),
                jnp.float32(link_gate), jnp.float32(link_scale),
                jnp.float32(self.ivf_online_eta),
                k=k_eff, shard_modes=shard_modes, mirror_rows=rows)
            if not shadow_fresh:
                self._int8_dirty = True
            if not pq_fresh:
                # kernel couldn't thread the pack (mesh fallback / pre-
                # publish): patch exactly this batch's rows host-side
                self._pq_encode_rows(rows)
            self._emb_gen += 1
            self._note_super(rows, [bool(x) for x in is_super])
            if self.tiering is not None:   # a re-added cold row is hot again
                self.tiering.on_rows_written(rows)

            host = fetch_packed(*link_flat)    # the ONE readback
        # Device-side ingest counters riding the same readback (ISSUE 6):
        # overflow flag + accepted-link count + pool-slot occupancy are the
        # trailing broadcast leaves after the per-mode triples (the online
        # IVF leaves, when maintained, trail those — ISSUE 12; the paged
        # free-list leaves are LAST — ISSUE 17).
        if self._pager is not None:
            self._note_page_tail(host[-S.PAGE_INGEST_TAIL:], page_mirror)
            host = host[:-S.PAGE_INGEST_TAIL]
        ctr = host[3 * n_modes:]
        self.telemetry.bump("ingest.dispatches", labels={"kind": kind})
        self._note_ingest_select()
        self.telemetry.bump("ingest.links_accepted", int(ctr[1][0, 0]))
        self.telemetry.bump("ingest.pool_slots_used", int(ctr[2][0, 0]))
        if ivf_fresh:
            self._ivf_note_online(rows, [True] * n, ctr[3:])
        else:
            self._ivf_note_added(rows)
        pool_real = len(link_pool_list)
        candidates: Dict[int, Dict[str, List[Tuple[str, float]]]] = {}
        created: Dict[int, List[Tuple[str, str, float]]] = {}
        reclaim: List[int] = []
        overflowed: List[Tuple[str, str, float]] = []
        consumed = 0
        for mi, sm in enumerate(shard_modes):
            sc, cd, ps = host[3 * mi], host[3 * mi + 1], host[3 * mi + 2]
            out_m: Dict[str, List[Tuple[str, float]]] = {}
            made: List[Tuple[str, str, float]] = []
            for bi in range(n):
                nid = ids[bi]
                pairs = []
                for j in range(k_eff):
                    p = int(ps[bi, j])
                    s = float(sc[bi, j])
                    # a slot no candidate filled holds (NEG_INF, capacity):
                    # the score says so before the row is looked up
                    cid = (self.row_to_id.get(int(cd[bi, j]))
                           if s > S.NEG_INF / 2 else None)
                    if cid is not None:
                        pairs.append((cid, s))
                    if p < 0:
                        continue               # rejected: no slot consumed
                    w = min(1.0, max(0.0, s * link_scale))
                    if p >= pool_real:
                        # accepted by the device gate but past the hinted
                        # pool: the edge was never written (sentinel slot)
                        # — queue it for the host-side retry insert below
                        if cid is not None \
                                and (nid, cid) not in self.edge_slots:
                            overflowed.append((nid, cid, w))
                            made.append((nid, cid, w))
                        continue
                    consumed = max(consumed, p + 1)
                    key = (nid, cid)
                    if cid is not None and key not in self.edge_slots:
                        self.edge_slots[key] = link_pool_list[p]
                        made.append((nid, cid, w))
                    else:
                        # device inserted it but the host won't register the
                        # key (defensive): the slot is reclaimed, not
                        # cleared, until the next write lands on it
                        reclaim.append(link_pool_list[p])
                out_m[nid] = pairs
            candidates[sm] = out_m
            created[sm] = made
        for key, slot in zip(chain_keys, chain_slot_list):
            if key in self.edge_slots:         # defensive: shouldn't happen
                reclaim.append(slot)
            else:
                self.edge_slots[key] = slot
        # the compaction win: the untouched pool suffix comes back whole
        self._free_edge_slots.extend(link_pool_list[consumed:])
        self._free_edge_slots.extend(reclaim)
        self._csr_dirty = True
        if overflowed:
            # the rare overfull batch pays one extra dispatch; the edges
            # land with the same weights/tenant/timestamp they would have
            self.link_pool_overflows += 1
            self.telemetry.bump("ingest.link_pool_overflows")
            self.add_edges(overflowed, tenant, now=now)
        if self._sem_host is not None:     # new facts change tenant top-k
            self._sem_host.invalidate_tenant(tid)
        return rows, candidates, created

    def _link_pool_size(self, worst: int, hint: float) -> int:
        """See module-level :func:`link_pool_size` (shared with the pod
        index)."""
        return link_pool_size(worst, hint)

    def _link_pool_dev(self, pool: List[int], padded_len: int, ecap: int):
        """See module-level :func:`link_pool_dev` (shared with the pod
        index)."""
        return link_pool_dev(pool, padded_len, ecap)

    def _ingest_dispatch(self, fn, *args, **kwargs):
        """The device-program entry point every fused ingest goes through
        — bench and the jit-counter tests wrap it to measure
        ``dispatches_per_conversation`` (one call == one dispatch, single
        chip or distributed)."""
        self.ingest_dispatch_count += 1
        return fn(*args, **kwargs)

    def _ingest_sharded_kernels(self, k: int, shard_modes: Tuple[int, ...],
                                with_shadow: bool, dedup: bool = True
                                ) -> S.IngestShardedKernels:
        """Cached distributed fused-ingest programs per (k, shard-mode
        tuple, shadow-maintained, dedup) key — batch geometry is a jit
        retrace within one program, exactly like the single-chip
        kernels."""
        key = (k, shard_modes, with_shadow, dedup)
        kern = self._ingest_sharded_cache.get(key)
        if kern is None:
            kern = S.make_ingest_fused_sharded(
                self.mesh, self.shard_axis, k=k, shard_modes=shard_modes,
                with_shadow=with_shadow, dedup=dedup)
            self._ingest_sharded_cache.put(key, kern)
            self.telemetry.gauge("kernel.cache_entries",
                                 len(self._ingest_sharded_cache),
                                 labels={"surface": "ingest_sharded"})
        return kern

    def _apply_dedup_fused(self, *args, k, shard_modes, mirror_rows=None):
        """Dispatch the device-dedup fused ingest over BOTH states (plus
        the maintained int8 shadow, online-IVF tables, and PQ pack) under
        the ownership gate (mirror of ``_apply_fused``); returns ``(flat,
        shadow_maintained, ivf_maintained, pq_maintained, page_mirror)``.
        Under a mesh with ``ingest_sharded`` the program is the
        distributed shard_map composition (ONE distributed dispatch; the
        shadow row-shards with the master, so it stays maintained
        in-kernel on the pod path too)."""
        sharded = self.ingest_sharded and self.mesh is not None
        with self._state_lock:
            arena, edges = self._state, self._edge_state
            shadow = self._ingest_shadow_arg(sharded_ok=sharded)
            ivf = self._ivf_online_arg()
            pq = self._pq_ingest_arg()
            pt = None if sharded else self._ptable
            sole = (sys.getrefcount(arena) <= self._SOLE_REFS
                    and sys.getrefcount(edges) <= self._SOLE_REFS
                    and self._shadow_sole(shadow) and self._ivf_sole(ivf)
                    and self._pq_sole(pq) and self._ptable_sole(pt))
            if sharded:
                kern = self._ingest_sharded_kernels(k, tuple(shard_modes),
                                                    shadow is not None)
                if shadow is not None:
                    new_arena, new_edges, q8n, sn, flat = self._guarded(
                        lambda fn: self._ingest_dispatch(
                            fn, arena, edges, shadow[0], shadow[1], *args),
                        kern.ingest, kern.ingest_copy, sole,
                        (arena, edges, shadow), "ingest_sharded")
                    new_shadow = (q8n, sn)
                else:
                    new_arena, new_edges, flat = self._guarded(
                        lambda fn: self._ingest_dispatch(fn, arena, edges,
                                                         *args),
                        kern.ingest, kern.ingest_copy, sole,
                        (arena, edges), "ingest_sharded")
                    new_shadow = None
                new_ivf = new_pq = new_pt = None
            else:
                (new_arena, new_edges, new_shadow, new_ivf, new_pq,
                 new_pt, flat) = self._guarded(
                    lambda fn: self._ingest_dispatch(
                        fn, arena, edges, shadow, ivf, pq, pt, *args, k=k,
                        shard_modes=shard_modes),
                    S.ingest_dedup_fused, S.ingest_dedup_fused_copy, sole,
                    (arena, edges, shadow, ivf, pq, pt), "ingest")
            del arena, edges, shadow, ivf, pq, pt
            self.state = new_arena
            self.edge_state = new_edges
            if new_shadow is not None:
                self._int8_shadow = new_shadow
            self._store_ivf_dev(new_ivf)
            self._store_pq_dev(new_pq)
            if new_pt is not None:
                self._ptable = new_pt
            mirror = None
            if self._pager is not None and mirror_rows is not None:
                mirror = (self._pager.alloc(mirror_rows),
                          self._pager.free_top)
        return (flat, new_shadow is not None, new_ivf is not None,
                new_pq is not None, mirror)

    def _ingest_geometry(self, n: int, link_k: int = 3) -> Geometry:
        return Geometry(
            kind="ingest", mode="ingest", batch=max(1, int(n)),
            rows=self.state.salience.shape[0], dim=self.dim,
            k=max(1, int(link_k)),
            dtype_bytes=int(np.dtype(self.dtype).itemsize),
            mesh_parts=self._n_parts, edge_cap=self.edge_state.capacity,
            link_k=max(1, int(link_k)),
            ivf=1 if self._ivf_online_arg() is not None else 0,
            pq=1 if self._pq_ingest_arg() is not None else 0,
            pool_rows=(self.state.emb.shape[0]
                       if self._pager is not None else 0))

    def plan_ingest(self, n: int, link_k: int = 3):
        """Admission decision for an ``n``-fact fused ingest mega-batch
        (ISSUE 11): the coalescer drain consults this BEFORE building the
        dispatch and splits the mega-batch into ``decision.splits``
        planned sub-batches when the geometry would blow the budget.
        Raises the typed :class:`PlanInfeasible` when no split fits
        (the resident live set alone is over budget)."""
        return self.planner.check_feasible(
            self._ingest_geometry(n, link_k), chunkable=False)

    def ingest_batch_dedup(self, embeddings: np.ndarray,
                           saliences: Sequence[float],
                           timestamps: Sequence[float],
                           types: Sequence[str],
                           shard_keys: Sequence[str],
                           tenant: str,
                           dedup_gate: float,
                           chain_weight: float = 0.5,
                           link_k: int = 3, link_gate: float = 0.5,
                           link_scale: float = 0.8,
                           shard_modes: Sequence[int] = (1, 0),
                           now: Optional[float] = None,
                           link_accept_hint: float = 1.0) -> Optional[dict]:
        """Truly single-round-trip ingest: the dedup probe (masked top-1
        against the pre-add arena + intra-batch gram) that ``_ingest_facts``
        used to pay a separate ``search_batch`` dispatch for runs INSIDE
        the fused program (ROADMAP open item 2). Duplicate facts never
        become nodes — the device merges them into their targets — and
        chain edges connect consecutive LIVE same-shard facts on device.

        Node ids are assigned by the caller AFTER the readback (so the id
        counter advances exactly like the classic path, which only names
        surviving facts): this method dispatches and returns a pending
        dict; ``commit_ingest_dedup`` finishes the host bookkeeping."""
        n = len(saliences)
        shard_modes = tuple(shard_modes)
        if n == 0:
            return None
        if self.planner is not None and self.planner.active:
            # admission gate (ISSUE 11): a geometry no split can fit
            # raises typed BEFORE rows/slots are allocated or anything
            # compiles; mega-batch SPLITTING happens one level up at the
            # coalescer drain (``plan_ingest``)
            self.planner.check_feasible(
                self._ingest_geometry(n, min(link_k, self.state.capacity)),
                chunkable=False)
        rows = self._alloc_rows(n)
        self._ensure_pool(rows)
        tid = self.tenant_id(tenant)
        k_eff = min(link_k, self.state.capacity)
        n_modes = len(shard_modes)
        pool_need = self._link_pool_size(n_modes * n * k_eff,
                                         link_accept_hint)
        slots = self._alloc_edge_slots(n + pool_need)
        chain_slot_list = slots[:n]
        link_pool_list = slots[n:]

        cap = self.state.capacity
        ecap = self.edge_state.capacity
        padded = S.pad_rows(np.asarray(rows, np.int32), cap)
        b = len(padded)

        def pad(vals, fill=0.0, dt=np.float32):
            out = np.full((b,), fill, dt)
            out[:n] = vals
            return out

        emb = np.zeros((b, self.dim), np.float32)
        emb[:n] = np.asarray(embeddings, np.float32).reshape(n, self.dim)
        emb[n:, 0] = 1.0  # sentinel rows get a unit vector (normalizable)

        # densified chain group per fact: consecutive live facts of one
        # shard group chain on device (dup facts bridge their neighbors)
        gid_of: Dict[str, int] = {}
        gids = [gid_of.setdefault(k or "default", len(gid_of))
                for k in shard_keys]
        chain_slots = np.full((b,), ecap, np.int32)
        chain_slots[:n] = chain_slot_list
        link_pool = self._link_pool_dev(link_pool_list, n_modes * b * k_eff,
                                        ecap)

        now_abs = now if now is not None else time.time()
        dev_args = (
            jnp.asarray(padded), jnp.asarray(emb),
            jnp.asarray(pad([float(s) for s in saliences])),
            jnp.asarray(pad([float(t) - self.epoch
                             for t in timestamps])),
            jnp.asarray(pad([S.TYPE_IDS.get(t, 0) for t in types], 0,
                            np.int32)),
            jnp.asarray(pad([self.shard_id(sk or "default")
                             for sk in shard_keys], -1, np.int32)),
            jnp.asarray(pad([tid] * n, -1, np.int32)),
            jnp.asarray(pad([False] * n, False, bool)),
            jnp.asarray(pad(gids, -1, np.int32)),
            jnp.asarray(chain_slots), link_pool,
            jnp.int32(len(link_pool_list)),
            jnp.float32(now_abs - self.epoch), jnp.int32(tid),
            jnp.float32(dedup_gate), jnp.float32(chain_weight),
            jnp.float32(link_gate), jnp.float32(link_scale),
            jnp.float32(self.ivf_online_eta))
        kind = ("sharded_dedup_fused"
                if self.ingest_sharded and self.mesh is not None
                else "dedup_fused")
        self._maybe_record_ingest_hbm(dev_args, k_eff, shard_modes, b)
        with self.telemetry.span("ingest." + kind, timer="ingest.dispatch_ms",
                                 labels={"kind": kind}):
            (flat, shadow_fresh, ivf_fresh, pq_fresh,
             page_mirror) = self._apply_dedup_fused(
                *dev_args, k=k_eff, shard_modes=shard_modes,
                mirror_rows=rows)
            if not shadow_fresh:
                self._int8_dirty = True
            if not pq_fresh:
                # dup rows never became alive, but their codes are masked
                # with them — patching the whole batch is safe and cheap
                self._pq_encode_rows(rows)
            self._emb_gen += 1
            host = fetch_packed(*flat)         # the ONE readback
        # Device counters riding the same readback: dedup verdicts are the
        # first wide leaf; the link counters trail the per-mode triples,
        # the online-IVF leaves (assign, member pos, 4 counters —
        # ISSUE 12) trail those when the coarse tables were maintained,
        # and the paged free-list leaves are LAST (ISSUE 17).
        if self._pager is not None:
            self._note_page_tail(host[-S.PAGE_INGEST_TAIL:], page_mirror)
            host = host[:-S.PAGE_INGEST_TAIL]
        ctr = host[3 + 3 * n_modes:]
        self.telemetry.bump("ingest.dispatches",
                            labels={"kind": kind})
        self._note_ingest_select()
        self.telemetry.bump("ingest.dedup_hits",
                            int((host[0][:n, 0] > 0).sum()))
        self.telemetry.bump("ingest.links_accepted", int(ctr[1][0, 0]))
        self.telemetry.bump("ingest.pool_slots_used", int(ctr[2][0, 0]))
        return {
            "rows": rows, "n": n, "k_eff": k_eff,
            "shard_modes": shard_modes, "link_scale": link_scale,
            "tenant": tenant, "now": now_abs,
            "dup": host[0][:n, 0] > 0,
            "target_rows": host[1][:n, 0],
            "chain_src": host[2][:n, 0],
            "chain_slots": chain_slot_list,
            "link_pool": link_pool_list,
            "link_host": host[3:],
            "ivf_host": (ctr[3:] if ivf_fresh else None),
        }

    def commit_ingest_dedup(self, pending: dict, ids: Sequence[Optional[str]]
                            ) -> Tuple[Dict, Dict, List, List]:
        """Finish host bookkeeping for ``ingest_batch_dedup``: register the
        surviving facts' ids, free duplicate rows, keep/reclaim edge slots
        per the device's gate verdicts. ``ids[i]`` names fact ``i`` and is
        ignored (may be None) where the device found a duplicate.

        Returns ``(candidates, created, merges, chains)``:
          candidates — {mode: {id: [(cand_id, score), ...]}} full lists
          created    — {mode: [(src_id, tgt_id, weight), ...]} link edges
          merges     — [(fact_idx, target_id)] device-merged duplicates
          chains     — [(src_id, tgt_id)] chain edges the device inserted
        """
        n = pending["n"]
        rows = pending["rows"]
        dup = pending["dup"]
        tenant = pending["tenant"]
        reclaim: List[int] = []
        live_rows: List[int] = []
        for i in range(n):
            if dup[i]:
                self._free_rows.append(rows[i])   # never became alive
                continue
            qid = ids[i]
            self.id_to_row[qid] = rows[i]
            self.row_to_id[rows[i]] = qid
            live_rows.append(rows[i])
        self.tenant_nodes.setdefault(tenant, set()).update(
            ids[i] for i in range(n) if not dup[i])
        merges = [(i, self.row_to_id.get(int(pending["target_rows"][i])))
                  for i in range(n) if dup[i]]
        chains: List[Tuple[str, str]] = []
        chain_src = pending["chain_src"]
        for i, slot in enumerate(pending["chain_slots"]):
            src_id = (self.row_to_id.get(int(chain_src[i]))
                      if chain_src[i] >= 0 else None)
            key = (src_id, ids[i]) if src_id and not dup[i] else None
            if key is not None and key not in self.edge_slots:
                self.edge_slots[key] = slot
                chains.append(key)
            else:
                reclaim.append(slot)
        candidates: Dict[int, Dict[str, List[Tuple[str, float]]]] = {}
        created: Dict[int, List[Tuple[str, str, float]]] = {}
        host = pending["link_host"]
        link_pool = pending["link_pool"]
        pool_real = len(link_pool)
        k_eff = pending["k_eff"]
        link_scale = pending["link_scale"]
        overflowed: List[Tuple[str, str, float]] = []
        consumed = 0
        for mi, sm in enumerate(pending["shard_modes"]):
            sc, cd, ps = host[3 * mi], host[3 * mi + 1], host[3 * mi + 2]
            out_m: Dict[str, List[Tuple[str, float]]] = {}
            made: List[Tuple[str, str, float]] = []
            for bi in range(n):
                nid = ids[bi]
                pairs = []
                for j in range(k_eff):
                    p = int(ps[bi, j])
                    s = float(sc[bi, j])
                    # a slot no candidate filled holds (NEG_INF, capacity):
                    # the score says so before the row is looked up
                    cid = (self.row_to_id.get(int(cd[bi, j]))
                           if s > S.NEG_INF / 2 else None)
                    if cid is not None and not dup[bi]:
                        pairs.append((cid, s))
                    if p < 0:
                        continue               # rejected: no slot consumed
                    w = min(1.0, max(0.0, s * link_scale))
                    if p >= pool_real:
                        # accepted but past the hinted pool (never written)
                        # — host-side retry insert below
                        if cid is not None and not dup[bi] \
                                and (nid, cid) not in self.edge_slots:
                            overflowed.append((nid, cid, w))
                            made.append((nid, cid, w))
                        continue
                    consumed = max(consumed, p + 1)
                    key = (nid, cid)
                    if cid is not None and not dup[bi] \
                            and key not in self.edge_slots:
                        self.edge_slots[key] = link_pool[p]
                        made.append((nid, cid, w))
                    else:
                        reclaim.append(link_pool[p])
                if not dup[bi]:
                    out_m[nid] = pairs
            candidates[sm] = out_m
            created[sm] = made
        # compaction: everything past the last accepted position was never
        # written — reclaim the suffix as one contiguous slice
        self._free_edge_slots.extend(link_pool[consumed:])
        self._free_edge_slots.extend(reclaim)
        self._csr_dirty = True
        if self._sem_host is not None:
            # Semantic-cache invalidation off THIS ingest readback
            # (ISSUE 20): dedup-merge targets mutated in place — flip
            # exactly the slots caching them via the row→slot reverse
            # index; any ACCEPTED fact can change its tenant's top-k,
            # which no row-level index can see, so those flush the
            # tenant's slots.
            tgt = pending["target_rows"]
            self._sem_host.invalidate_rows(
                int(tgt[i]) for i in range(n) if dup[i])
            if live_rows:
                self._sem_host.invalidate_tenant(self._tenants.get(tenant))
        if pending.get("ivf_host") is not None:
            # in-dispatch member appends: routed immediately, spills to
            # the exact-scan extras (ISSUE 12)
            self._ivf_note_online(rows, [not d for d in dup],
                                  pending["ivf_host"])
        else:
            self._ivf_note_added(live_rows)
        if overflowed:
            self.link_pool_overflows += 1
            self.telemetry.bump("ingest.link_pool_overflows")
            self.add_edges(overflowed, pending["tenant"],
                           now=pending["now"])
        return candidates, created, merges, chains

    def _maybe_record_ingest_hbm(self, dev_args, k_eff: int, shard_modes,
                                 b: int) -> None:
        """Opt-in peak-HBM gauge for one ingest-kernel geometry (ISSUE 9
        satellite, write-path twin of the serving ``_maybe_record_hbm``):
        AOT-lower the NON-donating twin once per (batch-bucket, k, modes,
        mesh) key and record ``memory_analysis()`` into
        ``kernel.peak_hbm_bytes{path="ingest",batch,rows,mesh}`` so
        ``scripts/check_hbm_budget.py`` gates write-path geometries too.
        One extra compile, zero extra dispatches."""
        if not self.telemetry_hbm or not self.telemetry.enabled:
            return    # never consume the once-key while warmup mutes the registry
        ivf_on = self._ivf_online_arg() is not None
        with self._state_lock:
            pq_on = self._pq_ingest_arg() is not None
        key = ("ingest", b, k_eff, tuple(shard_modes),
               self.state.salience.shape[0], ivf_on, pq_on)
        if key in self._hbm_recorded:
            return
        self._hbm_recorded.add(key)
        try:
            with self._state_lock:
                arena, edges = self._state, self._edge_state
                sharded = self.ingest_sharded and self.mesh is not None
                shadow = self._ingest_shadow_arg(sharded_ok=sharded)
                ivf = self._ivf_online_arg()
                pq = self._pq_ingest_arg()
                if sharded:
                    kern = self._ingest_sharded_kernels(
                        k_eff, tuple(shard_modes), shadow is not None)
                    sh = shadow if shadow is not None else ()
                    lowered = kern.ingest_copy.lower(arena, edges, *sh,
                                                     *dev_args)
                else:
                    lowered = S.ingest_dedup_fused_copy.lower(
                        arena, edges, shadow, ivf, pq, self._ptable,
                        *dev_args, k=k_eff, shard_modes=tuple(shard_modes))
            peak = peak_bytes(lowered.compile().memory_analysis())
        except Exception:   # noqa: BLE001 — observability must never block ingest
            return
        if peak is not None:
            labels = {"path": "ingest", "batch": str(b),
                      "rows": str(self.state.salience.shape[0]),
                      "mesh": (f"{self._n_parts}x{self.shard_axis}"
                               if self.mesh is not None else "1")}
            if ivf_on:
                # the AOT gauge the ivf-aware ingest cost model (ISSUE 12
                # satellite) calibrates against
                labels["ivf"] = "true"
            if pq_on:
                # the write-path gauge check_hbm_budget.py's pq=true
                # sweep reads (ISSUE 16 satellite)
                labels["pq"] = "true"
            self.telemetry.gauge("kernel.peak_hbm_bytes", peak,
                                 labels=labels)
            self.planner.observe_gauge(
                self._ingest_geometry(b, k_eff), peak)

    def warmup_ingest(self, geometries=(256,), *, dedup_gate: float = 0.95,
                      link_k: int = 3, shard_modes=(1, 0),
                      link_accept_hint: float = 1.0) -> Dict[int, float]:
        """Pre-compile the fused ingest kernels (ISSUE 9 satellite, the
        write-path mirror of ``warmup_serving``) so the first live
        mega-batch doesn't eat a cold multi-second XLA compile.
        ``geometries`` are fact-batch sizes (rounded to the ``pad_rows``
        bucket); for each, a synthetic batch of a throwaway tenant is
        driven through the REAL dispatch path (``ingest_batch_dedup`` +
        ``commit_ingest_dedup``) and then deleted — the live corpus is
        unchanged afterwards, but exactly the jit cache entries live
        traffic will hit (shapes, dtypes, mesh composition included) are
        populated. Telemetry counters are suppressed while warming; wall
        time lands in ``kernel.warmup_ms{path="ingest",batch}``. Returns
        ``{padded_batch: ms}``. Geometries that would force an arena grow
        are skipped (growth would change the compiled shapes anyway)."""
        out: Dict[int, float] = {}
        tel = self.telemetry
        rng = np.random.default_rng(0)
        buckets = sorted({len(S.pad_rows(np.zeros((g,), np.int32),
                                         self.state.capacity))
                          for g in geometries if g > 0})
        for g in buckets:
            if len(self._free_rows) < g:
                continue                    # would grow: wrong geometry
            if self.planner is not None and self.planner.active:
                # planner compile gate (ISSUE 11): don't precompile an
                # ingest geometry the admission path would refuse or
                # split — warm the planned sub-batch size instead
                try:
                    d = self.plan_ingest(g, link_k=link_k)
                except PlanInfeasible:
                    tel.bump("plan.warmup_skipped",
                             labels={"path": "ingest"})
                    continue
                if d.splits > 1:
                    g = max(1, -(-g // d.splits))
            t0 = time.perf_counter()
            prev = tel.enabled
            tel.enabled = False
            try:
                emb = rng.standard_normal((g, self.dim)).astype(np.float32)
                pending = self.ingest_batch_dedup(
                    emb, [0.5] * g, [self.epoch] * g, ["semantic"] * g,
                    ["~warmup"] * g, tenant="~warmup-ingest",
                    dedup_gate=float(dedup_gate), link_k=link_k,
                    shard_modes=tuple(shard_modes),
                    link_accept_hint=link_accept_hint)
                ids = []
                if pending is not None:
                    dup = pending["dup"]
                    ids = [None if dup[i] else f"~warm:{g}:{i}"
                           for i in range(g)]
                    self.commit_ingest_dedup(pending, ids)
                self.delete([i for i in ids if i])
            finally:
                tel.enabled = prev
            ms = (time.perf_counter() - t0) * 1e3
            tel.record("kernel.warmup_ms", ms,
                       labels={"path": "ingest", "batch": str(g)})
            out[g] = ms
        return out

    def delete(self, ids: Iterable[str]) -> None:
        ids = list(ids)
        for members in self.tenant_nodes.values():
            members.difference_update(ids)
        rows = [self.id_to_row.pop(i) for i in ids if i in self.id_to_row]
        if not rows:
            return
        for r in rows:
            self.row_to_id.pop(r, None)
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        if self._pager is not None:
            # delete + free in ONE dispatch: the rows' pool slots go back
            # on the free stack (reclaimed HBM, not dead zeros)
            pushes = self._apply_arena_paged(
                S.arena_delete_paged, S.arena_delete_paged_copy,
                jnp.asarray(padded), replay=lambda p: p.free(rows))
            self.telemetry.bump("arena.page_pushes", pushes)
        else:
            self._apply_arena(S.arena_delete, S.arena_delete_copy,
                              jnp.asarray(padded))
        self._apply_edges(S.edges_delete_for_nodes,
                          S.edges_delete_for_nodes_copy, jnp.asarray(padded))
        self._free_rows.extend(rows)
        if self._sem_host is not None:
            # cached results naming a freed row are stale the moment the
            # slot can be re-used — flip exactly those slots (ISSUE 20)
            self._sem_host.invalidate_rows(rows)
        if self.tiering is not None:       # freed cold rows leave the store
            self.tiering.on_rows_deleted(rows)
        if self._super_rows:
            self._note_super(rows, [False] * len(rows))
        routed = self._ivf_routed
        if routed is not None:
            # Per-build bookkeeping, by where the freed slot lives:
            #  - fresh residual: drop it from the fresh tuple (a re-add must
            #    append exactly once — leaving it would grow the residual
            #    with duplicates every churn cycle) and un-route it;
            #  - sealed residual: leave it routed — the residual scans the
            #    slot's CURRENT vector, so a re-add is served exactly with
            #    no action and no staleness;
            #  - member slot: un-route (a re-add must not inherit the dead
            #    vector's cluster) and count toward the rebuild trigger so
            #    churn at stable row count still converges to a rebuild
            #    (advisor r4).
            pack = self._ivf_pack
            fresh_set = set(pack[1]) if pack is not None else set()
            in_res = self._ivf_in_residual
            dropped_fresh = set()
            for r in rows:
                if r >= len(routed) or not routed[r]:
                    continue
                if r in fresh_set:
                    routed[r] = False
                    dropped_fresh.add(r)
                elif in_res is not None and r < len(in_res) and in_res[r]:
                    pass                   # sealed residual: already exact
                else:
                    routed[r] = False
                    self._ivf_stale += 1
            if dropped_fresh:
                self._ivf_pack = (pack[0], tuple(
                    x for x in pack[1] if x not in dropped_fresh))
        dead = [k for k, slot in self.edge_slots.items()
                if k[0] not in self.id_to_row or k[1] not in self.id_to_row]
        for k in dead:
            self._free_edge_slots.append(self.edge_slots.pop(k))
        self._csr_dirty = True

    def search(self, query: np.ndarray, tenant: str, k: int = 10,
               super_filter: int = 0, exact: bool = False
               ) -> Tuple[List[str], List[float]]:
        """Masked cosine top-k; returns (ids, scores), dead/padded hits
        dropped. Single-query view of ``search_batch``."""
        return self.search_batch(np.asarray(query, np.float32)[None, :],
                                 tenant, k, super_filter, exact=exact)[0]

    def search_batch(self, queries: np.ndarray, tenant: str, k: int = 10,
                     super_filter: int = 0, exact: bool = False
                     ) -> List[Tuple[List[str], List[float]]]:
        """Multi-query masked top-k: ONE matmul + top_k for Q queries (the
        TPU serving path for fleets of agents — per-query dispatch amortized
        away). Returns a (ids, scores) pair per query. Q is bucketed to a
        power of two so jit specializations stay bounded.

        ``exact=True`` forces the full-precision master arena even when the
        int8 serving shadow is enabled — consolidation's dedup/link gates
        compare scores against tight thresholds (0.95) where the ~1e-2
        quantization error could flip a decision."""

        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        if nq == 0 or not self.id_to_row:
            return empty_results(nq)
        tid = self._tenants.get(tenant)
        if tid is None:
            return empty_results(nq)
        k_eff = min(k, self.state.capacity)
        # ONE dispatch + ONE readback for the whole fleet: arena_search
        # streams query chunks through lax.map tiles on device, so host
        # round trips don't scale with the query count.
        q_pad = jnp.asarray(pad_to_pow2(queries))
        if self.mesh is None and self.ivf_nprobe and not exact:
            got = self._ivf_search(q_pad, tid, k_eff, super_filter)
            if got is not None:
                h_scores, h_rows = got
                # the device over-fetched k + slack; trim after dedup
                return decode_topk(h_scores[:nq], h_rows[:nq],
                                   self.row_to_id, S.NEG_INF, limit=k_eff)
        if self.mesh is None and self.int8_serving and not exact:
            from lazzaro_tpu.ops.quant import quantized_topk

            # ONE state snapshot feeds both the shadow and the mask: a
            # concurrent add/grow between two self.state reads would pair
            # an [N_old] shadow with an [N_new] mask (shape crash) — the
            # arena pytree is immutable, so everything derived from ``st``
            # is self-consistent (advisor r4, medium).
            st = self.state
            q8, qscale = self._int8_shadow_for(st)
            mask = S.arena_mask(st, jnp.int32(tid), super_filter)
            scores, rows = quantized_topk(q8, qscale, mask,
                                          S.normalize(q_pad), k_eff)
        elif self.mesh is None:
            # Dense-layout demotion zero-fills the master row but leaves it
            # alive; pass the residency column so cold rows mask to -inf
            # instead of surfacing as a score-0.0 tail (exact parity with
            # the paged layout, which frees the slot — ISSUE 18).
            cold = (self.tiering.cold_mask_dev()
                    if self.tiering is not None and self.tiering.cold_count
                    else None)
            scores, rows = S.arena_search(self.state, q_pad, jnp.int32(tid),
                                          k_eff, super_filter, impl="auto",
                                          cold=cold)
        else:
            # pallas_call has no GSPMD partitioning rule, so the blocked
            # kernel can't run on the sharded global array directly — but
            # under shard_map each device sees its local rows as a plain
            # array, so the per-shard scorer (pallas on big TPU shards, XLA
            # otherwise) composes with the mesh; only the k-candidate
            # combine crosses ICI (VERDICT r3 weak #7). The int8 shadow
            # composes the same way — row-local state, per-shard scan.
            st = self.state
            mask = S.arena_mask(st, jnp.int32(tid), super_filter)
            if self.int8_serving and not exact:
                q8, qscale = self._int8_shadow_for(st)
                scores, rows = self._mesh_searcher(k_eff, int8=True)(
                    q8, qscale, mask, S.normalize(q_pad))
            else:
                if self.tiering is not None and self.tiering.cold_count:
                    # same residency parity fix as the single-chip exact
                    # path: a demoted row's zeroed master must never score
                    # as 0.0 (the int8 branch above keeps cold rows — the
                    # shadow codes are preserved across demotion)
                    mask = mask & ~self.tiering.cold_mask_dev()
                scores, rows = self._mesh_searcher(k_eff)(
                    st.emb, mask, S.normalize(q_pad))
        h_scores, h_rows = fetch_packed(scores, rows)
        return decode_topk(h_scores[:nq], h_rows[:nq],
                           self.row_to_id, S.NEG_INF)

    # Below this many live rows an exact scan is trivially cheap and a
    # k-means build would be pure overhead.
    _IVF_MIN_ROWS = 4096

    def _ivf_search(self, q_pad, tid: int, k_eff: int, super_filter: int):
        """Coarse-to-fine serving scan, or None to fall through to the
        exact/int8 paths. Falls through when: no build exists yet (builds
        happen in ``ivf_maintenance``, NEVER on the query path — a k-means
        over 1M rows is multi-second), the super-node gate is being
        evaluated (threshold-sensitive: a missed cluster would
        nondeterministically disable the hierarchy fast path), or there
        are too few candidates for k."""
        from lazzaro_tpu.ops.ivf import ivf_search

        # Atomic snapshots: the (build, fresh) pair comes from ONE tuple
        # read, and mask + emb both derive from ONE immutable arena state —
        # a racing writer can swap either underneath us but never tear them
        # (advisor r4).
        pack = self._ivf_pack
        if pack is None or super_filter == 1:
            return None
        ivf, fresh = pack
        st = self.state
        residual = self._ivf_residual_dev(ivf, fresh)
        n_cand = (min(self.ivf_nprobe, ivf.n_clusters) * ivf.members.shape[1]
                  + residual.shape[0])
        if n_cand < k_eff:
            return None
        # Over-fetch slack (config-driven, shared with the int8 fused
        # path): duplicates (reused slot in a stale member slot AND the
        # residual) consume device top-k positions; the host dedup then
        # trims back to k without a shortfall.
        k_fetch = min(k_eff + self.coarse_slack, n_cand)
        mask = S.arena_mask(st, jnp.int32(tid), super_filter)
        pq_pack = self._pq_pack
        cent, members = self._ivf_live_tables(ivf)
        if self.pq_serving and pq_pack is not None:
            from lazzaro_tpu.ops.pq import ivf_pq_search

            codes = self._pq_codes_for(st, pq_pack)
            scores, rows = ivf_pq_search(
                cent, members, residual, pq_pack[0].centroids,
                codes, self._emb_logical(st), mask, S.normalize(q_pad),
                k_fetch, nprobe=self.ivf_nprobe, r=max(4 * k_eff, 64))
        else:
            scores, rows = ivf_search(cent, members, residual,
                                      self._emb_logical(st), mask,
                                      S.normalize(q_pad),
                                      k_fetch, nprobe=self.ivf_nprobe)
        return fetch_packed(scores, rows)      # ONE readback RTT

    def ivf_maintenance(self, iters: int = 8) -> bool:
        """Build or re-seed the coarse index; returns True if a (re)build
        ran. This is the ONLY place the k-means runs — call it from
        background maintenance (the consolidation worker does), never
        from a serving query. ``iters`` caps the k-means refinement steps
        (bench/maintenance knob; centroids only steer the coarse routing,
        so fewer iters trade a little recall-per-nprobe for build time).

        With ``ivf_online`` OFF this is the classic periodic rebuild
        (fresh residual outgrows 25% of the sealed build). With online
        maintenance ON (ISSUE 12), assignments are kept by the fused
        ingest dispatch itself, so this demotes to a RARE host-driven
        re-seed that only fires when the cluster-count geometry changed
        (the corpus grew/shrank enough that √N wants a different C —
        something no incremental step can do) or delete/overflow churn
        degraded the tables past the same 25% of the build (stale member
        holes + residual spill — a re-seed also re-packs the tables).
        Growth-by-ingest alone never trips it: appends routed rows, not
        residual."""
        if not self.ivf_nprobe:
            return False
        n_alive = len(self.id_to_row)
        if n_alive < self._IVF_MIN_ROWS:
            return False
        pack = self._ivf_pack
        if pack is not None:
            churn = len(pack[1]) + self._ivf_stale
            if self.ivf_online and self._ivf_dev is not None:
                # re-seed only when the IDEAL cluster count (raw √N, not
                # the pow2 rounding — which would double the instant a
                # corpus sitting exactly at 2^k grows by one row) drifted
                # ≥2× from the live table, or churn degraded the tables
                cur_c = max(1, int(self._ivf_dev[0].shape[0]))
                want_raw = max(4, int(np.sqrt(n_alive)))
                count_changed = (want_raw >= 2 * cur_c
                                 or 4 * want_raw <= cur_c)
                if not count_changed and churn <= pack[0].built_rows // 4:
                    # no re-seed due — but delete/demote holes still waste
                    # member-pool capacity; compact them in place when
                    # they cross the occupancy threshold (ISSUE 16)
                    self.ivf_member_repack()
                    return False
            elif churn <= pack[0].built_rows // 4:
                # staleness = rows awaiting a member slot PLUS member
                # slots invalidated by delete — churn at stable row count
                # still trips the trigger (advisor r4)
                return False
        from lazzaro_tpu.ops.ivf import build_ivf

        st = self.state
        mask_np = np.asarray(st.alive)
        if self.tiering is not None and self.tiering.cold_count:
            # Cold rows' master embeddings are zeroed (commit-then-zero
            # demotion) — never cluster them on garbage; the residency-
            # masked shadow coarse path serves them (ISSUE 12).
            mask_np = mask_np & ~self.tiering.cold_np[:len(mask_np)]
        ivf = build_ivf(self._emb_logical(st), mask_np, iters=iters,
                        member_cap_factor=self.ivf_member_cap_factor)
        routed, in_res = self._routed_bitmaps(ivf)
        # writer-side bookkeeping first, the reader-visible pack LAST — a
        # reader can only ever observe a fully-initialized build
        self._ivf_routed = routed
        self._ivf_in_residual = in_res
        self._ivf_stale = 0
        self._ivf_res_cache = None
        self._ivf_serve_cache = None
        self._ivf_pack = (ivf, ())
        self._publish_online_tables(ivf)
        if self._sem_host is not None:
            # a re-seed changes coarse routing for EVERY tenant — cached
            # ivf/pq windows may no longer match what a fresh scan returns
            self._sem_host.invalidate_tenant(None)
        if self.pq_serving:
            # (re)train the member codebook on the same build cadence and
            # publish it WITH its complete code slab in ONE pack swap — a
            # reader sees the old (book, codes) pair or the new complete
            # one, never old codes under a new book (r5 review) and never
            # a codeless book on the serving path. From here the pack is
            # self-maintaining (in-kernel ``_pq_scatter``, per-row
            # ``_pq_encode_rows``, grow-time slab pad) until the next
            # re-seed — this is the ONLY full encode (ISSUE 16).
            from lazzaro_tpu.ops.pq import train_pq
            self._pq_publish(train_pq(self._emb_logical(st), mask_np), st)
        return True

    def ivf_member_repack(self, hole_frac: float = 0.25) -> bool:
        """Compact the holes out of the LIVE online member tables. Tier-
        demote scrubs member slots to -1 and ``delete`` leaves slots
        pointing at dead (``alive``-masked) rows, both without moving the
        per-cluster append cursor — so the holes waste pool capacity
        (appends overflow to the extras earlier than the live population
        warrants) until a full re-seed re-packs the tables. This is the
        cheap middle ground (ISSUE 16 satellite): ONE host pass reusing
        the prefix-sum pool-compactor idiom (stable partition of live
        slots ahead of holes per cluster, cursors reset to the live
        population) and one table republish — no k-means, no re-route.
        Fires only when holes exceed ``hole_frac`` of the occupied slots;
        returns True if a repack ran and bumps ``ivf.member_repacks``."""
        if self._ivf_dev is None:
            return False
        with self._state_lock:
            dev = self._ivf_dev
            if dev is None:
                return False
            members = np.asarray(dev[1])
            counts = np.asarray(dev[2])
            alive = np.asarray(self._state.alive)
            n_slots = members.shape[1]
            idx = np.arange(n_slots)[None, :]
            occ = idx < counts[:, None]
            row_ok = np.take(alive, np.clip(members, 0, len(alive) - 1))
            live = (members >= 0) & occ & row_ok
            n_occ = int(occ.sum())
            holes = n_occ - int(live.sum())
            if holes <= 0 or holes < hole_frac * max(1, n_occ):
                return False
            order = np.argsort(~live, axis=1, kind="stable")
            packed = np.take_along_axis(members, order, axis=1)
            new_counts = live.sum(axis=1).astype(counts.dtype)
            packed[idx >= new_counts[:, None]] = -1
            # fresh uploads, never an in-place scatter: a serving dispatch
            # may still hold the old tables (same publish discipline as
            # ``_publish_online_tables``)
            self._ivf_dev = (dev[0], jnp.asarray(packed),
                             jnp.asarray(new_counts))
        self.telemetry.bump("ivf.member_repacks")
        self.telemetry.bump("ivf.member_holes_reclaimed", holes)
        return True

    def _pq_publish(self, book, st) -> None:
        """Publish a freshly trained codebook WITH its complete code slab
        in ONE pack swap — the pack is complete from the moment it is
        visible, so the serving path never encodes (ISSUE 16 killed
        ``_pq_dirty``/lazy re-encode). Cold rows' masters are zeroed by
        the commit-then-zero demote, so their codes are encoded from the
        exact vectors in the host cold store instead. If a writer raced
        the off-lock encode, it is redone once with the lock held (no
        further rows can land mid-encode); maintenance is rare, so the
        paused-writer window is acceptable."""
        from lazzaro_tpu.ops.pq import encode_pq

        def _codes(arena):
            codes = encode_pq(book.centroids, self._emb_logical(arena))
            tm = self.tiering
            if tm is not None and tm.cold_count:
                rows = np.nonzero(tm.cold_np[:arena.salience.shape[0]])[0]
                if len(rows):
                    vecs = jnp.asarray(
                        np.asarray(tm.gather_cold(rows.tolist()),
                                   np.float32))
                    r = jnp.asarray(rows.astype(np.int32))
                    codes = codes.at[r].set(
                        encode_pq(book.centroids, vecs))
            return codes

        codes = _codes(st)
        with self._state_lock:
            if self._state is not st:
                codes = _codes(self._state)
            self._pq_pack = (book, codes)
        self.telemetry.bump("pq.publishes")

    def ivf_staleness_probe(self) -> Optional[float]:
        """Measured ``assignment_staleness`` of the live coarse tables:
        the fraction of member slots whose row would pick a DIFFERENT
        centroid under the current centroids (mini-batch drift strands
        old members; an offline rebuild measures 0.0 by construction).
        O(N·C) — a bench/maintenance DIAGNOSTIC, never the serving path.
        Records the ``ivf.assignment_staleness`` gauge and returns the
        fraction, or None without live tables."""
        dev = self._ivf_dev
        if dev is None:
            return None
        from lazzaro_tpu.ops.ivf import assignment_staleness

        st = self.state
        mask = np.asarray(st.alive)
        if self.tiering is not None and self.tiering.cold_count:
            mask = mask & ~self.tiering.cold_np[:len(mask)]
        frac = assignment_staleness(self._emb_logical(st), mask,
                                    dev[0], dev[1])
        self.telemetry.gauge("ivf.assignment_staleness", frac)
        return frac

    def _pq_codes_for(self, st: S.ArenaState, pack):
        """Codes paired with ``pack``'s book for ONE arena snapshot. Since
        ISSUE 16 the published pack is complete and self-maintaining, so
        this is normally a plain read; the defensive one-shot encode only
        covers a pack caught mid-publish (codeless book) or an arena that
        grew past the slab. Defensively-encoded codes are still returned
        for THIS serve (they match the local book) but published only
        when neither the pack nor the arena moved — never against a newer
        book (r5 review: that pairing scores garbage)."""
        book, codes = pack
        if codes is None or codes.shape[0] != st.salience.shape[0]:
            from lazzaro_tpu.ops.pq import encode_pq
            codes = encode_pq(book.centroids, self._emb_logical(st))
            if self._pq_pack is pack and self.state is st:
                self._pq_pack = (book, codes)
        return codes

    def _ivf_residual_dev(self, ivf, fresh):
        """Sealed-build residual + fresh rows as one padded device array,
        re-uploaded only when the (build, fresh) snapshot changed. Cache
        validity is keyed on the IDENTITY of the build object, the
        immutable fresh tuple (writers replace the tuple, never mutate it),
        AND the residual device buffer itself (ISSUE 4 satellite: an
        ``IvfIndex`` is a mutable dataclass, so a same-length rebuild that
        swaps ``ivf.residual`` in place on the SAME build object — without
        passing through the ``_ivf`` setter — must not keep serving the
        stale residual rows), so a rebuild can never serve the old
        residual against the new member table — and a delete + re-add
        that lands in a DIFFERENT freed slot (same fresh length, different
        contents; ADVICE r5 high) can never serve a stale residual that
        silently drops the live row."""
        cache = self._ivf_res_cache
        if (cache is not None and cache[0] is ivf and cache[1] is fresh
                and cache[2] is ivf.residual):
            return cache[3]
        from lazzaro_tpu.ops.ivf import _pow2

        base = np.asarray(ivf.residual)
        comb = np.concatenate([base[base >= 0],
                               np.asarray(fresh, np.int32)])
        padded = np.full((_pow2(len(comb)),), -1, np.int32)
        padded[:len(comb)] = comb
        dev = jnp.asarray(padded)
        self._ivf_res_cache = (ivf, fresh, ivf.residual, dev)
        return dev

    def _ivf_extras_dev(self, ivf, fresh):
        """Exact-scan extras for the fused IVF serving kernel — sealed
        residual + fresh rows + super rows (``ops.ivf.pack_extras``) — as
        one padded device array, re-uploaded only when the (build, fresh,
        residual-buffer, super-set) snapshot changed. Same identity keying
        as ``_ivf_residual_dev``; the super tuple is replaced only on a
        real membership change (``_note_super``)."""
        supers = self._super_rows_frozen
        cache = self._ivf_serve_cache
        if (cache is not None and cache[0] is ivf and cache[1] is fresh
                and cache[2] is ivf.residual and cache[3] is supers):
            return cache[4]
        from lazzaro_tpu.ops.ivf import pack_extras

        n = self.state.salience.shape[0]
        dev = jnp.asarray(pack_extras(np.asarray(ivf.residual), fresh,
                                      [r for r in supers if r < n]))
        self._ivf_serve_cache = (ivf, fresh, ivf.residual, supers, dev)
        return dev

    def _ivf_live_tables(self, ivf):
        """(centroids, members) the serving scans gather through: the LIVE
        online tables when in-dispatch maintenance is on (ISSUE 12 — the
        serve always sees the last ingest's appends and centroid step, no
        cache in between), the sealed build arrays otherwise."""
        dev = self._ivf_dev
        if self.ivf_online and dev is not None:
            return dev[0], dev[1]
        return ivf.centroids, ivf.members

    def _ivf_fused_pack(self, k_kernel: int):
        """(centroids, members, extras, nprobe) tables for the fused IVF
        serving kernel, or None to serve the dense fused path instead.
        None when: IVF is off (or mesh-disabled), PQ member storage is
        active (that path keeps its own classic scan), no build exists yet
        (builds happen in ``ivf_maintenance``, NEVER on the query path),
        or the visited-cluster + extras candidate count can't fill the
        kernel's k (the dense scan is trivially cheap there anyway).
        With online IVF the centroid/member tables are the LIVE device
        arrays the fused ingest maintains — the serve-table identity IS
        the table, so there is nothing to invalidate."""
        if not self.ivf_nprobe or self.mesh is not None or self.pq_serving:
            return None
        pack = self._ivf_pack
        if pack is None:
            return None
        ivf, fresh = pack
        extras = self._ivf_extras_dev(ivf, fresh)
        cent, members = self._ivf_live_tables(ivf)
        nprobe = min(self.ivf_nprobe, int(cent.shape[0]))
        n_cand = nprobe * members.shape[1] + extras.shape[0]
        if n_cand < k_kernel:
            return None
        return cent, members, extras, nprobe

    def _pq_fused_pack(self, k_kernel: int):
        """(centroids, members, extras, nprobe, book_cent, codes) tables
        for the fused PQ serving kernel (ISSUE 16), or None to fall
        through the routing to the remaining modes. None when: PQ is off
        or has no coarse routing to ride, the index is mesh-backed (the
        pod index threads its own row-sharded pack), no COMPLETE pack is
        published yet (``ivf_maintenance`` trains and fully encodes in
        one swap — a codeless book never serves), the code slab lags the
        arena (grow mid-publish), no coarse build exists, or the
        candidate count can't fill the kernel's k. Like the IVF pack,
        the live tables ARE the identity — the in-kernel ``_pq_scatter``
        keeps the codes current, so there is nothing to invalidate."""
        if (not self.pq_serving or not self.ivf_nprobe
                or self.mesh is not None):
            return None
        pq = self._pq_pack
        if pq is None or pq[1] is None:
            return None
        if pq[1].shape[0] != self.state.salience.shape[0]:
            return None
        pack = self._ivf_pack
        if pack is None:
            return None
        ivf, fresh = pack
        extras = self._ivf_extras_dev(ivf, fresh)
        cent, members = self._ivf_live_tables(ivf)
        nprobe = min(self.ivf_nprobe, int(cent.shape[0]))
        n_cand = nprobe * members.shape[1] + extras.shape[0]
        if n_cand < k_kernel:
            return None
        return cent, members, extras, nprobe, pq[0].centroids, pq[1]

    def _int8_shadow_for(self, st: S.ArenaState):
        """(Re)build the int8 shadow from ONE arena snapshot; under a mesh
        the shadow is constrained to the master's row sharding so the
        per-shard scan never gathers. Clears the dirty flag only when no
        writer raced past ``st`` (advisor r4).

        Locking: readers take their array references UNDER ``_state_lock``
        (the returned pair is built inside the critical section), so the
        fused-ingest donation gate — which scatters new rows' codes into
        the shadow in place — can count those references the same way the
        arena gate does and fall back to the copying twin while a serve is
        holding the shadow."""
        with self._state_lock:
            shadow = self._int8_shadow
            if (not self._int8_dirty and shadow is not None
                    and shadow[0].shape[0] == st.salience.shape[0]):
                return shadow[0], shadow[1]
        from lazzaro_tpu.ops.quant import (quantize_arena,
                                           quantize_arena_sharded)
        tel = self.telemetry
        with tel.span("index.shadow"):
            # ONE program over the arena, blocked inside (ISSUE 36; under a
            # mesh every chip quantizes its own rows); what a paged or
            # tiered index adds is counted beside it
            dispatches = 1 + (st.row_map is not None)
            if self.mesh is None:
                quantize = quantize_arena
            else:
                if self._shadow_quantize is None:
                    self._shadow_quantize = quantize_arena_sharded(
                        self.mesh, self.shard_axis)
                quantize = self._shadow_quantize
            shadow = quantize(self._emb_logical(st))
            tm = self.tiering
            if tm is not None and tm.cold_count:
                # Cold rows hold ZEROS in the master (their exact bytes live
                # in the host cold store), so a rebuild from ``emb`` would
                # wipe their codes out of the coarse scan — patch them back
                # from the store (codes travel with the demoted row).
                rows, codes, scales = tm.snapshot_codes()
                keep = rows < st.salience.shape[0]
                if keep.any():
                    r = jnp.asarray(rows[keep].astype(np.int32))
                    shadow = (
                        shadow[0].at[r].set(jnp.asarray(codes[keep])),
                        shadow[1].at[r].set(jnp.asarray(scales[keep])))
                    dispatches += 2
        tel.bump("index.shadow_builds")
        tel.bump("index.shadow_dispatches", dispatches)
        with self._state_lock:
            self._int8_shadow = shadow
            if self._state is st:
                # only clear the flag if no writer raced past ``st`` —
                # otherwise rows added mid-quantize would stay invisible
                # to int8 serving until the NEXT mutation
                self._int8_dirty = False
        return shadow

    def _mesh_searcher(self, k: int, int8: bool = False):
        """Cached shard_map distributed top-k (ops/topk.py) per (k, mode)
        bucket."""
        key = ("int8", k) if int8 else k
        kern = self._mesh_topk_cache.get(key)
        if kern is None:
            from lazzaro_tpu.ops.topk import (make_sharded_int8_topk,
                                              make_sharded_topk)
            kern = (
                make_sharded_int8_topk(self.mesh, self.shard_axis, k=k)
                if int8 else
                make_sharded_topk(self.mesh, self.shard_axis, k=k, impl="auto"))
            self._mesh_topk_cache.put(key, kern)
        return kern

    # ------------------------------------------------- fused retrieval path
    def _csr_for(self, st: S.ArenaState):
        """Device CSR view of the edge arena for the fused neighbor gather:
        ``indptr`` [rows+1] i32 and ``nbr`` [E_pad] i32 (bidirectional,
        -1-padded). Built entirely from host bookkeeping (edge_slots ×
        id_to_row) — no device readback — and re-uploaded only after an
        edge-topology change. The dirty flag is cleared BEFORE the build,
        so a writer racing past us re-dirties and the next serve rebuilds."""
        n = st.salience.shape[0]
        tel = self.telemetry
        # every call counts: a window without a build reads 0 builds of
        # this many look-ups, not "a program that does not count"
        tel.bump("index.csr_lookups")
        # two read dispatches may come together: one builds, the other
        # finds its build
        with self._serve_shared_lock:
            cache = self._csr_cache
            if cache is not None and not self._csr_dirty and cache[0] == n:
                return cache[1], cache[2]
            self._csr_dirty = False
            with tel.span("index.csr"):
                keys = list(self.edge_slots.keys())
                indptr, nbr = build_host_csr(keys, self.id_to_row, n,
                                             min_pad=self._csr_pad_hwm)
                self._csr_pad_hwm = nbr.shape[0]
                if self.mesh is not None:
                    # pod path: per-shard CSR slices for the distributed
                    # fused kernel, placed so each chip holds its own rows'
                    # lists
                    from lazzaro_tpu.parallel.mesh import shard_stacked
                    sh = shard_stacked(self.mesh, self.shard_axis)
                    dev = tuple(jax.device_put(a, sh) for a in
                                split_csr(indptr, nbr, self._n_parts))
                else:
                    dev = (jnp.asarray(indptr), jnp.asarray(nbr))
            tel.bump("index.csr_builds")
            tel.bump("index.csr_edges", len(keys))
            self._csr_cache = (n, dev[0], dev[1])
            return dev

    def reads_may_overlap(self, reqs) -> bool:
        """The scheduler's overlap predicate (ISSUE 30): may this batch be
        in flight together with another such batch? Yes only for a batch
        that takes the non-donating ``*_read`` twins and touches no shared
        serving state — no boosting request (it would donate the arena
        the other dispatch reads, or copy it), no semantic ring (every
        dispatch writes it back), no cold rows (the tiered finish may
        donate), no HBM budget (the planner's model counts ONE dispatch's
        temporaries), not poisoned. Read at the time of asking; a read
        dispatch takes ``self.state`` at its own launch either way."""
        if (self._poisoned or self._sem_host is not None
                or (self.planner is not None and self.planner.active)):
            return False
        tm = self.tiering
        if tm is not None and tm.cold_count > 0:
            return False
        return not any(r.boost for r in reqs)

    # ------------------------------------------------- memory-safe serving
    def _serve_route(self, cap_take: int) -> _ServeRoute:
        """The choice of serving program, made HERE and nowhere else: the
        planner's geometry key, the dispatch (its ``_SERVE_KERNELS``
        triple, its operands, its ``serve.dispatches{mode}`` label) and
        the warmup all read this answer. Builds no device arrays beyond
        what the coarse packs cache.

        ``k_bucket`` is the static k every request of a dispatch clamps
        to, so the kernel key never depends on the batch's k mix: one
        compiled program per (mode × geometry) serves k∈{4..128} in one
        dispatch, per-request k riding as device data.

        Under a MESH the program is ``state.make_fused_sharded``'s
        (shard-local exact / int8 / tier-aware scan, all_gather merge).
        On one chip a complete PQ pack outranks the IVF build it rides on,
        either outranks the dense scans, and int8 mode takes the dense
        coarse + exact rescore; with cold rows present each composes with
        tiering (ISSUE 12 / 16: hot candidates from the member gather,
        cold rows from the residency-masked coarse scan over the int8
        shadow or the m-byte PQ slab — no dense fallback when a build is
        published)."""
        k_bucket = int(min(max(self.serve_k_max, cap_take, 1),
                           self.state.capacity))
        tm = self.tiering
        tiered = tm is not None and tm.cold_count > 0
        if self.mesh is not None:
            base = ("tiered" if tiered
                    else "quant" if self.int8_serving else "exact")
            return _ServeRoute("sharded_" + base, k_bucket, tiered, None)
        fam, tabs = "pq", self._pq_fused_pack(k_bucket)
        if tabs is None:
            fam, tabs = "ivf", self._ivf_fused_pack(k_bucket)
        if tabs is not None:
            mode = fam + "_tiered" if tiered else fam
        elif tiered:
            mode = "tiered"
        else:
            mode = "quant" if self.int8_serving else "exact"
        return _ServeRoute(mode, k_bucket, tiered, tabs)

    def _serve_operands(self, route: _ServeRoute, st) -> tuple:
        """A single-chip family's leading operands — what its entry points
        take between the arena and the CSR — against the state the caller
        dispatches on: ``cur`` under ``_state_lock`` for a boosting batch,
        so the (arena, codes, residency) tuple can never tear across a
        racing writer (re-entrant RLock; a shadow rebuild is
        dispatch-only), the snapshot for a read batch. The coarse tables,
        the PQ codebook and the code slab are read-only replicas."""
        mode, tabs = route.mode, route.coarse_tabs
        if mode.startswith("pq"):
            cent, members, extras, _, book_cent, codes = tabs
            # pq_tiered never touches the int8 shadow — the cold coarse
            # scan reads the PQ slab; only the residency mask rides
            cold = (self.tiering.cold_mask_dev(),) if route.tiered else ()
            return (book_cent, codes, *cold, cent, members, extras)
        if mode.startswith("ivf"):
            cent, members, extras, _ = tabs
            if route.tiered:
                return (*self._int8_shadow_for(st),
                        self.tiering.cold_mask_dev(), cent, members, extras)
            # two-stage candidate scan (int8 gathered coarse + exact
            # rescore) when the shadow is on too
            shadow = self._int8_shadow_for(st) if self.int8_serving else None
            return (shadow, cent, members, extras)
        if mode == "tiered":
            return (*self._int8_shadow_for(st), self.tiering.cold_mask_dev())
        if mode == "quant":
            return tuple(self._int8_shadow_for(st))
        return ()

    def _serve_geometry(self, nq: int, mode: str, k_bucket: int) -> Geometry:
        st = self.state
        return Geometry(
            kind="serve", mode=mode,
            batch=bucket_size(nq, self.serve_pad_granularity),
            rows=st.salience.shape[0],
            dim=self.dim, k=k_bucket,
            dtype_bytes=int(np.dtype(self.dtype).itemsize),
            mesh_parts=self._n_parts, edge_cap=self.edge_state.capacity,
            nprobe=int(self.ivf_nprobe or 0),
            slack=int(self.coarse_slack),
            pool_rows=(st.emb.shape[0] if st.row_map is not None else 0),
            sem_slots=(self._sem_host.slots if self._sem_host is not None
                       else 0),
            sem_width=(self._sem_host.width if self._sem_host is not None
                       else 0))

    def search_fused_requests(self, reqs, *, cap_take: int, max_nbr: int,
                              super_gate: float, acc_boost: float,
                              nbr_boost: float,
                              now: Optional[float] = None) -> List:
        """Memory-safe entry point of the fused serving path (ISSUE 11):
        with a planner budget configured, the requested geometry is
        ADMITTED before anything compiles or dispatches — it runs as the
        usual ONE fused dispatch when the prediction fits, with a chunked
        arena scan (still one dispatch) or as PLANNED sub-dispatches
        riding the linear pad buckets when it doesn't, and raises the
        typed :class:`PlanInfeasible` when no split can fit. A runtime
        ``RESOURCE_EXHAUSTED`` the model missed (reclassified by
        ``guard.run_guarded`` into :class:`DeviceOom`, never retried with
        backoff) gets exactly ONE replan — harder split, copy twins —
        before failing typed. With the planner disabled (the default)
        this is a zero-overhead passthrough to the fused dispatch."""
        nq = len(reqs)
        kw = dict(cap_take=cap_take, max_nbr=max_nbr,
                  super_gate=super_gate, acc_boost=acc_boost,
                  nbr_boost=nbr_boost, now=now)
        planner = self.planner
        if (nq == 0 or planner is None or not planner.active
                or not self.id_to_row):
            try:
                return self._search_fused_once(reqs, **kw)
            except DeviceOom:
                raise
            except Exception as e:  # noqa: BLE001 — typed OOM, uniform
                if not is_resource_exhausted(e):
                    raise
                # the read twins bypass run_guarded; keep the serving
                # surface's OOM contract typed there too
                self.telemetry.bump("reliability.oom",
                                    labels={"mode": "serve"})
                raise DeviceOom(
                    f"serving dispatch exhausted device memory and no "
                    f"planner budget is configured to replan it: {e}"
                ) from e
        check_not_poisoned(self._poisoned)
        route = self._serve_route(cap_take)
        geom = self._serve_geometry(nq, route.mode, route.k_bucket)
        decision = planner.check_feasible(geom,
                                          chunkable=self.mesh is None)
        return self._serve_planned(reqs, geom, decision, kw,
                                   replanned=False)

    def _serve_planned(self, reqs, geom, decision, kw,
                       replanned: bool) -> List:
        """Execute one plan decision: dispatch the (possibly split) batch,
        recording planned sub-dispatches, and answer a runtime OOM with
        ONE harder replan through the copy twins."""
        tel = self.telemetry
        n = len(reqs)
        splits = max(1, min(decision.splits, n))
        per = -(-n // splits)
        groups = [reqs[i:i + per] for i in range(0, n, per)]
        if len(groups) > 1:
            # a planned multi-dispatch turn is RECORDED, never silent —
            # the dispatch-count gate accepts exactly these
            tel.bump("plan.planned_turns", labels={"path": "serve"})
            tel.bump("plan.split_dispatches", len(groups),
                     labels={"path": "serve"})
        if decision.scan_chunk:
            tel.bump("plan.scan_chunked", labels={"path": "serve"})
        out: List = []
        done = 0
        try:
            for g in groups:
                out.extend(self._search_fused_once(
                    g, scan_chunk=decision.scan_chunk,
                    force_copy=replanned, **kw))
                done += len(g)
        except Exception as e:      # noqa: BLE001 — OOM-only replan below
            if not is_resource_exhausted(e):
                raise
            if replanned:
                tel.bump("plan.infeasible", labels={"path": "serve"})
                raise PlanInfeasible(
                    f"replanned serving dispatch still exhausted device "
                    f"memory (mode={geom.mode}, batch={geom.batch}, "
                    f"rows={geom.rows}): {e}") from e
            self.planner.note_oom(geom)
            harder = self.planner.replan_after_oom(
                geom, decision, chunkable=self.mesh is None)
            if harder is None:
                tel.bump("plan.infeasible", labels={"path": "serve"})
                raise PlanInfeasible(
                    f"serving dispatch exhausted device memory and no "
                    f"harder split fits the budget (mode={geom.mode}, "
                    f"batch={geom.batch}, rows={geom.rows})") from e
            tel.bump("plan.oom_replans", labels={"path": "serve"})
            out.extend(self._serve_planned(reqs[done:], geom, harder, kw,
                                           replanned=True))
        return out

    def _search_fused_once(self, reqs, *, cap_take: int, max_nbr: int,
                           super_gate: float, acc_boost: float,
                           nbr_boost: float,
                           now: Optional[float] = None,
                           scan_chunk: int = 0,
                           force_copy: bool = False) -> List:
        """Serve a coalesced batch of ``serve.RetrievalRequest``s with ONE
        device dispatch + ONE packed readback: masked super-node top-1
        gate, main-arena ANN top-k, CSR neighbor gather, and the neighbor-
        salience + access-salience boosts for every query that asked
        (donated scatter, ``*_copy`` twin under the refcount gate — PR 1's
        ownership rules). Pure-read batches (no boosts requested) take the
        non-donating ``*_read`` twins. Per-request tenants ride
        into the kernel as a device column, so one batch can serve many
        tenants with mask-enforced isolation; per-request k / cap / nprobe
        ride the same way under static ceilings, so one compiled program
        serves any mix of request shapes.

        Which program runs is ``_serve_route``'s answer (all still ONE
        dispatch + ONE readback): on one chip a ``_SERVE_KERNELS`` family,
        under a MESH the same program as ONE distributed shard_map
        dispatch (``state.make_fused_sharded``): shard-local scan (exact,
        or int8 coarse + exact rescore over the row-sharded shadow), one
        all_gather + global top-k merge, then the gate/CSR/boost tail
        with shard-local scatters — the pod path keeps the full chat-turn
        semantics (ISSUE 5)."""
        from lazzaro_tpu.serve.scheduler import RetrievalResult

        nq = len(reqs)
        if nq == 0:
            return []
        check_not_poisoned(self._poisoned)
        results = [RetrievalResult() for _ in range(nq)]
        if not self.id_to_row:
            return results
        tel = self.telemetry
        with tel.span("index.pack"):
            st = self.state
            cap = st.capacity
            dim = self.dim
            # With any row demoted (ISSUE 8) the route is a tier-aware
            # program: ONE bounded finish dispatch for queries whose
            # candidates touch cold rows; hot-only turns stay ONE dispatch
            # + ONE readback.
            route = self._serve_route(cap_take)
            mode, k_bucket, tiered = route.mode, route.k_bucket, route.tiered
            # Batches pad to a LINEAR granularity bucket, not the next power
            # of two: worst-case padded waste is granularity-1 slots instead
            # of ~50% of the dispatch, with jit specializations still bounded.
            # The carrier is the dispatch's ONE host operand (ISSUE 37): the
            # loop writes each query's bits straight into it.
            car = RequestCarrier(nq, dim, self.serve_pad_granularity)
            q = car.q[:nq]
            valid = np.zeros((nq,), bool)
            tenants = np.full((nq,), -1, np.int32)
            gate_on = np.zeros((nq,), bool)
            boost_on = np.zeros((nq,), bool)
            k_arr = np.zeros((nq,), np.int32)
            cap_arr = np.zeros((nq,), np.int32)
            for i, r in enumerate(reqs):
                v = np.asarray(r.query, np.float32).reshape(-1)
                tid = self._tenants.get(r.tenant)
                if v.size != dim or tid is None:
                    continue
                q[i] = v
                valid[i] = True
                tenants[i] = tid
                gate_on[i] = bool(r.gate_enabled)
                boost_on[i] = bool(r.boost)
                # k_q ≥ cap so the boosted prefix is always live
                k_arr[i] = min(max(int(r.k), cap_take, 1), k_bucket)
                rc = getattr(r, "cap_take", None)
                cap_arr[i] = min(int(rc) if rc else cap_take, cap_take,
                                 k_bucket)
            if not valid.any():
                return results
            pad_n = car.buf.shape[0]
        with tel.span("index.stage"):
            # Coalesce/pad inflation: padded kernel slots vs live requests.
            tel.bump("serve.live_requests", nq)
            tel.bump("serve.padded_slots", pad_n)
            tel.gauge("serve.batch_occupancy", nq / pad_n)
            indptr, nbr = self._csr_for(st)
            tm = self.tiering
            # Per-query k / cap / nprobe as int32 DATA next to the query
            # batch. Pad rows carry 0 (their top-k masks fully dead; they
            # were q_valid=False anyway). ``now`` is read here, a moment
            # before a boosting launch takes _state_lock.
            car.fill(valid=valid, tenant=tenants, gate_on=gate_on,
                     boost_on=boost_on, k=k_arr, cap=cap_arr,
                     super_gate=super_gate, acc_boost=acc_boost,
                     nbr_boost=nbr_boost,
                     now=(now if now is not None else time.time())
                     - self.epoch)
            boosting = bool(boost_on.any())
            if self.mesh is None:
                statics = dict(k=k_bucket, cap_take=min(cap_take, k_bucket),
                               max_nbr=max_nbr)
                coarse_tabs = route.coarse_tabs
                if coarse_tabs is not None:
                    ceil_np = statics["nprobe"] = coarse_tabs[3]
                    statics["slack"] = self.coarse_slack
                    # the coarse families' probe-width column
                    np_arr = np.zeros((nq,), np.int32)
                    for i, r in enumerate(reqs):
                        rn = getattr(r, "nprobe", None)
                        np_arr[i] = (min(max(int(rn), 1), ceil_np) if rn
                                     else ceil_np)
                    np_arr[~valid] = 0
                    car.fill(nprobe=np_arr)
                elif mode in ("quant", "tiered"):
                    statics["slack"] = self.coarse_slack
                if scan_chunk:
                    # Planner streaming-width override (ISSUE 11): the scan
                    # chunks the arena stream tighter — smaller [chunk, rows]
                    # score tile, SAME single dispatch, bit-identical results.
                    statics["scan_chunk"] = int(scan_chunk)
                # Semantic query cache (ISSUE 20): the ring probe, hit
                # substitution with per-query scan early-out, and the miss
                # writeback all ride INSIDE this one dispatch; the hit verdict
                # comes back in the packed readback's semantic counter. Skipped
                # when the batch's candidate window outgrows the ring width
                # (cap_take above serve_k_max).
                semh = self._sem_host
                sem_kw = {}
                if semh is not None and mode in S.SEM_MODE_IDS:
                    win = k_bucket + (statics.get("slack", 0) if tiered
                                      else 0)
                    if win <= semh.width:
                        statics["sem_block"] = semh.block
                        sem_kw = {"sem": semh.tuple_for(mode)}
                self._note_serve_kernel(mode, statics)
                self._note_select_core(mode, st)
                donated, copying, read = (getattr(S, name)
                                          for name in _SERVE_KERNELS[mode])
                args = (indptr, nbr, self._hand_requests(car, mode))
                self._maybe_record_hbm(route, st, read, args, statics)
                # Fault point "plan.oom" (ISSUE 11): an HBM allocation failure the
                # admission plan missed; the wrapper answers with one replan.
                faults.fire("plan.oom", mode=mode, batch=pad_n)
                if sem_kw and not boosting:
                    # the read twins take the ring operand as a plain kwarg next
                    # to their statics; the boost branch passes it explicitly
                    # beside its donated state
                    statics = dict(statics, **sem_kw)
            else:
                # Semantic query cache (ISSUE 20): the replicated ring rides
                # the SAME distributed dispatch (substitution-only — the
                # shard-local scans still run; the probe/substitute/
                # writeback are replicated arithmetic after the merge).
                # Entries key on the FAMILY mode id, so they never cross
                # serving modes.
                semh = self._sem_host
                fam = mode[len("sharded_"):]
                self._note_select_core(fam, st)
                sem_state = None
                if semh is not None and fam in S.SEM_MODE_IDS:
                    win = k_bucket + (self.coarse_slack if tiered else 0)
                    if win <= semh.width:
                        sem_state = semh.tuple_for(fam)
                # the distributed program's lookup, staged as on one chip
                staged = self._stage_fused_sharded(
                    st, indptr, nbr, car, k_bucket, cap_take, max_nbr, fam,
                    boosting, sem=sem_state)
                # Fault point "plan.oom" (ISSUE 11): models an HBM allocation
                # failure the admission plan missed — recovery is ONE replan
                # into split sub-dispatches through the copy twins.
                faults.fire("plan.oom", mode=mode, batch=pad_n)
        if self.mesh is not None:
            with tel.span("serve." + mode, timer="serve.dispatch_ms",
                          labels={"mode": mode}):
                with tel.span("dispatch.launch"):
                    if not staged.boosting:
                        packed = staged.kern.read(
                            st, self._sharded_tables(st, tiered),
                            *staged.sargs, *staged.sem_tail)
                    else:
                        # a live snapshot would trip the sole-owner gate,
                        # and this frame's reference is one
                        st = None
                        packed = self._serve_fused_sharded(
                            staged, mode, tiered, force_copy)
                    if sem_state is not None:
                        sem_ring2, packed = packed
                with tel.span("dispatch.readback"):
                    host = np.asarray(packed)      # the ONE readback
            tel.bump("serve.dispatches", labels={"mode": mode})
            if tiered:
                from lazzaro_tpu.tier.serve import tiered_decode_and_finish
                st = None                  # the finish may donate the state
                now_rel = ((now if now is not None else time.time())
                           - self.epoch)
                if sem_state is not None:
                    k_unpack = (host.shape[1] - 8) // 2
                    g_s, g_r, a_s, a_r, _, ctr = unpack_retrieval(
                        host[:nq], k_unpack)
                    semh.note_readback(sem_ring2, ctr[:, 4], valid[:nq],
                                       tenants[:nq], g_s, g_r, a_s, a_r)
                with tel.span("index.decode", timer="serve.decode_ms"):
                    return tiered_decode_and_finish(
                        self, tm, reqs, results, valid, boost_on, q,
                        tenants, host, k_bucket=k_bucket,
                        cap_take=min(cap_take, k_bucket), max_nbr=max_nbr,
                        acc_boost=acc_boost, nbr_boost=nbr_boost,
                        now_rel=now_rel, cap_arr=cap_arr, tel=tel)
            with tel.span("index.decode", timer="serve.decode_ms"):
                gate_s, gate_r, ann_s, ann_r, fast, counters = \
                    unpack_retrieval(host[:nq], k_bucket)
                out = self._demux_fused(reqs, results, valid, boost_on,
                                        gate_s, gate_r, ann_s, ann_r, fast,
                                        cap, counters[:, 0])
                if sem_state is not None:
                    semh.note_readback(sem_ring2, counters[:, 4],
                                       valid[:nq], tenants[:nq], gate_s,
                                       gate_r, ann_s, ann_r)
                record_device_counters(
                    tel, counters, fast, gate_on[:nq], valid[:nq],
                    np.asarray([min(int(r.k), cap) for r in reqs]),
                    sem_active=sem_state is not None)
            return out
        with tel.span("serve." + mode, timer="serve.dispatch_ms",
                      labels={"mode": mode}):
            with tel.span("dispatch.launch"):
                if boosting:
                    del st  # a live snapshot would trip the sole-owner gate
                    with self._state_lock:
                        cur = self._state
                        # force_copy: a post-OOM replan always dispatches
                        # through the non-donating twin (ISSUE 11)
                        sole = (not force_copy
                                and sys.getrefcount(cur) <= self._SOLE_REFS)
                        if not sole:
                            tel.bump("serve.copy_dispatches",
                                     labels={"mode": mode})
                        # The family's leading operands, taken against
                        # ``cur`` under the lock; ONE guarded call executes
                        # the (donated, copying) pair donation-safe (ISSUE
                        # 10): a transient failure retries through the
                        # copying twin, a consumed input raises typed
                        # ArenaPoisoned.
                        pre = self._serve_operands(route, cur)
                        out = self._guarded(
                            lambda fn: fn(cur, *pre, *args, **sem_kw,
                                          **statics),
                            donated, copying, sole, (cur,),
                            "serve_" + mode)
                        if sem_kw:
                            new_state, sem_ring2, packed = out
                        else:
                            new_state, packed = out
                        del cur
                        self.state = new_state
                else:
                    packed = read(st, *self._serve_operands(route, st),
                                  *args, **statics)
                    if sem_kw:
                        sem_ring2, packed = packed
            with tel.span("dispatch.readback"):
                host = np.asarray(packed)          # the ONE readback
        tel.bump("serve.dispatches", labels={"mode": mode})
        if tiered:
            from lazzaro_tpu.tier.serve import tiered_decode_and_finish
            try:
                del st                     # the finish may donate the state
            except NameError:
                pass                       # boost path already dropped it
            now_rel = (now if now is not None else time.time()) - self.epoch
            with tel.span("index.decode", timer="serve.decode_ms"):
                out = tiered_decode_and_finish(
                    self, tm, reqs, results, valid, boost_on, q, tenants,
                    host, k_bucket=k_bucket, cap_take=statics["cap_take"],
                    max_nbr=max_nbr, acc_boost=acc_boost,
                    nbr_boost=nbr_boost, now_rel=now_rel, cap_arr=cap_arr,
                    tel=tel)
                k_unpack = (host.shape[1] - 8) // 2
                g_s, g_r, a_s, a_r, fast_np, counters = unpack_retrieval(
                    host[:nq], k_unpack)
                if sem_kw:
                    semh.note_readback(sem_ring2, counters[:, 4],
                                       valid[:nq], tenants[:nq], g_s, g_r,
                                       a_s, a_r)
                record_device_counters(
                    tel, counters, fast_np, gate_on[:nq], valid[:nq],
                    np.asarray([min(int(r.k), cap) for r in reqs]),
                    sem_active=bool(sem_kw))
            return out
        with tel.span("index.decode", timer="serve.decode_ms"):
            gate_s, gate_r, ann_s, ann_r, fast, counters = unpack_retrieval(
                host[:nq], k_bucket)
            out = self._demux_fused(reqs, results, valid, boost_on, gate_s,
                                    gate_r, ann_s, ann_r, fast, cap,
                                    counters[:, 0])
            if sem_kw:
                semh.note_readback(sem_ring2, counters[:, 4], valid[:nq],
                                   tenants[:nq], gate_s, gate_r, ann_s,
                                   ann_r)
            record_device_counters(
                tel, counters, fast, gate_on[:nq], valid[:nq],
                np.asarray([min(int(r.k), cap) for r in reqs]),
                sem_active=bool(sem_kw))
        return out

    def _hand_requests(self, car: RequestCarrier, mode: str) -> np.ndarray:
        """The ONE host→device transfer of a serving dispatch (ISSUE 37):
        its request carrier, handed to the jitted call as the NumPy array
        it is — the call's own argument handling makes the transfer (under
        a mesh one to every chip, the carrier is replicated). On the chip's
        host that is 0.16 ms a dispatch less than a ``jax.device_put``
        before the call (PERF.md §6, PR 37), so ``lz.index.stage`` holds no
        transfer and ``lz.dispatch.launch`` holds this one.
        ``serve.h2d_puts{mode}`` counts these beside
        ``serve.dispatches{mode}``: their ratio reads 1 while nobody adds a
        second transfer (a CSR or shadow rebuild is not a request's, nor
        are the cold rows of a tiered turn's bounded finish dispatch)."""
        self.telemetry.bump("serve.h2d_puts", labels={"mode": mode})
        return car.buf

    def _note_select_core(self, mode: str, st) -> None:
        """``serve.select{core}``: which form of the select-while-scanning
        core this dispatch runs (ISSUE 26) — ``blocked`` when a block tiles
        the pool (each chip's slice of it under a mesh) and the selection
        follows the stream, ``whole_pool`` when the pool is ONE block of the
        same code (smaller than a block, or a row count no block divides);
        the int8 family's coarse scan over the shadow (ISSUE 36) reads
        ``blocked_q8`` / ``whole_pool_q8``."""
        if mode not in ("exact", "quant"):
            return
        from lazzaro_tpu.ops.pallas_topk import block_tiles, q8_block_tiles
        d = st.emb.shape[1]
        if mode == "exact":
            blocked, tag = block_tiles(st.emb.shape[0] // self._n_parts, d,
                                       st.emb.dtype.itemsize), ""
        else:                       # the shadow lies in logical row space
            blocked, tag = q8_block_tiles(
                st.salience.shape[0] // self._n_parts, d), "_q8"
        self.telemetry.bump("serve.select", labels={
            "core": ("blocked" if blocked else "whole_pool") + tag})

    def _note_ingest_select(self) -> None:
        """``ingest.select{core}``: ``serve.select``'s write-path twin
        (ISSUE 45), bumped once a fused ingest dispatch — which form of the
        select-while-scanning link scan it runs: ``blocked`` when a block
        tiles the pool (each chip's slice of it under the sharded program),
        ``whole_pool`` when the pool is ONE block of the same code."""
        from lazzaro_tpu.ops.pallas_topk import block_tiles
        emb = self.state.emb
        parts = self._n_parts if self.ingest_sharded else 1
        blocked = block_tiles(emb.shape[0] // parts, emb.shape[1],
                              emb.dtype.itemsize)
        self.telemetry.bump("ingest.select", labels={
            "core": "blocked" if blocked else "whole_pool"})

    def _note_serve_kernel(self, mode: str, statics: dict) -> None:
        """Track the distinct fused serving-kernel keys this index has
        dispatched — exactly ONE per mode while the k/cap/nprobe ceilings
        stand. The ``kernel.cache_entries{surface="single_fused"}`` gauge
        this maintains is what a compile-cache measurement reads."""
        key = (mode, tuple(sorted(statics.items())))
        if key in self._serve_kernel_keys:
            return
        with self._serve_shared_lock:
            self._serve_kernel_keys.add(key)
            self.telemetry.gauge("kernel.cache_entries",
                                 len(self._serve_kernel_keys),
                                 labels={"surface": "single_fused"})

    def warmup_serving(self, geometries=(8, 64), *, cap_take: int = 5,
                       max_nbr: int = 32, super_gate: float = 0.4,
                       acc_boost: float = 0.05, nbr_boost: float = 0.02,
                       k: Optional[int] = None) -> Dict[tuple, float]:
        """Pre-compile the fused serving kernels (ISSUE 7 satellite) so
        the FIRST live request doesn't eat a cold multi-second XLA
        compile. ``geometries`` are query-batch sizes (rounded to the
        serving pad bucket); for each, the current mode's read twin AND
        donated serve twin are driven once through the REAL dispatch path
        (``search_fused_requests``) with queries of a synthetic tenant
        that owns no rows — numerically a no-op on the arena (no live
        hits, every boost scatter routes to the sentinel), but it
        populates exactly the jit cache entries live traffic will hit,
        shapes and dtypes included. Serving counters are suppressed while
        warming (a warmup must not skew the pad-waste / dispatch
        baselines); wall time lands in ``kernel.warmup_ms{mode,batch}``.
        Returns ``{(mode, padded_batch): ms}``. Call AFTER the corpus and
        edge graph are in place (the CSR buffer's padded shape is part of
        the kernel key) — bench.py does, right before its timed sections.
        No-op on an empty index (no tenant ever resolves there)."""
        from lazzaro_tpu.serve.scheduler import RetrievalRequest

        out: Dict[tuple, float] = {}
        if not self.id_to_row:
            return out
        tel = self.telemetry
        mode = self._serve_route(cap_take).mode
        # the warmup tenant matches no arena row (never allocated to one)
        self._tenants.setdefault("~warmup", -2)
        kk = int(k if k is not None else self.serve_k_max)
        buckets = sorted({bucket_size(g, self.serve_pad_granularity)
                          for g in geometries if g > 0})
        kw = dict(cap_take=cap_take, max_nbr=max_nbr, super_gate=super_gate,
                  acc_boost=acc_boost, nbr_boost=nbr_boost)
        for g in buckets:
            zero_q = np.zeros((self.dim,), np.float32)
            t0 = time.perf_counter()
            prev = tel.enabled
            tel.enabled = False
            try:
                # serve twin (one boosting request), then the read twin.
                # Warmups route through the SAME planner-gated entry as
                # live traffic (ISSUE 11), so a planned-split geometry
                # precompiles exactly the sub-dispatch kernels it will
                # serve with; an infeasible one is skipped typed instead
                # of compiling a program that could never dispatch.
                self.search_fused_requests(
                    [RetrievalRequest(query=zero_q, tenant="~warmup", k=kk,
                                      gate_enabled=True, boost=(i == 0))
                     for i in range(g)], **kw)
                self.search_fused_requests(
                    [RetrievalRequest(query=zero_q, tenant="~warmup", k=kk,
                                      gate_enabled=True)
                     for i in range(g)], **kw)
            except PlanInfeasible:
                tel.enabled = prev
                tel.bump("plan.warmup_skipped", labels={"path": "serve"})
                continue
            finally:
                tel.enabled = prev
            ms = (time.perf_counter() - t0) * 1e3
            tel.record("kernel.warmup_ms", ms,
                       labels={"mode": mode, "batch": str(g)})
            out[(mode, g)] = ms
        return out

    def _hbm_once(self, key) -> bool:
        """True for the ONE serving dispatch that records ``key``'s HBM
        gauge (two read dispatches may ask together)."""
        with self._serve_shared_lock:
            if key in self._hbm_recorded:
                return False
            self._hbm_recorded.add(key)
            return True

    def _maybe_record_hbm(self, route: _ServeRoute, st, read, args,
                          statics) -> None:
        """Record the ``memory_analysis()`` peak-HBM gauge for one fused
        serving geometry, once per (mode × k-bucket × cap/nbr) key —
        "Memory Safe Computations with XLA": compiled-program introspection
        is cheap, so every kernel the serving path builds reports its peak
        footprint before a new size/mode combination can OOM in production.
        Opt-in (``telemetry_hbm``) because the AOT lower+compile of the
        read twin is an extra compile (never an extra dispatch)."""
        if not self.telemetry_hbm or not self.telemetry.enabled:
            return    # never consume the once-key while warmup mutes the registry
        mode = route.mode
        key = (mode,) + tuple(sorted(statics.items()))
        if not self._hbm_once(key):
            return
        try:
            lowered = read.lower(st, *self._serve_operands(route, st), *args,
                                 **statics)
            peak = peak_bytes(lowered.compile().memory_analysis())
        except Exception:   # noqa: BLE001 — observability must never serve 500s
            return
        if peak is not None:
            labels = {"mode": mode,
                      "k": str(statics.get("k")),
                      "rows": str(st.salience.shape[0]),
                      "batch": str(int(args[2].shape[0])),
                      "mesh": (f"{self._n_parts}x{self.shard_axis}"
                               if self.mesh is not None else "1")}
            if mode.startswith("pq"):
                # the serve-path gauge check_hbm_budget.py's pq=true
                # sweep reads (ISSUE 16 satellite); slack sizes the
                # exact-rescore shortlist the cost model must over-bound
                labels["pq"] = "true"
                labels["slack"] = str(int(self.coarse_slack))
            if self._sem_host is not None and "sem_block" in statics:
                # ring geometry for check_hbm_budget.py's semantic-cache
                # sweep (ISSUE 20): resident ring + [batch, slots] probe
                labels["sem_slots"] = str(self._sem_host.slots)
                labels["sem_width"] = str(self._sem_host.width)
            self.telemetry.gauge("kernel.peak_hbm_bytes", peak,
                                 labels=labels)
            # Calibrate the admission model against the measured truth
            # (ISSUE 11): predictions must over-bound every recorded
            # gauge — the multiplier grows here whenever one beats it.
            self.planner.observe_gauge(
                Geometry(kind="serve", mode=mode,
                         batch=int(args[2].shape[0]),
                         rows=int(st.salience.shape[0]), dim=self.dim,
                         k=int(statics.get("k") or 1),
                         dtype_bytes=int(np.dtype(self.dtype).itemsize),
                         mesh_parts=self._n_parts,
                         edge_cap=self.edge_state.capacity,
                         nprobe=int(statics.get("nprobe") or 0),
                         scan_chunk=int(statics.get("scan_chunk") or 0),
                         slack=int(self.coarse_slack),
                         sem_slots=(self._sem_host.slots
                                    if self._sem_host is not None
                                    and "sem_block" in statics else 0),
                         sem_width=(self._sem_host.width
                                    if self._sem_host is not None
                                    and "sem_block" in statics else 0)),
                peak)

    def _demux_fused(self, reqs, results, valid, boost_on, gate_s, gate_r,
                     ann_s, ann_r, fast, cap, lengths):
        """Per-request demux of the unpacked fused readback — shared by the
        single-chip and the pod-sharded dispatch. ``lengths`` is the
        decode bound: the readback's per-query live-length counter, so a
        k=4 request in a K-ceiling batch decodes 4 columns, not K."""
        for i, r in enumerate(reqs):
            if not valid[i]:
                continue
            res = results[i]
            ids, scores = decode_topk(ann_s[i:i + 1], ann_r[i:i + 1],
                                      self.row_to_id, S.NEG_INF,
                                      limit=min(int(r.k), cap),
                                      lengths=lengths[i:i + 1])[0]
            res.ids, res.scores = ids, scores
            if gate_s[i] > S.NEG_INF / 2:
                res.gate_id = self.row_to_id.get(int(gate_r[i]))
                res.gate_score = float(gate_s[i])
            res.fast = bool(fast[i])
            res.boosted = bool(boost_on[i] and not fast[i])
        return results

    def _fused_sharded_kernels(self, mode: str, k_bucket: int,
                               cap_take: int, max_nbr: int,
                               sem: bool = False):
        # k_bucket IS the static ceiling, identical for every batch, so a
        # mixed-k request stream compiles one distributed program per mode.
        key = (mode, k_bucket, cap_take, max_nbr)
        if sem:
            key = key + ("sem",)
        with self._serve_shared_lock:   # the LRU reorders on every get
            kern = self._fused_sharded_cache.get(key)
            if kern is None:
                kern = S.make_fused_sharded(
                    self.mesh, self.shard_axis, k=k_bucket,
                    cap_take=min(cap_take, k_bucket), max_nbr=max_nbr,
                    mode=mode, slack=self.coarse_slack, sem=sem)
                self._fused_sharded_cache.put(key, kern)
                self.telemetry.gauge("kernel.cache_entries",
                                     len(self._fused_sharded_cache),
                                     labels={"surface": "fused_sharded"})
        return kern

    def _sharded_tables(self, st, tiered: bool) -> tuple:
        """The distributed program's extra tables against ``st``: the int8
        shadow (quant), with the residency mask (tiered), row-sharded like
        the master; none in exact mode."""
        if tiered:
            return (*self._int8_shadow_for(st), self.tiering.cold_mask_dev())
        return self._int8_shadow_for(st) if self.int8_serving else ()

    def _stage_fused_sharded(self, st, indptr, nbr, car: RequestCarrier,
                             k_bucket, cap_take, max_nbr, mode,
                             boosting: bool, *, sem=None) -> _StagedSharded:
        """Everything the pod serving dispatch (ISSUE 5) needs before its
        launch, made under ``lz.index.stage`` as on one chip: the compiled
        program for the batch's geometry and the request carrier ``car``,
        the ONE host operand (``_hand_requests``). ``indptr``/``nbr``
        are the PER-SHARD CSR slices ``_csr_for`` builds under a mesh;
        ``mode`` is the route's family (``exact`` / ``quant`` / ``tiered``),
        ``k_bucket`` the static ceiling. A batch that asked for no boost
        (``boosting`` False) takes the read twin."""
        tiered = mode == "tiered"
        kern = self._fused_sharded_kernels(mode, k_bucket, cap_take,
                                           max_nbr, sem=sem is not None)
        sem_tail = () if sem is None else (sem,)
        sargs = (indptr, nbr, self._hand_requests(car, "sharded_" + mode))
        pad_n = car.buf.shape[0]
        # what sharded_topk_merge gathers: every shard's k_merge candidates
        # of every padded query
        self.telemetry.bump(
            "serve.merge_candidates",
            self._n_parts * pad_n
            * (k_bucket + (self.coarse_slack if tiered else 0)),
            labels={"mode": "sharded_" + mode})
        if self.telemetry_hbm and self.telemetry.enabled:
            hkey = ("sharded", mode, k_bucket, cap_take, max_nbr)
            if self._hbm_once(hkey):
                try:
                    peak = peak_bytes(kern.read.lower(
                        st, self._sharded_tables(st, tiered), *sargs,
                        *sem_tail).compile().memory_analysis())
                except Exception:   # noqa: BLE001 — never fail the serve
                    peak = None
                if peak is not None:
                    self.telemetry.gauge(
                        "kernel.peak_hbm_bytes", peak,
                        labels={"mode": f"sharded_{mode}",
                                "k": str(k_bucket),
                                "rows": str(st.salience.shape[0]),
                                "batch": str(pad_n),
                                "mesh": f"{self._n_parts}x{self.shard_axis}"})
                    self.planner.observe_gauge(
                        Geometry(kind="serve", mode=f"sharded_{mode}",
                                 batch=pad_n,
                                 rows=int(st.salience.shape[0]), dim=self.dim,
                                 k=int(k_bucket),
                                 dtype_bytes=int(
                                     np.dtype(self.dtype).itemsize),
                                 mesh_parts=self._n_parts,
                                 edge_cap=self.edge_state.capacity),
                        peak)
        return _StagedSharded(kern, sargs, boosting, sem_tail)

    def _serve_fused_sharded(self, staged: _StagedSharded, mode: str,
                             tiered: bool, force_copy: bool):
        """The BOOSTING pod dispatch: the full chat-turn program as ONE
        distributed shard_map dispatch against the row-sharded arena, its
        boost scatters shard-local. The donation gate is the same refcount
        contract as every other mutation: donate only when this index
        provably holds the sole arena reference — so the caller has dropped
        its own snapshot before it calls. ``force_copy``: a post-OOM replan
        always dispatches through the non-donating twin (ISSUE 11)."""
        with self._state_lock:
            cur = self._state
            tables = self._sharded_tables(cur, tiered)
            sole = (not force_copy
                    and sys.getrefcount(cur) <= self._SOLE_REFS)
            if not sole:
                self.telemetry.bump("serve.copy_dispatches",
                                    labels={"mode": mode})
            out = self._guarded(
                lambda fn: fn(cur, tables, *staged.sargs, *staged.sem_tail),
                staged.kern.serve, staged.kern.serve_copy, sole, (cur,),
                "serve_sharded")
            new_state, packed = out[0], out[1:]
            del cur
            self.state = new_state
        return packed if staged.sem_tail else packed[0]

    def apply_boosts(self, entries: Dict[str, Tuple[int, int, float]],
                     acc_boost: float, nbr_boost: float) -> None:
        """Flush deferred (access_count, neighbor_count, latest_now) boost
        accumulators — many cache-hit chat turns' worth of salience
        bookkeeping — in ONE donated scatter (``arena_apply_boosts``).
        Positive capped adds commute, so the summed counts reproduce the
        serial per-turn sequence exactly."""
        rows, accs, nbrs, nows = [], [], [], []
        for qid, (acc, nbr, now) in entries.items():
            r = self.id_to_row.get(qid)
            if r is None:
                continue
            rows.append(r)
            accs.append(int(acc))
            nbrs.append(int(nbr))
            nows.append(float(now) - self.epoch)
        if not rows:
            return
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        b = len(padded)
        acc_arr = np.zeros((b,), np.int32)
        acc_arr[:len(accs)] = accs
        nbr_arr = np.zeros((b,), np.int32)
        nbr_arr[:len(nbrs)] = nbrs
        now_arr = np.full((b,), S.NEG_INF, np.float32)   # pad: .max() no-op
        now_arr[:len(nows)] = nows
        self._apply_arena(
            S.arena_apply_boosts, S.arena_apply_boosts_copy,
            jnp.asarray(padded), jnp.asarray(acc_arr), jnp.asarray(nbr_arr),
            jnp.asarray(now_arr), jnp.float32(acc_boost),
            jnp.float32(nbr_boost))

    # ------------------------------------------------------- numeric sweeps
    def update_access(self, ids: Sequence[str], boost: float = 0.05,
                      now: Optional[float] = None) -> None:
        rows = [self.id_to_row[i] for i in ids if i in self.id_to_row]
        if not rows:
            return
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        self._apply_arena(
            S.arena_update_access, S.arena_update_access_copy,
            jnp.asarray(padded),
            jnp.float32((now if now is not None else time.time()) - self.epoch),
            jnp.float32(boost))

    def boost(self, ids: Sequence[str], boost: float = 0.02,
              now: Optional[float] = None) -> None:
        """Neighbor boost: salience bump + freshness, no access increment."""
        rows = [self.id_to_row[i] for i in ids if i in self.id_to_row]
        if not rows:
            return
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        self._apply_arena(
            S.arena_boost, S.arena_boost_copy, jnp.asarray(padded),
            jnp.float32((now if now is not None else time.time()) - self.epoch),
            jnp.float32(boost))

    def restore_access(self, ids: Sequence[str], access_counts: Sequence[int],
                       last_accessed: Sequence[float]) -> None:
        """Put persisted access history back onto freshly-added arena rows
        (``add`` zeroes it for new inserts)."""
        rows, acs, las = [], [], []
        for i, ac, la in zip(ids, access_counts, last_accessed):
            r = self.id_to_row.get(i)
            if r is not None:
                rows.append(r)
                acs.append(int(ac))
                las.append(float(la) - self.epoch)
        if not rows:
            return
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        b = len(padded)
        ac_arr = np.zeros((b,), np.int32)
        ac_arr[:len(acs)] = acs
        la_arr = np.zeros((b,), np.float32)
        la_arr[:len(las)] = las
        self._apply_arena(
            S.arena_restore_access, S.arena_restore_access_copy,
            jnp.asarray(padded), jnp.asarray(ac_arr), jnp.asarray(la_arr))

    def merge_touch(self, ids: Sequence[str], candidate_saliences: Sequence[float],
                    now: Optional[float] = None) -> None:
        """Dedup-merge: salience=max(old, candidate), access+1, refresh."""
        rows, sals = [], []
        for i, s in zip(ids, candidate_saliences):
            if i in self.id_to_row:
                rows.append(self.id_to_row[i])
                sals.append(float(s))
        if not rows:
            return
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        sal = np.zeros((len(padded),), np.float32)
        sal[:len(sals)] = sals
        self._apply_arena(
            S.arena_merge_touch, S.arena_merge_touch_copy,
            jnp.asarray(padded), jnp.asarray(sal),
            jnp.float32((now if now is not None else time.time()) - self.epoch))

    def decay(self, tenant: str, rate: float, salience_floor: float = 0.2) -> None:
        """Classic per-tenant decay tick — arena salience + edge weights in
        ONE fused dispatch (ISSUE 19 satellite; this used to be two device
        round trips per tenant per tick)."""
        tid = self._tenants.get(tenant)
        if tid is None:
            return
        with self._state_lock:
            arena, edges = self._state, self._edge_state
            sole = (sys.getrefcount(arena) <= self._SOLE_REFS
                    and sys.getrefcount(edges) <= self._SOLE_REFS)
            new_arena, new_edges = self._guarded(
                lambda fn: self._lifecycle_dispatch(
                    fn, arena, edges, jnp.int32(tid), jnp.float32(rate),
                    jnp.float32(salience_floor)),
                S.decay_fused, S.decay_fused_copy, sole, (arena, edges),
                "decay")
            del arena, edges
            self.state = new_arena
            self.edge_state = new_edges

    def evict_candidates(self, tenant: str, k: int, now: Optional[float] = None,
                         weights: Tuple[float, float, float] = (0.5, 0.3, 0.2)
                         ) -> List[Tuple[str, float]]:
        """k least-important (id, importance) pairs for a tenant."""
        tid = self._tenants.get(tenant)
        if tid is None:
            return []
        # bucket k to a power of two so jit specializations stay bounded
        k_bucket = min(self.state.capacity, max(8, 1 << (max(1, k - 1)).bit_length()))
        imps, rows = S.arena_evict_candidates(
            self.state, jnp.int32(tid),
            jnp.float32((now if now is not None else time.time()) - self.epoch),
            jnp.float32(weights[0]), jnp.float32(weights[1]), jnp.float32(weights[2]),
            k_bucket)
        h_imps, h_rows = fetch_packed(imps, rows)      # ONE readback RTT
        out = []
        for imp, r in zip(h_imps, h_rows):
            if not np.isfinite(imp):
                continue
            node_id = self.row_to_id.get(int(r))
            if node_id is not None:
                out.append((node_id, float(imp)))
        return out[:k]

    # ------------------------------------------------ device-side lifecycle
    def _lifecycle_dispatch(self, fn, *args, **kwargs):
        """Every lifecycle device program goes through here — bench and
        the jit-counter tests wrap it (one call == one dispatch, single
        chip or distributed), mirroring ``_ingest_dispatch``."""
        self.lifecycle_dispatch_count += 1
        return fn(*args, **kwargs)

    def _lifecycle_sharded_kernels(self, prune_cap: int, archive_k: int
                                   ) -> S.LifecycleShardedKernels:
        """Cached distributed lifecycle-sweep programs per (prune_cap,
        archive_k) bucket — both are pow2-bucketed by the caller, so the
        cache stays tiny."""
        key = (prune_cap, archive_k)
        kern = self._lifecycle_sharded_cache.get(key)
        if kern is None:
            kern = S.make_lifecycle_sharded(
                self.mesh, self.shard_axis, prune_cap=prune_cap,
                archive_k=archive_k)
            self._lifecycle_sharded_cache.put(key, kern)
            self.telemetry.gauge("kernel.cache_entries",
                                 len(self._lifecycle_sharded_cache),
                                 labels={"surface": "lifecycle_sharded"})
        return kern

    def _apply_lifecycle(self, *args, prune_cap: int, archive_k: int):
        """Combined arena+edges donation gate for the all-tenant sweep:
        BOTH states hand off through ONE ``_guarded`` dispatch (compound
        sole check, mirror of ``_apply_fused``); returns the packed
        payload. Under a mesh the program is the ``make_lifecycle_sharded``
        composition — still ONE distributed dispatch."""
        sharded = self.mesh is not None
        with self._state_lock:
            arena, edges = self._state, self._edge_state
            sole = (sys.getrefcount(arena) <= self._SOLE_REFS
                    and sys.getrefcount(edges) <= self._SOLE_REFS)
            if sharded:
                kern = self._lifecycle_sharded_kernels(prune_cap, archive_k)
                new_arena, new_edges, payload = self._guarded(
                    lambda fn: self._lifecycle_dispatch(
                        fn, arena, edges, *args),
                    kern.sweep, kern.sweep_copy, sole, (arena, edges),
                    "lifecycle")
            else:
                new_arena, new_edges, payload = self._guarded(
                    lambda fn: self._lifecycle_dispatch(
                        fn, arena, edges, *args, prune_cap=prune_cap,
                        archive_k=archive_k),
                    S.lifecycle_sweep, S.lifecycle_sweep_copy, sole,
                    (arena, edges), "lifecycle")
            del arena, edges
            self.state = new_arena
            self.edge_state = new_edges
        return payload

    def _prune_cap(self) -> int:
        """Static compaction-buffer bucket for the prune kernels: pow2 of
        the live host edge count (so the cap can never bind — every weak
        edge fits), floored to bound jit specializations, capped at the
        pool size. The bucket only ever GROWS (high-water mark): a
        draining edge population crossing pow2 boundaries downward would
        otherwise recompile the fused sweep on every crossing — an
        oversized compaction buffer costs a few KiB of readback, a
        recompile stalls live serving for hundreds of ms."""
        cap = min(self.edge_state.capacity,
                  max(256, next_pow2(max(1, len(self.edge_slots))),
                      self._prune_cap_hwm))
        self._prune_cap_hwm = cap
        return cap

    def _lifecycle_geometry(self, tv: int, archive_k: int) -> Geometry:
        """The sweep's planner geometry: ``batch`` carries the verdict-
        tenant count (the [Tv, rows] masked-importance tile is the
        transient high-water mark), ``k`` the archive depth."""
        return Geometry(
            kind="lifecycle", mode="lifecycle", batch=max(1, int(tv)),
            rows=self.state.salience.shape[0], dim=self.dim,
            k=max(1, int(archive_k)),
            dtype_bytes=int(np.dtype(self.dtype).itemsize),
            mesh_parts=self._n_parts, edge_cap=self.edge_state.capacity,
            pool_rows=(self.state.emb.shape[0]
                       if self.state.row_map is not None else 0))

    def _maybe_record_lifecycle_hbm(self, dev_args, prune_cap: int,
                                    archive_k: int, tv: int) -> None:
        """Opt-in peak-HBM gauge for one sweep geometry (maintenance twin
        of ``_maybe_record_ingest_hbm``): AOT-lower the non-donating twin
        once per (tenants, k, prune_cap, rows, mesh) key and record
        ``kernel.peak_hbm_bytes{path="lifecycle",...}`` so
        ``scripts/check_hbm_budget.py`` sweeps maintenance geometries
        too. One extra compile, zero extra dispatches."""
        if not self.telemetry_hbm or not self.telemetry.enabled:
            return
        key = ("lifecycle", tv, archive_k, prune_cap,
               self.state.salience.shape[0])
        if key in self._hbm_recorded:
            return
        self._hbm_recorded.add(key)
        try:
            with self._state_lock:
                arena, edges = self._state, self._edge_state
                if self.mesh is not None:
                    kern = self._lifecycle_sharded_kernels(prune_cap,
                                                           archive_k)
                    lowered = kern.sweep_copy.lower(arena, edges, *dev_args)
                else:
                    lowered = S.lifecycle_sweep_copy.lower(
                        arena, edges, *dev_args, prune_cap=prune_cap,
                        archive_k=archive_k)
            peak = peak_bytes(lowered.compile().memory_analysis())
        except Exception:  # noqa: BLE001 — observability must never block
            return
        if peak is not None:
            self.telemetry.gauge(
                "kernel.peak_hbm_bytes", peak,
                labels={"path": "lifecycle", "tenants": str(tv),
                        "k": str(archive_k),
                        "edge_cap": str(self.edge_state.capacity),
                        "rows": str(self.state.salience.shape[0]),
                        "mesh": (f"{self._n_parts}x{self.shard_axis}"
                                 if self.mesh is not None else "1")})
            self.planner.observe_gauge(
                self._lifecycle_geometry(tv, archive_k), peak)

    def _reclaim_pruned_slots(self, pruned_slots: np.ndarray
                              ) -> List[Tuple[str, str]]:
        """Decode a compacted pruned-slot vector (ascending, -1 padded)
        through the ``by_slot`` reverse index — O(pruned) host cleanup
        (ISSUE 19 satellite; the old path scanned the whole edge map)."""
        removed = []
        by_slot = self.edge_slots.by_slot
        for slot in pruned_slots.tolist():
            if slot < 0:
                break                      # compacted prefix ends here
            key = by_slot.get(int(slot))
            if key is None:
                continue                   # device-only edge, no mirror
            removed.append(key)
            self._free_edge_slots.append(self.edge_slots.pop(key))
        if removed:
            self._csr_dirty = True
        return removed

    def lifecycle_sweep(self, passes: Dict[str, int], *, rate: float,
                        salience_floor: float, prune_threshold: float,
                        weights: Tuple[float, float, float] = (0.5, 0.3, 0.2),
                        archive_k: int = 8,
                        now: Optional[float] = None) -> Dict[str, object]:
        """Decay + prune + archive for ALL tenants in ONE donated dispatch
        + ONE packed readback (ISSUE 19).

        ``passes`` maps tenant name → owed decay passes (0/missing =
        skip); the steady-state tick passes 1 per tenant and stays
        bit-identical to the classic per-tenant loop, while catch-up
        ticks replay the closed form. Returns::

            {"verdicts": {tenant: [(node_id, importance, row), ...]},
             "removed_edges": [(qsrc, qtgt), ...],
             "decayed_rows": n, "decayed_edges": n, "pruned_edges": n,
             "prune_total": n, "prune_overflow": 0/1, "dispatches": 1}

        Verdicts are each tenant's bottom-``archive_k`` live non-super
        rows by importance — the archive-means-demote feed for the
        TierPump queue. Removed edges are already reclaimed from the host
        mirror (O(pruned))."""
        swept = {t: int(p) for t, p in passes.items()
                 if int(p) > 0 and t in self._tenants}
        if not swept:
            return {"verdicts": {}, "removed_edges": [], "decayed_rows": 0,
                    "decayed_edges": 0, "pruned_edges": 0, "prune_total": 0,
                    "prune_overflow": 0, "dispatches": 0}
        now_rel = (now if now is not None else time.time()) - self.epoch
        # dense per-tenant-id owed-pass table, pow2-bucketed like pad_rows
        n_tids = max(self._tenants.values()) + 1
        tc = max(8, next_pow2(n_tids))
        passes_arr = np.zeros((tc,), np.int32)
        v_list = sorted(self._tenants[t] for t in swept)
        for t, p in swept.items():
            passes_arr[self._tenants[t]] = p
        v_tids = S.pad_rows(np.asarray(v_list, np.int32), -1)
        k_bucket = min(self.state.capacity,
                       max(8, next_pow2(max(1, archive_k))))
        prune_cap = self._prune_cap()
        dev_args = (jnp.asarray(passes_arr), jnp.asarray(v_tids),
                    jnp.float32(rate), jnp.float32(salience_floor),
                    jnp.float32(prune_threshold), jnp.float32(now_rel),
                    jnp.float32(weights[0]), jnp.float32(weights[1]),
                    jnp.float32(weights[2]))
        # admission: the planner prices the sweep's [Tv, rows] verdict
        # transient before the dispatch commits to it (lifecycle kind)
        if self.planner is not None and self.planner.active:
            self.planner.check_feasible(
                self._lifecycle_geometry(len(v_tids), k_bucket),
                chunkable=False)
        self._maybe_record_lifecycle_hbm(dev_args, prune_cap, k_bucket,
                                         len(v_tids))
        payload = self._apply_lifecycle(
            *dev_args, prune_cap=prune_cap, archive_k=k_bucket)
        host = np.asarray(payload)             # the ONE packed readback
        tv, off = len(v_tids), len(v_tids) * k_bucket
        v_imps = host[:off].view(np.float32).reshape(tv, k_bucket)
        v_rows = host[off:2 * off].reshape(tv, k_bucket)
        pruned_slots = host[2 * off:2 * off + prune_cap]
        tail = host[2 * off + prune_cap:]
        removed = self._reclaim_pruned_slots(pruned_slots)
        by_tid = {tid: name for name, tid in self._tenants.items()}
        verdicts: Dict[str, List[Tuple[str, float, int]]] = {}
        for vi, tid in enumerate(v_list):
            out = []
            for imp, r in zip(v_imps[vi], v_rows[vi]):
                if not np.isfinite(imp):
                    continue
                node_id = self.row_to_id.get(int(r))
                if node_id is not None:
                    out.append((node_id, float(imp), int(r)))
            verdicts[by_tid[tid]] = out[:archive_k]
        self.telemetry.bump("lifecycle.decayed_rows", int(tail[0]))
        self.telemetry.bump("lifecycle.decayed_edges", int(tail[1]))
        self.telemetry.bump("lifecycle.pruned_edges", int(tail[2]))
        if tail[4]:
            self.telemetry.bump("lifecycle.prune_overflow")
        return {"verdicts": verdicts, "removed_edges": removed,
                "decayed_rows": int(tail[0]), "decayed_edges": int(tail[1]),
                "pruned_edges": int(tail[2]), "prune_total": int(tail[3]),
                "prune_overflow": int(tail[4]), "dispatches": 1}

    def link_candidates_multi(self, new_ids: Sequence[str], tenant: str,
                              k: int = 3, shard_modes: Sequence[int] = (1, 0)
                              ) -> Dict[int, Dict[str, List[Tuple[str, float]]]]:
        """Several shard-mode link scans in ONE host round trip.

        The consolidation pipeline needs both the same-shard (mode 1) and
        the any-shard (mode 0) candidate sets per conversation. Both modes
        are masks over the SAME block of query×arena scores, so ONE fused
        kernel streams the arena from HBM once and selects per mode while
        it does (``arena_link_candidates_multi``) — at 1M rows the stream
        is the whole cost, so two modes for the price of one — and all four
        output arrays come back in one packed readback: one host round
        trip per conversation total."""
        rows = [self.id_to_row[i] for i in new_ids if i in self.id_to_row]
        tid = self._tenants.get(tenant)
        if not rows or tid is None:
            return {sm: {} for sm in shard_modes}
        all_rows = np.asarray(rows, np.int32)
        rows_dev = jnp.asarray(S.pad_rows(all_rows, self.state.capacity))
        flat = fetch_packed(*S.arena_link_candidates_multi(
            self.state, rows_dev, rows_dev, jnp.int32(tid),
            min(k, self.state.capacity), tuple(shard_modes)))
        result: Dict[int, Dict[str, List[Tuple[str, float]]]] = {}
        for i, sm in enumerate(shard_modes):
            scores, cand = flat[2 * i], flat[2 * i + 1]
            out: Dict[str, List[Tuple[str, float]]] = {}
            for bi, node_row in enumerate(all_rows.tolist()):
                node_id = self.row_to_id[node_row]
                pairs = []
                for s, c in zip(scores[bi], cand[bi]):
                    if s <= S.NEG_INF / 2:
                        continue
                    cid = self.row_to_id.get(int(c))
                    if cid is not None:
                        pairs.append((cid, float(s)))
                out[node_id] = pairs
            result[sm] = out
        return result

    def link_candidates(self, new_ids: Sequence[str], tenant: str, k: int = 3,
                        shard_mode: int = 0) -> Dict[str, List[Tuple[str, float]]]:
        """Per new node: top-k (existing_id, cosine) candidates — the
        single-mode view of ``link_candidates_multi`` (same ONE dispatch +
        ONE readback; the kernel holds one block's scores, no
        [rows, capacity] tile)."""
        return self.link_candidates_multi(new_ids, tenant, k,
                                          (shard_mode,))[shard_mode]

    def merge_candidates(self, tenant: str, threshold: float = 0.95
                         ) -> List[Tuple[str, str, float]]:
        """All-pairs near-duplicates (intended `_merge_similar_nodes` semantics,
        not the reference's last-node bug): (keep_id, merge_id, sim) triples."""
        tid = self._tenants.get(tenant)
        if tid is None:
            return []
        mask = self.state.alive & (self.state.tenant_id == jnp.int32(tid)) & ~self.state.is_super
        # bf16 arena goes in as-is (f32 accumulation happens inside the
        # matmul); the chunked kernel bounds HBM to one [512, N] tile.
        top_s, top_j = graphops.pairwise_merge_candidates(
            self._emb_logical(self.state), mask, jnp.float32(threshold), k=4)
        top_s, top_j = fetch_packed(top_s, top_j)      # ONE readback RTT
        out = []
        # Only rows with an above-threshold hit reach Python — at 1M rows
        # with few duplicates this loop is O(hits), not O(N) (VERDICT r3 #3).
        hit_rows = np.nonzero((top_j >= 0).any(axis=1))[0]
        for i in hit_rows.tolist():
            a = self.row_to_id.get(i)
            if a is None:
                continue
            for s, j in zip(top_s[i], top_j[i]):
                if j < 0:
                    continue
                b = self.row_to_id.get(int(j))
                if b is not None:
                    out.append((a, b, float(s)))
        return out

    def mean_embedding(self, ids: Sequence[str]) -> np.ndarray:
        rows = [self.id_to_row[i] for i in ids if i in self.id_to_row]
        if not rows:
            return np.zeros((self.dim,), np.float32)
        padded = S.pad_rows(np.asarray(rows, np.int32), self.state.capacity)
        return np.asarray(S.arena_mean_embedding(self.state, jnp.asarray(padded)))

    def get_embedding(self, node_id: str) -> Optional[np.ndarray]:
        """Single-row fetch — COLD-PATH utility (CLI inspection, tests).
        One device→host round trip per call; every per-conversation
        path uses the bulk transfers instead
        (``_bulk_fill_embeddings``, ``pull_numeric_rows``,
        ``mean_embedding``)."""
        r = self.id_to_row.get(node_id)
        if r is None:
            return None
        if self.tiering is not None and self.tiering.cold_np[r]:
            return np.asarray(self.tiering.gather_cold([r])[0], np.float32)
        st = self.state
        return np.asarray(st.emb[S._phys(st, jnp.int32(r))], np.float32)

    def pull_numeric(self) -> Dict[str, np.ndarray]:
        """One bulk device→host transfer of mutable numeric columns, for
        syncing host Node objects after decay/boost sweeps."""
        sal, la, ac = fetch_packed(self.state.salience,
                                   self.state.last_accessed,
                                   self.state.access_count)
        return {"salience": sal, "last_accessed": la + self.epoch,
                "access_count": ac}

    def pull_numeric_rows(self, rows: Sequence[int]) -> Dict[str, np.ndarray]:
        """Selective variant of ``pull_numeric``: gather only the given arena
        rows (the incremental-persistence path syncs dirty rows, not the
        whole 1M-row arena)."""
        r = jnp.asarray(np.asarray(rows, np.int32))
        sal, la, ac = fetch_packed(self.state.salience[r],
                                   self.state.last_accessed[r],
                                   self.state.access_count[r])
        return {"salience": sal, "last_accessed": la + self.epoch,
                "access_count": ac}

    def edge_weights_for(self, keys: Sequence[Tuple[str, str]]
                         ) -> Dict[Tuple[str, str], Tuple[float, int]]:
        """Selective variant of ``edge_weights``: (weight, co) for the given
        edge keys only — one small device gather instead of an O(E) pull."""
        present = [(k, self.edge_slots[k]) for k in keys if k in self.edge_slots]
        if not present:
            return {}
        slots = jnp.asarray(np.asarray([s for _, s in present], np.int32))
        w, co = fetch_packed(self.edge_state.weight[slots],
                             self.edge_state.co[slots])
        return {k: (float(w[i]), int(co[i])) for i, (k, _) in enumerate(present)}

    # ---------------------------------------------------------------- edges
    def _alloc_edge_slots(self, n: int) -> List[int]:
        while len(self._free_edge_slots) < n:
            old = self.edge_state.capacity
            if self._pager is not None:
                # Paged arena: the edge pool grows by whole pages — the
                # transient copy is O(old + pages), never a doubling spike.
                deficit = n - len(self._free_edge_slots)
                new = self._round_capacity(
                    old + max(deficit, self.page_rows), block=False)
            else:
                new = self._grown_capacity(old, block=False)
            self.edge_state = self._grow_rows(S.init_edges, S.grow_edges,
                                              self.edge_state, new)
            self._free_edge_slots = list(range(new - 1, old - 1, -1)) + self._free_edge_slots
        return [self._free_edge_slots.pop() for _ in range(n)]

    def add_edges(self, triples: Sequence[Tuple[str, str, float]], tenant: str,
                  reinforce: float = 0.1, now: Optional[float] = None) -> None:
        """(src_id, tgt_id, weight) batch. Existing edges are reinforced
        (+0.1 capped, co+1); new ones inserted. A key repeated WITHIN the
        batch inserts once then reinforces (the scatter accumulates duplicate
        slots), matching what sequential singleton calls would do."""
        with self.telemetry.span("index.edges"):
            now = (now if now is not None else time.time()) - self.epoch
            new, existing = [], []
            pending = set()
            for src, tgt, w in triples:
                if src not in self.id_to_row or tgt not in self.id_to_row:
                    continue
                key = (src, tgt)
                if key in self.edge_slots:
                    existing.append(self.edge_slots[key])
                elif key in pending:
                    existing.append(key)    # slot resolved after the insert
                else:
                    pending.add(key)
                    new.append((key, w))
            if new:
                slots = self._alloc_edge_slots(len(new))
                for (key, _), slot in zip(new, slots):
                    self.edge_slots[key] = slot
                self._csr_dirty = True
                self.telemetry.bump("index.edges_added", len(new))
                cap = self.edge_state.capacity
                padded = S.pad_rows(np.asarray(slots, np.int32), cap)
                b = len(padded)
                src_r = np.full((b,), -1, np.int32)
                tgt_r = np.full((b,), -1, np.int32)
                w = np.zeros((b,), np.float32)
                live = np.zeros((b,), bool)
                for i, ((s_id, t_id), wt) in enumerate(new):
                    src_r[i] = self.id_to_row[s_id]
                    tgt_r[i] = self.id_to_row[t_id]
                    w[i] = wt
                    live[i] = True
                self._apply_edges(
                    S.edges_add, S.edges_add_copy,
                    jnp.asarray(padded), jnp.asarray(src_r),
                    jnp.asarray(tgt_r), jnp.asarray(w),
                    jnp.ones((b,), jnp.int32), jnp.float32(now),
                    jnp.int32(self.tenant_id(tenant)), jnp.asarray(live))
            if existing:
                slots = [self.edge_slots[s] if isinstance(s, tuple) else s
                         for s in existing]
                padded = S.pad_rows(np.asarray(slots, np.int32),
                                    self.edge_state.capacity)
                self._apply_edges(
                    S.edges_reinforce, S.edges_reinforce_copy,
                    jnp.asarray(padded), jnp.float32(reinforce),
                    jnp.float32(now))

    def prune_edges(self, tenant: str, threshold: float) -> List[Tuple[str, str]]:
        """Drop the tenant's weak edges; host cleanup is O(pruned) via the
        kernel's compacted pruned-slot list (ISSUE 19 satellite — this
        used to re-scan the whole ``edge_slots`` map per prune)."""
        tid = self._tenants.get(tenant)
        if tid is None:
            return []
        prune_cap = self._prune_cap()
        with self._state_lock:
            cur = self._edge_state
            sole = sys.getrefcount(cur) <= self._SOLE_REFS
            new_state, slots = self._guarded(
                lambda fn: fn(cur, jnp.int32(tid), jnp.float32(threshold),
                              prune_cap=prune_cap),
                S.edges_prune, S.edges_prune_copy, sole, (cur,), "edges")
            del cur
            self.edge_state = new_state
        return self._reclaim_pruned_slots(np.asarray(slots))

    def edge_weights(self) -> Dict[Tuple[str, str], Tuple[float, int]]:
        """Bulk pull of (weight, co_occurrence) for host Edge sync."""
        w, co = fetch_packed(self.edge_state.weight, self.edge_state.co)
        return {k: (float(w[slot]), int(co[slot])) for k, slot in self.edge_slots.items()}

    def components(self) -> List[List[str]]:
        """Connected components via device label propagation."""
        n = self.state.capacity + 1
        labels = graphops.connected_components(
            self.edge_state.src, self.edge_state.tgt, self.edge_state.alive,
            self.state.alive, n)
        labels = np.asarray(labels)
        groups: Dict[int, List[str]] = {}
        for row, node_id in self.row_to_id.items():
            lbl = int(labels[row])
            if lbl >= 0:
                groups.setdefault(lbl, []).append(node_id)
        return list(groups.values())
