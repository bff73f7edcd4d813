"""ShardedMemoryIndex: the memory index spread across a device mesh.

This is the pod-scale variant of ``core.index.MemoryIndex`` (SURVEY §2.3's
"index model-parallelism" + "tenant partitioning = mesh sharding"): every
arena column — embeddings, salience, access counters, tenant and super-node
flags — is row-sharded over the mesh 'data' axis (HBM-resident on every
chip), queries are replicated, and serving is shard-local scan →
``all_gather`` merge → shard-local boost scatters.

Serving (ISSUE 5): ``serve_requests`` runs the FULL chat-turn retrieval
program — masked super-node top-1 gate, main ANN top-k, CSR neighbor
gather over a row-sharded edge arena, and the neighbor- + access-salience
boost scatters — as ONE distributed ``shard_map`` dispatch + ONE packed
readback per coalesced mega-batch (``core.state.make_fused_sharded``; the
pre-ISSUE-5 pod path served a plain multitenant top-k that silently
DROPPED the gate, the neighbor gather, and every boost). Per-request
tenants ride into the kernel as a replicated column, so one mixed-tenant
batch dispatches once with mask-enforced isolation; boosts land as
shard-local scatters (each chip writes only the rows it owns — no boost
ever crosses a chip boundary), and the kernel batch is keyed on the batch
max-k (pow2-bucketed), so a request's ``k`` is never silently truncated
to a construction-time constant. With ``int8_serving`` the shard-local
scan streams the per-chip int8 shadow (coarse top-(k+slack) + exact
rescore — on real TPU that also rides the MXU int8 path), and with a
build published by ``ivf_build`` it becomes the centroid prefilter over
per-shard LOCAL member tables. ``serve_fused=False`` keeps the classic
single-purpose multitenant top-k for A/B and fallback.

Tenant partitioning (the EP analog): with ``tenant_affinity`` on, every
tenant's rows are allocated inside one mesh partition (hash(tenant) % n),
so per-tenant sweeps (decay, eviction scoring) touch one chip's rows and
multi-tenant fleets spread across the pod — replacing the reference's
row-level `user_id` BTREE filter (vector_store.py:55) with physical placement.
Multi-host works unchanged: build the mesh after ``jax.distributed.initialize``.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import (_EdgeSlotMap, build_host_csr,
                                    link_pool_dev, link_pool_size,
                                    split_csr)
from lazzaro_tpu.ops.topk import make_sharded_topk
from lazzaro_tpu.parallel.mesh import shard_stacked
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

NEG_INF = -1e30


@jax.jit
def _shadow_update(q8, scale, rows, emb_stored):
    """Incremental int8-shadow maintenance for freshly written rows —
    O(batch), mirroring the fused-ingest ``_shadow_scatter``."""
    from lazzaro_tpu.ops.quant import quantize_rows

    q_new, s_new = quantize_rows(emb_stored)
    return q8.at[rows].set(q_new), scale.at[rows].set(s_new)


@jax.jit
def _pq_codes_update(book_cent, codes, rows, emb_stored):
    """Incremental PQ-code maintenance for freshly written rows (ISSUE
    16) — the non-fused-write twin of the in-kernel ``_pq_scatter``:
    encode the stored vectors against the frozen codebook and patch the
    batch's rows in place."""
    from lazzaro_tpu.ops.pq import encode_pq

    return codes.at[rows].set(encode_pq(book_cent, emb_stored))


class ShardedMemoryIndex:
    # References to the arena pytree at the donation gate when this index
    # is the sole owner: the ``_arena`` attribute, the ``cur`` local, and
    # ``sys.getrefcount``'s own argument (same contract as MemoryIndex).
    _SOLE_REFS = 3

    def __init__(self, mesh: Mesh, dim: int, capacity: int = 1 << 20,
                 axis: str = "data", dtype=jnp.bfloat16,
                 tenant_affinity: bool = True, k: int = 10,
                 serve_fused: bool = True, int8_serving: bool = False,
                 pq_serving: bool = False,
                 coarse_slack: int = 8, cap_take: int = 5,
                 max_nbr: int = 32, super_gate: float = 0.4,
                 acc_boost: float = 0.05, nbr_boost: float = 0.02,
                 epoch: Optional[float] = None, telemetry=None,
                 telemetry_hbm: bool = False,
                 serve_k_max: int = 128, serve_pad_granularity: int = 8,
                 serve_kernel_cache_max: int = 8,
                 edge_capacity: int = 1 << 17,
                 ingest_fused: bool = True,
                 ivf_online: bool = True,
                 ivf_member_cap_factor: int = 4,
                 ivf_online_eta: float = 1.0,
                 hbm_budget_bytes: int = 0,
                 hbm_headroom_fraction: float = 0.1,
                 plan_max_splits: int = 16,
                 plan_calibration_path: Optional[str] = None,
                 planner: Optional[HbmPlanner] = None,
                 semantic_cache: bool = False,
                 semantic_cache_slots: int = 64,
                 semantic_cache_threshold: float = 0.985,
                 semantic_cache_block: int = 16):
        self.mesh = mesh
        # Serving telemetry (ISSUE 6): same registry contract as
        # MemoryIndex — spans per dispatch, device counters decoded from
        # the packed readback tail, opt-in peak-HBM gauges per kernel.
        self.telemetry = telemetry if telemetry is not None \
            else default_registry()
        self.telemetry_hbm = bool(telemetry_hbm)
        self._hbm_recorded: set = set()
        # Admission-time HBM planner (ISSUE 11): same contract as
        # MemoryIndex — the pod path admits fused, splits the query batch
        # into planned sub-dispatches, or rejects typed. (The distributed
        # kernels keep their built-in chunk structure; the scan-chunk
        # override is a single-chip degradation rung.)
        self.planner = planner if planner is not None else HbmPlanner(
            budget_bytes=hbm_budget_bytes,
            headroom_fraction=hbm_headroom_fraction,
            telemetry=self.telemetry,
            granularity=max(1, int(serve_pad_granularity)),
            max_splits=plan_max_splits,
            calibration_path=plan_calibration_path)
        self.dispatch_count = 0
        self.axis = axis
        self.dim = dim
        self.n_parts = mesh.shape[axis]
        # Replica-group serving (ISSUE 18): set >1 by ReplicaPlacement on
        # each group's index — this index then owns one FULL arena copy
        # row-sharded over a group-local sub-mesh, and the group count
        # rides into geometry admission and the peak-HBM gauge labels so
        # the planner/CI can see the fleet-wide replication factor.
        self.replica_groups = 1
        # Row geometry: the arena carries capacity+1 rows (last = the
        # sentinel scratch row, core.state contract) and the TOTAL must
        # divide the mesh axis — capacity is rounded UP, never rejected.
        total = capacity + 1
        total = -(-total // self.n_parts) * self.n_parts
        self.capacity = total - 1
        self.part_rows = total // self.n_parts
        self.tenant_affinity = tenant_affinity
        self.dtype = dtype
        self.epoch = float(epoch if epoch is not None else time.time())

        self.serve_fused = bool(serve_fused)
        self.int8_serving = bool(int8_serving)
        self.pq_serving = bool(pq_serving)
        self.coarse_slack = max(0, int(coarse_slack))
        self.cap_take = int(cap_take)
        self.max_nbr = int(max_nbr)
        self.super_gate = float(super_gate)
        self.acc_boost = float(acc_boost)
        self.nbr_boost = float(nbr_boost)

        self._row_sh = NamedSharding(mesh, P(axis))
        self._mat_sh = NamedSharding(mesh, P(axis, None))
        self._rep = NamedSharding(mesh, P())
        self._stacked = shard_stacked(mesh, axis)
        # Donation-safe recovery (ISSUE 10): same contract as MemoryIndex —
        # transient failures retry through the copying twin, a consumed
        # input poisons the index and raises typed.
        self.dispatch_retry_max = 2
        self.dispatch_retry_backoff_s = 0.005
        self._poisoned = False

        self._state_lock = threading.RLock()
        self._arena = self._reshard(S.init_arena(self.capacity, dim, dtype))

        # host bookkeeping: per-partition free lists (the global sentinel
        # row — the last row of the last partition — is never allocated),
        # global id maps, host edge map for the CSR shadow, super rows.
        self._free: List[List[int]] = [
            [r for r in range((p + 1) * self.part_rows - 1,
                              p * self.part_rows - 1, -1)
             if r != self.capacity]
            for p in range(self.n_parts)]
        self.id_to_row: Dict[str, int] = {}
        self.row_to_id: Dict[int, str] = {}
        self._tenants: Dict[str, int] = {}
        self.edges: Dict[Tuple[str, str], float] = {}
        self._csr_cache = None             # (indptr_dev, nbr_dev)
        self._csr_dirty = True
        self._super_rows: set = set()

        # int8 serving shadow (row-sharded like the master; rebuilt lazily,
        # maintained incrementally by add()'s scatter once built)
        self._int8_shadow = None
        self._int8_dirty = True

        # PQ serving pack (ISSUE 16): ``(book_cent [m,256,dsub] replicated,
        # codes [rows,m] u8 row-sharded with the master)``. Published
        # COMPLETE by ivf_build, then maintained incrementally — the fused
        # ingest's in-kernel ``_pq_scatter`` and add()'s host patch — so
        # the pack never carries a dirty flag.
        self._pq_pack = None

        # Pod-scale fused ingest (ISSUE 9): a row-sharded edge arena is
        # the write target of the distributed ingest program — the fused
        # kernel's gated link insert compacts accepted edges into it
        # owner-chip-local — while the host edge map (``self.edges``)
        # mirrors every accepted edge from the packed readback, so the
        # serving CSR build and checkpoints are unchanged. Slots are
        # GLOBAL ids; the last slot of the last shard is the sentinel.
        self.ingest_fused = bool(ingest_fused)
        total_e = edge_capacity + 1
        total_e = -(-total_e // self.n_parts) * self.n_parts
        self.edge_capacity = total_e - 1
        self._edge_state = self._reshard(S.init_edges(self.edge_capacity))
        self._free_edge_slots: List[int] = list(
            range(self.edge_capacity - 1, -1, -1))
        self.edge_slots: _EdgeSlotMap = _EdgeSlotMap()
        self._ingest_cache = LRUKernelCache(serve_kernel_cache_max)
        self._ingest_classic_cache = LRUKernelCache(serve_kernel_cache_max)
        self.link_pool_overflows = 0
        self.ingest_dispatch_count = 0

        # IVF serve tables (publish via ivf_build): centroids replicated,
        # member/extras tables split per shard with LOCAL row indices
        self._ivf = None          # (centroids_dev, members_np, residual_np,
        #                            nprobe)
        self._ivf_routed = None   # np bool [rows]
        self._ivf_fresh: List[int] = []
        self._ivf_tabs_cache = None
        # Online IVF maintenance (ISSUE 12), pod twin: with a seeded
        # build, the LIVE coarse tables — ``(cent [C,d] replicated,
        # members [n,C,M] stacked per shard with LOCAL row ids — the
        # exact layout make_fused_sharded mode="ivf" serves from —
        # counts [n,C] REPLICATED per-(shard, cluster) occupancy)`` —
        # ride the distributed ingest dispatch as donated state: the
        # centroid scores join the grouped all_gather as a fourth
        # candidate group, member appends land owner-chip-local, and the
        # mini-batch centroid step is replicated arithmetic.
        self.ivf_online = bool(ivf_online)
        self.ivf_member_cap_factor = max(1, int(ivf_member_cap_factor))
        self.ivf_online_eta = float(ivf_online_eta)
        self._ivf_dev = None      # (cent, members_sh, counts) live tables

        # Tiered memory (ISSUE 8): attach_tiering hangs a TierManager here
        # (per-shard host cold stores — one per mesh partition — plus the
        # row-sharded residency column). ``_emb_gen`` guards the pump's
        # gather→scatter window against racing embedding writes.
        self.tiering = None
        self._emb_gen = 0
        self._csr_flat_cache = None

        self._k = k
        self._search = make_sharded_topk(mesh, axis, k=k)
        # Fused pod serving: per-query k/cap/nprobe as device columns,
        # kernels keyed per MODE at the serve_k_max ceiling.
        self.serve_k_max = max(1, int(serve_k_max))
        self.serve_pad_granularity = max(1, int(serve_pad_granularity))
        # Classic pod serving kernels (serve_fused=False A/B + fallback),
        # keyed by the batch max-k pow2 bucket so a request's k above the
        # construction-time default retraces instead of truncating.
        # LRU-capped (ISSUE 7 satellite) like the fused cache below.
        self._serve_search_cache = LRUKernelCache(serve_kernel_cache_max)
        # Fused distributed serving programs, keyed (mode, k ceiling,
        # nprobe) — one per mode while the ceilings stand; LRU-capped so a
        # changed ceiling evicts, never grows.
        self._fused_cache = LRUKernelCache(serve_kernel_cache_max)

        # Semantic query cache (ISSUE 20): the ring is REPLICATED over
        # the mesh (probe/substitute/writeback run identically on every
        # chip after the all_gather merge), so the single-chip host
        # mirror works unchanged — same hit masks, same LIFO replay.
        self._sem_host = None
        if semantic_cache:
            from lazzaro_tpu.core.index import SemanticCacheHost
            self._sem_host = SemanticCacheHost(
                semantic_cache_slots, dim,
                self.serve_k_max + self.coarse_slack,
                semantic_cache_threshold, semantic_cache_block,
                telemetry=self.telemetry)

    # ------------------------------------------------------------------ util
    def _reshard(self, pytree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(
                a, self._mat_sh if a.ndim == 2 else self._row_sh), pytree)

    @property
    def state(self) -> S.ArenaState:
        with self._state_lock:
            return self._arena

    @state.setter
    def state(self, s: S.ArenaState) -> None:
        self._arena = self._reshard(s)

    # Legacy column views (checkpointing, tests, bench poke these).
    @property
    def emb(self):
        return self.state.emb

    @property
    def alive(self):
        return self.state.alive

    @property
    def tenant(self):
        return self.state.tenant_id

    @property
    def salience(self):
        return self.state.salience

    def tenant_id(self, name: str) -> int:
        if name not in self._tenants:
            self._tenants[name] = len(self._tenants)
        return self._tenants[name]

    def _partition_for(self, tenant: str) -> int:
        if not self.tenant_affinity:
            return int(np.random.default_rng(
                abs(hash(tenant)) % 2**32).integers(self.n_parts))
        return abs(hash(tenant)) % self.n_parts

    def _alloc(self, tenant: str, n: int) -> List[int]:
        """Allocate rows, preferring the tenant's home partition, spilling
        round-robin to others when full."""
        home = self._partition_for(tenant)
        order = [home] + [p for p in range(self.n_parts) if p != home]
        rows: List[int] = []
        for p in order:
            while self._free[p] and len(rows) < n:
                rows.append(self._free[p].pop())
            if len(rows) == n:
                break
        if len(rows) < n:
            raise RuntimeError("ShardedMemoryIndex capacity exhausted")
        return rows

    @property
    def poisoned(self) -> bool:
        """True once a donated dispatch consumed this index's state and
        then failed (recovery: checkpoint restore + journal replay)."""
        return self._poisoned

    def _guarded(self, call, donated, copying, sole, states, mode):
        """Donation-safe executor (ISSUE 10) — the pod twin of
        ``MemoryIndex._guarded``: copy-twin retries on transient failure,
        typed ``ArenaPoisoned`` when the input was consumed."""
        check_not_poisoned(self._poisoned, "ShardedMemoryIndex")
        try:
            return run_guarded(call, donated, copying, sole, states,
                               telemetry=self.telemetry, mode=mode,
                               retries=self.dispatch_retry_max,
                               backoff_s=self.dispatch_retry_backoff_s)
        except ArenaPoisoned:
            self._poisoned = True
            raise

    def _apply_arena(self, donated, copying, *args, **kwargs) -> None:
        """The zero-copy mutation gate (PR 1 contract): donate when this
        index provably holds the sole reference to the arena pytree,
        otherwise run the copying twin so a concurrent reader's snapshot
        is never invalidated."""
        with self._state_lock:
            cur = self._arena
            sole = sys.getrefcount(cur) <= self._SOLE_REFS
            out = self._guarded(lambda fn: fn(cur, *args, **kwargs),
                                donated, copying, sole, (cur,),
                                "pod_arena")
            del cur
            self.state = out

    # The device-program entry point every serve goes through — tests and
    # bench wrap it to count dispatches (one call == one dispatch). The
    # count ALSO lands in the telemetry registry (ISSUE 6 satellite: it
    # used to be reachable only by wrapping this hook).
    def _dispatch(self, fn, *args, **kwargs):
        self.dispatch_count += 1
        self.telemetry.bump("serve.dispatches", labels={"mode": "pod"})
        return fn(*args, **kwargs)

    # The write-path twin: every device program the ingest path runs —
    # the ONE distributed fused dispatch, or each step of the host-driven
    # classic sequence — goes through here, so bench and the jit-counter
    # tests measure ``dispatches_per_conversation`` by wrapping one hook.
    def _ingest_dispatch(self, fn, *args, **kwargs):
        self.dispatch_count += 1
        self.ingest_dispatch_count += 1
        return fn(*args, **kwargs)

    # ------------------------------------------------------- edge arena
    @property
    def edge_state(self) -> S.EdgeState:
        with self._state_lock:
            return self._edge_state

    @edge_state.setter
    def edge_state(self, s: S.EdgeState) -> None:
        self._edge_state = self._reshard(s)

    def _alloc_edge_slots(self, n: int) -> List[int]:
        if len(self._free_edge_slots) < n:
            raise RuntimeError("ShardedMemoryIndex edge capacity exhausted")
        return [self._free_edge_slots.pop() for _ in range(n)]

    def _apply_edges(self, donated, copying, *args, **kwargs) -> None:
        """Edge-arena twin of ``_apply_arena`` (same donation gate)."""
        with self._state_lock:
            cur = self._edge_state
            sole = sys.getrefcount(cur) <= self._SOLE_REFS
            out = self._guarded(
                lambda fn: self._ingest_dispatch(fn, cur, *args, **kwargs),
                donated, copying, sole, (cur,), "pod_edges")
            del cur
            self.edge_state = out

    def _edges_insert_device(self, triples, tenant_id_val: int,
                             now_rel: float) -> List[Tuple[str, str]]:
        """Insert NEW edges into the device edge arena + host maps (the
        classic write path's edge step, and the fused path's overflow
        retry). Keys already registered are skipped."""
        fresh = [(s, t, w) for s, t, w in triples
                 if (s, t) not in self.edge_slots
                 and s in self.id_to_row and t in self.id_to_row]
        if not fresh:
            return []
        slots = self._alloc_edge_slots(len(fresh))
        ecap = self.edge_capacity
        padded = S.pad_rows(np.asarray(slots, np.int32), ecap)
        b = len(padded)
        src_r = np.full((b,), -1, np.int32)
        tgt_r = np.full((b,), -1, np.int32)
        w_arr = np.zeros((b,), np.float32)
        live = np.zeros((b,), bool)
        made = []
        for i, ((s, t, w), slot) in enumerate(zip(fresh, slots)):
            src_r[i] = self.id_to_row[s]
            tgt_r[i] = self.id_to_row[t]
            w_arr[i] = w
            live[i] = True
            self.edge_slots[(s, t)] = slot
            self.edges[(s, t)] = float(w)
            made.append((s, t))
        self._apply_edges(
            S.edges_add, S.edges_add_copy, jnp.asarray(padded),
            jnp.asarray(src_r), jnp.asarray(tgt_r), jnp.asarray(w_arr),
            jnp.ones((b,), jnp.int32), jnp.float32(now_rel),
            jnp.int32(tenant_id_val), jnp.asarray(live))
        self._csr_dirty = True
        return made

    # --------------------------------------------------- fused pod ingest
    def _ingest_kernels(self, k: int, shard_modes: Tuple[int, ...],
                        with_shadow: bool, with_ivf: bool = False,
                        with_pq: bool = False
                        ) -> S.IngestShardedKernels:
        key = (k, shard_modes, with_shadow, with_ivf, with_pq)
        kern = self._ingest_cache.get(key)
        if kern is None:
            kern = S.make_ingest_fused_sharded(
                self.mesh, self.axis, k=k, shard_modes=shard_modes,
                with_shadow=with_shadow, with_ivf=with_ivf,
                with_pq=with_pq)
            self._ingest_cache.put(key, kern)
            self.telemetry.gauge("kernel.cache_entries",
                                 len(self._ingest_cache),
                                 labels={"surface": "pod_ingest"})
        return kern

    def ingest(self, ids: Sequence[str], embeddings: np.ndarray,
               tenant: str, saliences: Optional[Sequence[float]] = None, *,
               dedup_gate: float = 0.95, chain: bool = False,
               chain_weight: float = 0.5, link_k: int = 3,
               link_gate: float = 0.5, link_scale: float = 0.8,
               shard_modes: Sequence[int] = (0,),
               link_accept_hint: float = 1.0,
               now: Optional[float] = None) -> Dict:
        """The pod WRITE path as ONE distributed dispatch (ISSUE 9): dedup
        probe (shard-local top-1 → all_gather merge), intra-batch resolve,
        owner-chip node scatter, merge touch, link scans, gated edge
        insert with prefix-sum pool compaction, and the incremental int8
        shadow update — the full ``ingest_dedup_fused`` program composed
        with the mesh (``state.make_ingest_fused_sharded``), replacing the
        host-driven multi-op sequence (probe dispatch + resolve + add
        scatter + shadow scatter + link-scan dispatch + edge insert) the
        pre-ISSUE-9 pod write path needed for the same semantics.
        ``ingest_fused=False`` keeps that classic sequence for A/B and
        fallback — same verdicts, many dispatches.

        ``ids`` must be fresh (the consolidation contract — the dedup
        verdict decides merge-vs-insert, so re-adding an existing id goes
        through :meth:`add`). Returns ``{"rows", "created", "merged",
        "links", "chains", "counters"}`` with ``merged`` mapping each
        duplicate fact's id to the id it merged into and ``links`` the
        gate-passing similarity edges the device inserted."""
        n = len(ids)
        out_empty = {"rows": [], "created": [], "merged": {}, "links": [],
                     "chains": [], "counters": {}}
        if n == 0:
            return out_empty
        if self.planner is not None and self.planner.active:
            # admission gate (ISSUE 11): typed rejection BEFORE rows or
            # edge slots are allocated; mega-batch splitting happens at
            # the coalescer drain via ``plan_ingest``
            self.planner.check_feasible(
                self._ingest_geometry(n, link_k), chunkable=False)
        for node_id in ids:
            if node_id in self.id_to_row:
                raise ValueError(f"ingest() requires fresh ids: {node_id!r}")
        if saliences is None:
            saliences = [0.5] * n
        shard_modes = tuple(shard_modes)
        emb_np = np.asarray(embeddings, np.float32).reshape(n, self.dim)
        now_abs = now if now is not None else time.time()
        if not self.ingest_fused:
            return self._ingest_classic(
                ids, emb_np, tenant, saliences, dedup_gate=dedup_gate,
                chain=chain, chain_weight=chain_weight, link_k=link_k,
                link_gate=link_gate, link_scale=link_scale,
                shard_modes=shard_modes, now=now_abs)
        tid = self.tenant_id(tenant)
        rows = self._alloc(tenant, n)
        k_eff = max(1, min(int(link_k), self.capacity))
        n_modes = len(shard_modes)
        pool_need = link_pool_size(n_modes * n * k_eff, link_accept_hint)
        n_chain = n if chain else 0
        slots = self._alloc_edge_slots(n_chain + pool_need)
        chain_slot_list = slots[:n_chain]
        link_pool_list = slots[n_chain:]
        ecap = self.edge_capacity
        padded = S.pad_rows(np.asarray(rows, np.int32), self.capacity)
        b = len(padded)

        def pad(vals, fill=0.0, dt=np.float32):
            out = np.full((b,), fill, dt)
            out[:n] = vals
            return out

        emb_p = np.zeros((b, self.dim), np.float32)
        emb_p[:n] = emb_np
        emb_p[n:, 0] = 1.0      # sentinel rows: unit vector (normalizable)
        gids = pad(([0] * n) if chain else ([-1] * n), -1, np.int32)
        chain_slots = np.full((b,), ecap, np.int32)
        chain_slots[:n_chain] = chain_slot_list
        pool_dev = link_pool_dev(link_pool_list, n_modes * b * k_eff, ecap)
        now_rel = now_abs - self.epoch
        with self._state_lock:
            with_shadow = (
                self.int8_serving and not self._int8_dirty
                and self._int8_shadow is not None
                and self._int8_shadow[0].shape[0] == self.capacity + 1)
            with_ivf = self.ivf_online and self._ivf_dev is not None
            with_pq = (self._pq_pack is not None
                       and self._pq_pack[1].shape[0] == self.capacity + 1)
        kern = self._ingest_kernels(k_eff, shard_modes, with_shadow,
                                    with_ivf, with_pq)
        dev_args = (
            jnp.asarray(padded), jnp.asarray(emb_p),
            jnp.asarray(pad(np.asarray(saliences, np.float32))),
            jnp.full((b,), now_rel, jnp.float32),
            jnp.zeros((b,), jnp.int32),
            jnp.asarray(pad([0] * n, -1, np.int32)),
            jnp.asarray(pad([tid] * n, -1, np.int32)),
            jnp.asarray(pad([False] * n, False, bool)),
            jnp.asarray(gids), jnp.asarray(chain_slots), pool_dev,
            jnp.int32(len(link_pool_list)), jnp.float32(now_rel),
            jnp.int32(tid), jnp.float32(dedup_gate),
            jnp.float32(chain_weight), jnp.float32(link_gate),
            jnp.float32(link_scale), jnp.float32(self.ivf_online_eta))
        self._maybe_record_ingest_hbm(kern, dev_args, with_shadow, b,
                                      with_ivf=with_ivf, with_pq=with_pq)
        tel = self.telemetry
        with tel.span("ingest.pod_fused", timer="ingest.dispatch_ms",
                      labels={"kind": "pod_fused"}):
            with self._state_lock:
                arena, edges = self._arena, self._edge_state
                shadow = self._int8_shadow if with_shadow else None
                ivf = self._ivf_dev if with_ivf else None
                pq = self._pq_pack if with_pq else None
                sole = (sys.getrefcount(arena) <= self._SOLE_REFS
                        and sys.getrefcount(edges) <= self._SOLE_REFS
                        and (shadow is None
                             or (sys.getrefcount(shadow[0]) <= 2
                                 and sys.getrefcount(shadow[1]) <= 2))
                        and (ivf is None
                             or (sys.getrefcount(ivf[0]) <= 2
                                 and sys.getrefcount(ivf[1]) <= 2
                                 and sys.getrefcount(ivf[2]) <= 2))
                        and (pq is None
                             or (sys.getrefcount(pq[0]) <= 2
                                 and sys.getrefcount(pq[1]) <= 2)))
                state_args = ((arena, edges)
                              + (shadow if shadow is not None else ())
                              + (ivf if ivf is not None else ())
                              + (pq if pq is not None else ()))
                got = self._guarded(
                    lambda fn: self._ingest_dispatch(fn, *state_args,
                                                     *dev_args),
                    kern.ingest, kern.ingest_copy, sole,
                    (arena, edges, shadow, ivf, pq), "pod_ingest")
                new_arena, new_edges, got = got[0], got[1], got[2:]
                if shadow is not None:
                    self._int8_shadow = (got[0], got[1])
                    got = got[2:]
                if ivf is not None:
                    self._ivf_dev = (got[0], got[1], got[2])
                    got = got[3:]
                if pq is not None:
                    self._pq_pack = (got[0], got[1])
                    got = got[2:]
                flat = got[0]
                del arena, edges, shadow, ivf, pq
                self._arena = new_arena
                self._edge_state = new_edges
            host = fetch_packed(*flat)          # the ONE readback
        tel.bump("ingest.dispatches", labels={"kind": "pod_fused"})
        return self._ingest_finish_host(
            ids, rows, host, chain_slot_list, link_pool_list,
            shard_modes=shard_modes, k_eff=k_eff, tid=tid,
            chain_weight=chain_weight, link_scale=link_scale,
            now_abs=now_abs, shadow_fresh=with_shadow,
            ivf_fresh=with_ivf)

    def _ingest_finish_host(self, ids, rows, host, chain_slot_list,
                            link_pool_list, *, shard_modes, k_eff, tid,
                            chain_weight, link_scale, now_abs,
                            shadow_fresh, ivf_fresh=False) -> Dict:
        """Host bookkeeping after the ONE fused readback: register
        surviving ids, free duplicate rows, mirror accepted edges into the
        host map, reclaim the untouched pool suffix, retry overflowed
        links (one extra dispatch for that rare batch only)."""
        n = len(ids)
        n_modes = len(shard_modes)
        tel = self.telemetry
        dup = host[0][:n, 0] > 0
        target = host[1][:n, 0]
        chain_src = host[2][:n, 0]
        ctr = host[3 + 3 * n_modes:]
        tel.bump("ingest.dedup_hits", int(dup.sum()))
        tel.bump("ingest.links_accepted", int(ctr[1][0, 0]))
        tel.bump("ingest.pool_slots_used", int(ctr[2][0, 0]))
        live_rows: List[int] = []
        merged: Dict[str, Optional[str]] = {}
        for i in range(n):
            r = rows[i]
            if dup[i]:
                self._free[r // self.part_rows].append(r)
                merged[ids[i]] = self.row_to_id.get(int(target[i]))
            else:
                self.id_to_row[ids[i]] = r
                self.row_to_id[r] = ids[i]
                live_rows.append(r)
        reclaim: List[int] = []
        chains: List[Tuple[str, str]] = []
        for i, slot in enumerate(chain_slot_list):
            src_id = (self.row_to_id.get(int(chain_src[i]))
                      if chain_src[i] >= 0 else None)
            key = (src_id, ids[i]) if src_id and not dup[i] else None
            if key is not None and key not in self.edge_slots:
                self.edge_slots[key] = slot
                self.edges[key] = float(chain_weight)
                chains.append(key)
            else:
                reclaim.append(slot)
        links: List[Tuple[str, str, float]] = []
        overflowed: List[Tuple[str, str, float]] = []
        pool_real = len(link_pool_list)
        consumed = 0
        for mi in range(n_modes):
            sc = host[3 + 3 * mi]
            cd = host[3 + 3 * mi + 1]
            ps = host[3 + 3 * mi + 2]
            for bi in range(n):
                if dup[bi]:
                    continue
                nid = ids[bi]
                for j in range(k_eff):
                    p = int(ps[bi, j])
                    if p < 0:
                        continue            # rejected: no slot consumed
                    s = float(sc[bi, j])
                    cid = (self.row_to_id.get(int(cd[bi, j]))
                           if s > NEG_INF / 2 else None)
                    w = min(1.0, max(0.0, s * link_scale))
                    if p >= pool_real:
                        if cid is not None \
                                and (nid, cid) not in self.edge_slots:
                            overflowed.append((nid, cid, w))
                            links.append((nid, cid, w))
                        continue
                    consumed = max(consumed, p + 1)
                    key = (nid, cid)
                    if cid is not None and key not in self.edge_slots:
                        self.edge_slots[key] = link_pool_list[p]
                        self.edges[key] = w
                        links.append((nid, cid, w))
                    else:
                        reclaim.append(link_pool_list[p])
        # dup facts' accepted positions never exist (valid_q gates them),
        # but their pool PREFIX positions may still have been consumed by
        # earlier live facts — the suffix comes back whole either way
        self._free_edge_slots.extend(link_pool_list[consumed:])
        self._free_edge_slots.extend(reclaim)
        self._csr_dirty = True
        if not shadow_fresh:
            self._int8_dirty = True
        self._emb_gen += 1
        if ivf_fresh:
            # Online IVF (ISSUE 12): in-dispatch member appends — routed
            # immediately; cluster-capacity spills join the exact-scan
            # extras (readback position -1), like link-pool overflow.
            ivf_ctr = ctr[3:]
            pos_w = ivf_ctr[1]
            routed = self._ivf_routed
            spilled = []
            for i in range(n):
                if dup[i]:
                    continue
                r = rows[i]
                if int(pos_w[i, 0]) >= 0:
                    if routed is not None:
                        routed[r] = True
                elif not (routed is not None and routed[r]) \
                        and r not in self._ivf_fresh:
                    spilled.append(r)
            if spilled:
                tel.bump("ivf.member_overflows", len(spilled))
                self._ivf_fresh.extend(spilled)
                self._ivf_tabs_cache = None
            dev = self._ivf_dev
            if dev is not None:
                slots = int(np.prod(dev[1].shape))
                tel.gauge("ivf.member_pool_occupancy",
                          float(ivf_ctr[3][0, 0]) / max(slots, 1))
            tel.bump("ivf.appends", int(ivf_ctr[4][0, 0]))
            tel.bump("ivf.centroid_shift_ppm", int(ivf_ctr[5][0, 0]))
        elif self._ivf is not None and live_rows:
            routed = self._ivf_routed
            for r in live_rows:
                if not routed[r] and r not in self._ivf_fresh:
                    self._ivf_fresh.append(r)
            self._ivf_tabs_cache = None
        if self.tiering is not None and live_rows:
            self.tiering.on_rows_written(live_rows)
        if self._sem_host is not None:
            # dedup-merge touched rows: exactly those slots; accepted new
            # rows: the whole tenant (a fresh fact changes its top-k
            # invisibly to any row-level index)
            self._sem_host.invalidate_rows(
                int(target[i]) for i in range(n) if dup[i])
            if live_rows:
                self._sem_host.invalidate_tenant(tid)
        if overflowed:
            self.link_pool_overflows += 1
            tel.bump("ingest.link_pool_overflows")
            self._edges_insert_device(overflowed, tid, now_abs - self.epoch)
        return {
            "rows": rows,
            "created": [i for i, d in zip(ids, dup) if not d],
            "merged": merged, "links": links, "chains": chains,
            "counters": {"dedup_hits": int(dup.sum()),
                         "links_accepted": int(ctr[1][0, 0]),
                         "pool_slots_used": int(ctr[2][0, 0]),
                         "overflow": bool(ctr[0][0, 0])},
        }

    def _ingest_classic(self, ids, emb_np, tenant, saliences, *, dedup_gate,
                        chain, chain_weight, link_k, link_gate, link_scale,
                        shard_modes, now) -> Dict:
        """The host-driven pod write sequence with the SAME semantics as
        the fused program (the A/B baseline and ``ingest_fused=False``
        fallback): probe dispatch → host dedup resolve → arena add (+
        shadow scatter) → merge touch → one link-scan dispatch per shard
        mode → host gate → edge-insert dispatch. Each device step routes
        through ``_ingest_dispatch``, so the dispatch-count gap vs the
        fused path is measured, not asserted."""
        tid = self.tenant_id(tenant)
        n = len(ids)
        k_eff = max(1, min(int(link_k), self.capacity))
        norms = np.maximum(np.linalg.norm(emb_np, axis=1, keepdims=True),
                           1e-9)
        qn = (emb_np / norms).astype(np.float32)
        st = self.state
        # probe: masked top-1 over the pre-add arena (one dispatch; the
        # mask arithmetic itself is extra eager device work — part of why
        # the host-driven path loses)
        probe_kern = self._ingest_classic_cache.get(("probe", 1))
        if probe_kern is None:
            probe_kern = make_sharded_topk(self.mesh, self.axis, k=1)
            self._ingest_classic_cache.put(("probe", 1), probe_kern)
        mask = st.alive & (st.tenant_id == tid) & ~st.is_super
        p_s, p_r = self._ingest_dispatch(probe_kern, st.emb, mask,
                                         jnp.asarray(qn))
        p_s, p_r = fetch_packed(p_s, p_r)
        p_s, p_r = p_s[:, 0], p_r[:, 0]
        # drop id-less probe hits (the sentinel/stale rows the classic
        # decode path filters) and resolve duplicates on host
        p_ok = np.asarray([self.row_to_id.get(int(r)) is not None
                           for r in p_r])
        p_s = np.where(p_ok, p_s, NEG_INF)
        gram = qn @ qn.T
        dup = np.zeros((n,), bool)
        # a dup's target is either an existing arena ROW (probe hit) or an
        # earlier FACT of this batch (intra hit, chained through that
        # fact's own resolution — rows for live facts exist only after
        # the add below)
        t_row = np.full((n,), -1, np.int64)
        t_fact = np.full((n,), -1, np.int64)
        chain_src_id: List[Optional[str]] = [None] * n
        last_live: Optional[str] = None
        for i in range(n):
            best_s, tr_i, tf_i = float(p_s[i]), int(p_r[i]), -1
            if i > 0:
                j = int(np.argmax(gram[i, :i]))
                if float(gram[i, j]) > best_s:
                    best_s = float(gram[i, j])
                    if dup[j]:              # dup-of-a-dup: same survivor
                        tr_i, tf_i = int(t_row[j]), int(t_fact[j])
                    else:
                        tr_i, tf_i = -1, j
            if best_s > dedup_gate:
                dup[i] = True
                t_row[i], t_fact[i] = tr_i, tf_i
                continue
            if chain and last_live is not None:
                chain_src_id[i] = last_live
            if chain:
                last_live = ids[i]
        live_idx = [i for i in range(n) if not dup[i]]
        live_ids = [ids[i] for i in live_idx]
        rows_all = np.full((n,), -1, np.int64)
        if live_ids:
            got = self.add(live_ids, emb_np[live_idx], tenant,
                           saliences=[saliences[i] for i in live_idx])
            for i, r in zip(live_idx, got):
                rows_all[i] = r
        merged: Dict[str, Optional[str]] = {}
        t_rows, t_sals = [], []
        for i in range(n):
            if dup[i]:
                tgt_id = (ids[int(t_fact[i])] if t_fact[i] >= 0
                          else self.row_to_id.get(int(t_row[i])))
                merged[ids[i]] = tgt_id
                r = self.id_to_row.get(tgt_id) if tgt_id else None
                if r is not None:
                    t_rows.append(int(r))
                    t_sals.append(float(saliences[i]))
        now_rel = now - self.epoch
        if t_rows and self._sem_host is not None:
            # same taxonomy as the fused path: merge targets row-level
            # (add() above already flushed the tenant for the live rows)
            self._sem_host.invalidate_rows(t_rows)
        if t_rows:
            padded = S.pad_rows(np.asarray(t_rows, np.int32), self.capacity)
            sal = np.zeros((len(padded),), np.float32)
            sal[:len(t_sals)] = t_sals
            with self._state_lock:
                cur = self._arena
                sole = sys.getrefcount(cur) <= self._SOLE_REFS
                out = self._guarded(
                    lambda fn: self._ingest_dispatch(
                        fn, cur, jnp.asarray(padded), jnp.asarray(sal),
                        jnp.float32(now_rel)),
                    S.arena_merge_touch, S.arena_merge_touch_copy, sole,
                    (cur,), "pod_arena")
                del cur
                self.state = out
        links: List[Tuple[str, str, float]] = []
        chains: List[Tuple[str, str]] = []
        if live_ids:
            # link scans: one distributed top-k per shard mode over the
            # post-add arena, new rows excluded as candidates
            st = self.state
            excl = jnp.zeros((self.capacity + 1,), bool).at[
                jnp.asarray(rows_all[live_idx].astype(np.int32))].set(True)
            base = (st.alive & (st.tenant_id == tid) & ~st.is_super
                    & ~excl)
            link_kern = self._ingest_classic_cache.get(("link", k_eff))
            if link_kern is None:
                link_kern = make_sharded_topk(self.mesh, self.axis,
                                              k=k_eff)
                self._ingest_classic_cache.put(("link", k_eff), link_kern)
            q_live = jnp.asarray(qn[live_idx])
            seen: set = set()
            for sm in shard_modes:
                # the pod surface writes one shard group (add() stamps
                # shard_id 0), so every mode shares the base mask
                l_s, l_r = self._ingest_dispatch(link_kern, st.emb, base,
                                                 q_live)
                l_s, l_r = fetch_packed(l_s, l_r)
                for li, bi in enumerate(live_idx):
                    nid = ids[bi]
                    for s, r in zip(l_s[li], l_r[li]):
                        cid = (self.row_to_id.get(int(r))
                               if s > NEG_INF / 2 else None)
                        if cid is None or float(s) <= link_gate:
                            continue
                        if (nid, cid) in seen:
                            continue
                        seen.add((nid, cid))
                        links.append((nid, cid,
                                      min(1.0, max(0.0,
                                                   float(s) * link_scale))))
            if chain:
                chains = [(chain_src_id[i], ids[i]) for i in live_idx
                          if chain_src_id[i] is not None]
            triples = ([(s, t, chain_weight) for s, t in chains]
                       + links)
            if triples:
                self._edges_insert_device(triples, tid, now_rel)
        return {
            "rows": [int(r) for r in rows_all],
            "created": live_ids, "merged": merged, "links": links,
            "chains": chains,
            "counters": {"dedup_hits": int(dup.sum()),
                         "links_accepted": len(links),
                         "pool_slots_used": 0, "overflow": False},
        }

    def _ingest_geometry(self, n: int, link_k: int = 3) -> Geometry:
        return Geometry(
            kind="ingest", mode="ingest", batch=max(1, int(n)),
            rows=self.capacity + 1, dim=self.dim,
            k=max(1, int(link_k)),
            dtype_bytes=int(np.dtype(self.dtype).itemsize),
            mesh_parts=self.n_parts, edge_cap=self.edge_capacity,
            link_k=max(1, int(link_k)),
            ivf=1 if (self.ivf_online and self._ivf_dev is not None)
            else 0,
            pq=1 if self._pq_pack is not None else 0,
            replica_groups=self.replica_groups)

    def plan_ingest(self, n: int, link_k: int = 3):
        """Pod twin of ``MemoryIndex.plan_ingest`` (ISSUE 11): admission
        decision for an ``n``-fact distributed ingest mega-batch; raises
        the typed :class:`PlanInfeasible` when no split fits."""
        return self.planner.check_feasible(
            self._ingest_geometry(n, link_k), chunkable=False)

    def _maybe_record_ingest_hbm(self, kern, dev_args, with_shadow: bool,
                                 b: int, with_ivf: bool = False,
                                 with_pq: bool = False) -> None:
        """Opt-in peak-HBM gauge for one pod ingest-kernel geometry
        (AOT lower + ``memory_analysis()`` of the non-donating twin; one
        extra compile, zero extra dispatches) — feeds the
        ``scripts/check_hbm_budget.py`` write-path gate."""
        if not self.telemetry_hbm or not self.telemetry.enabled:
            return    # never consume the once-key while warmup mutes the registry
        key = ("ingest", b, with_shadow, with_ivf, with_pq)
        if key in self._hbm_recorded:
            return
        self._hbm_recorded.add(key)
        try:
            with self._state_lock:
                sh = self._int8_shadow if with_shadow else None
                ivf = self._ivf_dev if with_ivf else None
                pq = self._pq_pack if with_pq else None
                args = ((self._arena, self._edge_state)
                        + ((sh[0], sh[1]) if sh is not None else ())
                        + (ivf if ivf is not None else ())
                        + (pq if pq is not None else ())
                        + dev_args)
            peak = peak_bytes(
                kern.ingest_copy.lower(*args).compile().memory_analysis())
        except Exception:   # noqa: BLE001 — never fail the write path
            return
        if peak is not None:
            labels = {"path": "ingest", "batch": str(b),
                      "rows": str(self.capacity + 1),
                      "mesh": f"{self.n_parts}x{self.axis}"}
            if with_ivf:
                labels["ivf"] = "true"
            if with_pq:
                labels["pq"] = "true"
            if self.replica_groups > 1:
                labels["groups"] = str(self.replica_groups)
            self.telemetry.gauge("kernel.peak_hbm_bytes", peak,
                                 labels=labels)
            self.planner.observe_gauge(self._ingest_geometry(b), peak)

    def warmup_ingest(self, geometries=(256,), *, dedup_gate: float = 0.95,
                      link_k: int = 3) -> Dict[int, float]:
        """Pod twin of ``MemoryIndex.warmup_ingest`` (ISSUE 9 satellite):
        pre-compile the distributed fused ingest program for the given
        fact-batch geometries by driving :meth:`ingest` with a throwaway
        tenant and deleting the rows afterwards — the live corpus is
        unchanged, the jit cache entries live traffic hits are warm. Wall
        time lands in ``kernel.warmup_ms{path="ingest",batch}``."""
        out: Dict[int, float] = {}
        tel = self.telemetry
        rng = np.random.default_rng(0)
        buckets = sorted({len(S.pad_rows(np.zeros((g,), np.int32),
                                         self.capacity))
                          for g in geometries if g > 0})
        for g in buckets:
            if self.planner is not None and self.planner.active:
                # planner compile gate (ISSUE 11): skip geometries the
                # admission path would refuse; warm the planned sub-batch
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
                ids = [f"~warm:{g}:{i}" for i in range(g)]
                got = self.ingest(
                    ids, rng.standard_normal((g, self.dim)), "~warmup",
                    dedup_gate=float(dedup_gate), link_k=link_k)
                self.delete(got["created"])
            finally:
                tel.enabled = prev
            ms = (time.perf_counter() - t0) * 1e3
            tel.record("kernel.warmup_ms", ms,
                       labels={"path": "ingest", "batch": str(g)})
            out[g] = ms
        return out

    # ------------------------------------------------------- tiered memory
    def attach_tiering(self, hot_budget_rows: int, **kw):
        """Attach a :class:`tier.TierManager` with one host ColdStore per
        mesh partition (each chip's demoted rows bucket to its own store).
        Serving switches to the distributed tiered program while any row
        is cold; cold-hit turns finish with the shared bounded rescore
        dispatch (plain jnp under jit — GSPMD partitions it against the
        row-sharded arena)."""
        from lazzaro_tpu.tier import TierManager

        self.tiering = TierManager(self, hot_budget_rows, **kw)
        return self.tiering

    def _flat_csr_for(self):
        """Replicated FLAT CSR over the host edge map for the tiered
        cold-finish kernel (the per-shard split ``_csr_sharded`` builds is
        the wrong layout for the GSPMD-partitioned finish)."""
        import jax.numpy as jnp

        cache = self._csr_flat_cache
        n = self.capacity + 1
        if cache is not None and cache[0] == len(self.edges) \
                and cache[1] == n:
            return cache[2], cache[3]
        indptr, nbr = build_host_csr(list(self.edges.keys()),
                                     self.id_to_row, n)
        dev = (jnp.asarray(indptr), jnp.asarray(nbr))
        self._csr_flat_cache = (len(self.edges), n, dev[0], dev[1])
        return dev

    # ------------------------------------------------------------------- api
    def add(self, ids: Sequence[str], embeddings: np.ndarray, tenant: str,
            saliences: Optional[Sequence[float]] = None,
            supers: Optional[Sequence[bool]] = None) -> List[int]:
        n = len(ids)
        if n == 0:
            return []
        if saliences is None:
            saliences = [0.5] * n
        if supers is None:
            supers = [False] * n
        # Happy path (ISSUE 18 satellite): with live online-IVF tables, an
        # all-fresh add() rides the fused ingest program — same one-dispatch
        # write, and the in-kernel assignment routes the rows into member
        # slots instead of spilling them to the exact-scan extras
        # (``ivf.add_extras_spills`` stops counting here). The gates are
        # pinned so ingest() IS add(): dedup_gate above max cosine so no
        # fact ever merges (every id keeps its own row), link_gate above
        # max cosine so no edge inserts. Re-adds (overwrite in place) and
        # super-node adds keep the classic scatter below — ingest() owns
        # neither semantics.
        if (self.ingest_fused and self.ivf_online
                and self._ivf_dev is not None and not any(supers)
                and all(i not in self.id_to_row for i in ids)):
            self.ingest(ids, embeddings, tenant, saliences,
                        dedup_gate=1.5, link_k=1, link_gate=1.5,
                        link_accept_hint=0.0)
            return [self.id_to_row[i] for i in ids]
        rows = []
        fresh = self._alloc(tenant,
                            sum(1 for i in ids if i not in self.id_to_row))
        fi = 0
        for node_id in ids:
            if node_id in self.id_to_row:
                rows.append(self.id_to_row[node_id])
            else:
                r = fresh[fi]; fi += 1
                self.id_to_row[node_id] = r
                self.row_to_id[r] = node_id
                rows.append(r)

        emb = np.asarray(embeddings, np.float32).reshape(n, self.dim)
        tid = self.tenant_id(tenant)
        rows_np = np.asarray(rows, np.int32)
        padded = S.pad_rows(rows_np, self.capacity)
        b = len(padded)

        def pad(vals, fill=0.0, dt=np.float32):
            out = np.full((b,), fill, dt)
            out[:n] = vals
            return out

        emb_p = np.zeros((b, self.dim), np.float32)
        emb_p[:n] = emb
        emb_dev = jnp.asarray(emb_p)
        self._apply_arena(
            S.arena_add, S.arena_add_copy,
            jnp.asarray(padded), emb_dev,
            jnp.asarray(pad(np.asarray(saliences, np.float32))),
            jnp.full((b,), time.time() - self.epoch, jnp.float32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.asarray(pad(tid, -1, np.int32)),
            jnp.asarray(pad(np.asarray(supers, bool), False, bool)))
        for r, is_sup in zip(rows, supers):
            (self._super_rows.add if is_sup
             else self._super_rows.discard)(r)
        # int8 shadow: incremental scatter when a maintained shadow exists
        # (O(batch)); otherwise it rebuilds lazily at the next serve.
        shadow = self._int8_shadow
        if (self.int8_serving and shadow is not None and not self._int8_dirty
                and shadow[0].shape[0] == self.capacity + 1):
            stored = S.normalize(emb_dev).astype(self.dtype)
            q8, scale = _shadow_update(shadow[0], shadow[1],
                                       jnp.asarray(padded), stored)
            self._int8_shadow = (jax.device_put(q8, self._mat_sh),
                                 jax.device_put(scale, self._row_sh))
        else:
            self._int8_dirty = True
        # PQ codes: patched in place against the frozen codebook — the
        # pack stays COMPLETE through every write path (ISSUE 16).
        pack = self._pq_pack
        if pack is not None and pack[1].shape[0] == self.capacity + 1:
            stored = S.normalize(emb_dev).astype(self.dtype)
            codes = _pq_codes_update(pack[0], pack[1],
                                     jnp.asarray(padded), stored)
            self._pq_pack = (pack[0], jax.device_put(codes, self._mat_sh))
        # IVF freshness: unrouted rows serve exactly from the extras until
        # the next ivf_build folds them into clusters. Spills through this
        # non-fused write surface are counted (ISSUE 16 satellite) so the
        # exact-scan extras burden stays measurable.
        if self._ivf is not None:
            routed = self._ivf_routed
            spilled = 0
            for r in rows:
                if not routed[r] and r not in self._ivf_fresh:
                    self._ivf_fresh.append(r)
                    spilled += 1
            if spilled:
                self.telemetry.bump("ivf.add_extras_spills", spilled)
            self._ivf_tabs_cache = None
        self._emb_gen += 1
        if self.tiering is not None:       # a re-added cold row is hot again
            self.tiering.on_rows_written(rows)
        if self._sem_host is not None:     # new facts change tenant top-k
            self._sem_host.invalidate_tenant(tid)
        return rows

    def delete(self, ids: Sequence[str]) -> None:
        rows = [self.id_to_row.pop(i) for i in ids if i in self.id_to_row]
        if not rows:
            return
        gone = set(ids)
        dead_edges = [key for key in self.edges
                      if key[0] in gone or key[1] in gone]
        for key in dead_edges:
            del self.edges[key]
            slot = self.edge_slots.pop(key, None)
            if slot is not None:      # reclaim the device edge-arena slot
                self._free_edge_slots.append(slot)
        if dead_edges:
            self._csr_dirty = True
        for r in rows:
            self.row_to_id.pop(r, None)
            self._super_rows.discard(r)
            self._free[r // self.part_rows].append(r)
            if self._ivf is not None:
                # un-route freed slots so a re-used row joins the fresh
                # extras (exact) instead of inheriting a stale cluster
                self._ivf_routed[r] = False
                if r in self._ivf_fresh:
                    self._ivf_fresh.remove(r)
        if self._ivf is not None:
            self._ivf_tabs_cache = None
        if self.tiering is not None:       # freed cold rows leave the store
            self.tiering.on_rows_deleted(rows)
        if self._sem_host is not None:
            self._sem_host.invalidate_rows(rows)
        padded = S.pad_rows(np.asarray(rows, np.int32), self.capacity)
        self._apply_arena(S.arena_delete, S.arena_delete_copy,
                          jnp.asarray(padded))

    def add_edges(self, triples: Sequence[Tuple[str, str, float]],
                  tenant: Optional[str] = None) -> None:
        """Register association edges (host bookkeeping + CSR shadow; the
        device side is the per-shard CSR the fused serving program
        gathers). ``tenant`` is accepted for MemoryIndex API parity —
        edge visibility is governed by the endpoint rows' tenant column."""
        changed = False
        for src, tgt, w in triples:
            if src in self.id_to_row and tgt in self.id_to_row:
                self.edges[(src, tgt)] = float(w)
                changed = True
        if changed:
            self._csr_dirty = True

    def set_super(self, ids: Sequence[str], flag: bool = True) -> None:
        """Mark rows as super nodes (the gate tier of the fused program)."""
        rows = [self.id_to_row[i] for i in ids if i in self.id_to_row]
        if not rows:
            return
        for r in rows:
            (self._super_rows.add if flag else self._super_rows.discard)(r)
        padded = S.pad_rows(np.asarray(rows, np.int32), self.capacity)
        b = len(padded)
        flags = np.zeros((b,), bool)
        flags[:len(rows)] = flag
        self._apply_arena(S.arena_set_parentage, S.arena_set_parentage_copy,
                          jnp.asarray(padded), jnp.asarray(flags))
        if self._ivf is not None:
            self._ivf_tabs_cache = None       # extras carry every super row

    def search(self, query: np.ndarray, tenant: str
               ) -> Tuple[List[str], List[float]]:
        """Distributed masked top-k: local per-chip → all_gather → global.
        Single-query view of ``search_batch``."""
        return self.search_batch(np.asarray(query, np.float32)[None, :],
                                 tenant)[0]

    def search_batch(self, queries: np.ndarray, tenant: str
                     ) -> List[Tuple[List[str], List[float]]]:
        """Multi-query distributed top-k: Q queries share one local-score
        matmul per chip and one all_gather — fleet serving over the pod.
        Q is bucketed to a power of two: each distinct query-batch shape
        would otherwise retrace the pod-wide shard_map kernel (multi-second
        compiles are most expensive exactly here)."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        nq = queries.shape[0]
        tid = self._tenants.get(tenant)
        if tid is None or nq == 0:
            return empty_results(nq)
        norms = np.maximum(np.linalg.norm(queries, axis=1, keepdims=True), 1e-9)
        q = pad_to_pow2(queries / norms)
        st = self.state
        mask = st.alive & (st.tenant_id == tid)
        scores, rows = self._dispatch(self._search, st.emb, mask,
                                      jnp.asarray(q))
        return decode_topk(np.asarray(scores)[:nq], np.asarray(rows)[:nq],
                           self.row_to_id, NEG_INF)

    # --------------------------------------------------- fused pod serving
    def _csr_sharded(self):
        """Per-shard CSR slices of the host edge map (each chip's own
        rows' neighbor lists, global neighbor ids), re-uploaded only after
        an edge-topology change. Span and counters are the one-chip
        index's (``MemoryIndex._csr_for``): ``lz.index.csr`` only when it
        builds, ``index.csr_lookups`` on every call."""
        tel = self.telemetry
        tel.bump("index.csr_lookups")
        if self._csr_cache is not None and not self._csr_dirty:
            return self._csr_cache
        self._csr_dirty = False
        with tel.span("index.csr"):
            keys = list(self.edges.keys())
            indptr, nbr = build_host_csr(keys, self.id_to_row,
                                         self.capacity + 1)
            ish, nsh = split_csr(indptr, nbr, self.n_parts)
            self._csr_cache = (jax.device_put(ish, self._stacked),
                               jax.device_put(nsh, self._stacked))
        tel.bump("index.csr_builds")
        tel.bump("index.csr_edges", len(keys))
        return self._csr_cache

    def _int8_shadow_for(self):
        """(Re)build the row-sharded int8 shadow from the current master;
        after the first build, ``add()`` maintains it incrementally."""
        with self._state_lock:
            shadow = self._int8_shadow
            if (not self._int8_dirty and shadow is not None
                    and shadow[0].shape[0] == self.capacity + 1):
                return shadow
            from lazzaro_tpu.ops.quant import quantize_rows

            q8, scale = quantize_rows(self._arena.emb)
            shadow = (jax.device_put(q8, self._mat_sh),
                      jax.device_put(scale, self._row_sh))
            self._int8_shadow = shadow
            self._int8_dirty = False
            return shadow

    def ivf_build(self, n_clusters: Optional[int] = None, nprobe: int = 8,
                  iters: int = 8) -> bool:
        """Offline coarse build for the pod path: k-means over the (host-
        gathered) master, then the member/extras tables are split into
        per-shard LOCAL-row tables (``ops.ivf.shard_serve_tables``) so the
        distributed fused kernel's gathers never leave a chip. Returns
        False when the arena is too small to benefit."""
        from lazzaro_tpu.ops.ivf import build_ivf

        st = self.state
        mask = np.asarray(st.alive)
        if int(mask.sum()) < 2 * max(4, nprobe):
            return False
        ivf = build_ivf(st.emb, mask, n_clusters=n_clusters, iters=iters,
                        member_cap_factor=self.ivf_member_cap_factor)
        members = np.asarray(ivf.members)
        residual = np.asarray(ivf.residual)
        routed = np.zeros((self.capacity + 1,), bool)
        m = members.ravel()
        routed[m[(m >= 0) & (m <= self.capacity)]] = True
        r = residual[(residual >= 0) & (residual <= self.capacity)]
        routed[r] = True
        with self._state_lock:
            self._ivf = (jax.device_put(ivf.centroids, self._rep), members,
                         residual, min(int(nprobe), ivf.n_clusters))
            self._ivf_routed = routed
            self._ivf_fresh = []
            self._ivf_tabs_cache = None
            self._publish_online_tables(members)
            self._publish_pq(st, mask)
        if self._sem_host is not None:
            # a (re)build flips the serving mode / coarse routing for
            # every tenant — cached windows may no longer be reproducible
            self._sem_host.invalidate_tenant(None)
        return True

    def _publish_pq(self, st: S.ArenaState, mask_np: np.ndarray) -> None:
        """Train + publish the COMPLETE PQ pack for the pod path (ISSUE
        16): codebook replicated, the full-slab encode row-sharded with
        the master. After this one build the pack is maintained
        incrementally (fused ingest's ``_pq_scatter``, add()'s host
        patch) — there is no dirty flag to clear. Caller holds
        ``_state_lock``."""
        if not self.pq_serving:
            self._pq_pack = None
            return
        from lazzaro_tpu.ops.pq import encode_pq, train_pq

        book = train_pq(st.emb, mask_np)
        codes = encode_pq(book.centroids, st.emb)
        self._pq_pack = (
            jax.device_put(book.centroids, self._rep),
            jax.device_put(codes, self._mat_sh))
        self.telemetry.bump("pq.publishes", labels={"surface": "pod"})

    def _pq_tables(self, k_bucket: int):
        """(book_cent, codes_sh, centroids, members_sh, extras_sh, nprobe)
        device tables for the fused ``mode="pq"`` pod program, or None to
        fall through to the IVF/dense routing (no pack, no coarse build,
        or a stale-capacity slab after growth)."""
        if not self.pq_serving:
            return None
        with self._state_lock:
            pack = self._pq_pack
        if pack is None or pack[1].shape[0] != self.capacity + 1:
            return None
        ivf_tabs = self._ivf_tables(k_bucket)
        if ivf_tabs is None:
            return None
        cent, mem_sh, ext_sh, nprobe = ivf_tabs
        return pack[0], pack[1], cent, mem_sh, ext_sh, nprobe

    def _publish_online_tables(self, members: np.ndarray) -> None:
        """Seed the LIVE pod coarse tables from a build (ISSUE 12): the
        per-shard LOCAL-row member split becomes the array the
        distributed ingest appends through AND the serving kernel
        gathers from; ``counts [n, C]`` is each (shard, cluster) append
        cursor, replicated so the ingest kernel's verdicts stay
        replicated arithmetic. Caller holds ``_state_lock``."""
        if not self.ivf_online or self._ivf is None:
            self._ivf_dev = None
            return
        from lazzaro_tpu.ops.ivf import shard_serve_tables

        cent = self._ivf[0]
        mem_sh, _ = shard_serve_tables(members,
                                       np.zeros((0,), np.int64),
                                       self.n_parts, self.part_rows)
        counts = (mem_sh >= 0).sum(axis=-1).astype(np.int32)
        self._ivf_dev = (
            jax.device_put(jnp.asarray(cent, jnp.float32), self._rep),
            jax.device_put(jnp.asarray(mem_sh), self._stacked),
            jax.device_put(jnp.asarray(counts), self._rep))

    def _ivf_tables(self, k_bucket: int):
        """(centroids, members_sh, extras_sh, nprobe) device tables for the
        fused IVF program, or None to serve dense (no build, or too few
        candidates per shard to fill k). With online maintenance the
        centroid/member tables are the LIVE device arrays the distributed
        ingest maintains (never cached — their identity IS the snapshot);
        only the extras split (sealed residual + overflow/add spills +
        supers) is host-assembled and cached."""
        if self._ivf is None:
            return None
        live = self._ivf_dev if self.ivf_online else None
        cache = self._ivf_tabs_cache
        if cache is not None and cache[0] >= k_bucket:
            ext_sh_dev, nprobe, n_static = cache[1]
            if live is not None:
                n_cand = nprobe * live[1].shape[2] + n_static
                if n_cand < k_bucket + self.coarse_slack:
                    return None
                return live[0], live[1], ext_sh_dev, nprobe
            return cache[2]
        from lazzaro_tpu.ops.ivf import pack_extras, shard_serve_tables

        cent, members, residual, nprobe = self._ivf
        extras = pack_extras(residual, self._ivf_fresh,
                             sorted(self._super_rows))
        n_cand = nprobe * members.shape[1] + extras.shape[0]
        if n_cand < k_bucket + self.coarse_slack:
            return None
        mem_sh, ext_sh = shard_serve_tables(members, extras, self.n_parts,
                                            self.part_rows)
        ext_sh_dev = jax.device_put(ext_sh, self._stacked)
        if live is not None:
            tabs = (live[0], live[1], ext_sh_dev, nprobe)
        else:
            tabs = (cent, jax.device_put(mem_sh, self._stacked),
                    ext_sh_dev, nprobe)
        self._ivf_tabs_cache = (k_bucket,
                                (ext_sh_dev, nprobe, extras.shape[0]),
                                tabs)
        return tabs

    def _fused_kernels(self, mode: str, k_bucket: int, nprobe: int,
                       scan_chunk: int = 0,
                       sem: bool = False) -> S.FusedShardedKernels:
        # k_bucket/nprobe are the fixed per-mode ceilings, so the cache
        # holds one entry per mode. A planner scan_chunk override keys
        # separately: same ONE dispatch, smaller in-kernel score tile
        # (ISSUE 17 satellite — the pod path chunks the scan instead of
        # splitting batches).
        key = (mode, k_bucket, nprobe)
        if scan_chunk:
            key = key + ("chunk", scan_chunk)
        if sem:
            key = key + ("sem",)
        kern = self._fused_cache.get(key)
        if kern is None:
            kern = S.make_fused_sharded(
                self.mesh, self.axis, k=k_bucket,
                cap_take=min(self.cap_take, k_bucket), max_nbr=self.max_nbr,
                mode=mode, slack=self.coarse_slack, nprobe=nprobe,
                scan_chunk=scan_chunk, sem=sem)
            self._fused_cache.put(key, kern)
            self.telemetry.gauge("kernel.cache_entries",
                                 len(self._fused_cache),
                                 labels={"surface": "pod_fused"})
        return kern

    def _serve_mode_hint(self, reqs) -> Tuple[str, int]:
        """Cheap (mode, k-ceiling) prediction of the pod dispatch's
        routing — the planner's geometry key (mirror of
        ``MemoryIndex._serve_mode_hint``)."""
        if self.serve_fused:
            k_bucket = int(min(max(self.serve_k_max, self.cap_take, 1),
                               self.capacity))
        else:
            k_req = max((min(int(r.k), self.capacity) for r in reqs),
                        default=1)
            k_bucket = min(max(next_pow2(max(self.cap_take, k_req, 1)), 1),
                           self.capacity)
        tm = self.tiering
        if tm is not None and tm.cold_count > 0:
            return "sharded_tiered", k_bucket
        if self._ivf is not None and self.serve_fused:
            if self.pq_serving and self._pq_pack is not None:
                return "sharded_pq", k_bucket
            return "sharded_ivf", k_bucket
        if self.int8_serving:
            return "sharded_quant", k_bucket
        return "sharded_exact", k_bucket

    def _serve_geometry(self, nq: int, mode: str, k_bucket: int) -> Geometry:
        pad_n = (bucket_size(nq, self.serve_pad_granularity)
                 if self.serve_fused else next_pow2(nq))
        return Geometry(
            kind="serve", mode=mode, batch=pad_n, rows=self.capacity + 1,
            dim=self.dim, k=k_bucket,
            dtype_bytes=int(np.dtype(self.dtype).itemsize),
            mesh_parts=self.n_parts, edge_cap=self.edge_capacity,
            nprobe=int(self._ivf[3] if self._ivf is not None else 0),
            replica_groups=self.replica_groups,
            sem_slots=(self._sem_host.slots if self._sem_host is not None
                       else 0),
            sem_width=(self._sem_host.width if self._sem_host is not None
                       else 0))

    def serve_requests(self, reqs) -> List:
        """Memory-safe entry point of the pod serving path (ISSUE 11):
        the distributed geometry is ADMITTED against the HBM planner
        before anything compiles — fused single distributed dispatch when
        the prediction fits, PLANNED sub-dispatches riding the linear pad
        buckets when it doesn't, typed :class:`PlanInfeasible` when no
        split fits; a runtime ``RESOURCE_EXHAUSTED`` gets ONE replan
        through the copy twins. Planner disabled (default) = zero-overhead
        passthrough. See :meth:`_serve_requests_once` for the dispatch."""
        nq = len(reqs)
        planner = self.planner
        if (nq == 0 or planner is None or not planner.active
                or not self.id_to_row):
            try:
                return self._serve_requests_once(reqs)
            except DeviceOom:
                raise
            except Exception as e:  # noqa: BLE001 — typed OOM, uniform
                if not is_resource_exhausted(e):
                    raise
                self.telemetry.bump("reliability.oom",
                                    labels={"mode": "serve_pod"})
                raise DeviceOom(
                    f"pod serving dispatch exhausted device memory and "
                    f"no planner budget is configured to replan it: {e}"
                ) from e
        check_not_poisoned(self._poisoned)
        mode, k_bucket = self._serve_mode_hint(reqs)
        geom = self._serve_geometry(nq, mode, k_bucket)
        # chunkable: an over-budget pod geometry first shrinks the
        # in-kernel scan tile (STILL one distributed dispatch) and only
        # then splits the batch (ISSUE 17 satellite — previously the pod
        # path could only split).
        decision = planner.check_feasible(geom, chunkable=True)
        return self._serve_planned(reqs, geom, decision, replanned=False)

    def _serve_planned(self, reqs, geom, decision,
                       replanned: bool) -> List:
        tel = self.telemetry
        n = len(reqs)
        splits = max(1, min(decision.splits, n))
        per = -(-n // splits)
        groups = [reqs[i:i + per] for i in range(0, n, per)]
        if len(groups) > 1:
            tel.bump("plan.planned_turns", labels={"path": "serve"})
            tel.bump("plan.split_dispatches", len(groups),
                     labels={"path": "serve"})
        if decision.scan_chunk:
            tel.bump("plan.scan_chunked_turns", labels={"path": "serve"})
        out: List = []
        done = 0
        try:
            for g in groups:
                out.extend(self._serve_requests_once(
                    g, force_copy=replanned,
                    scan_chunk=decision.scan_chunk))
                done += len(g)
        except Exception as e:      # noqa: BLE001 — OOM-only replan below
            if not is_resource_exhausted(e):
                raise
            if replanned:
                tel.bump("plan.infeasible", labels={"path": "serve"})
                raise PlanInfeasible(
                    f"replanned pod dispatch still exhausted device "
                    f"memory (mode={geom.mode}, batch={geom.batch}): "
                    f"{e}") from e
            self.planner.note_oom(geom)
            harder = self.planner.replan_after_oom(geom, decision,
                                                   chunkable=True)
            if harder is None:
                tel.bump("plan.infeasible", labels={"path": "serve"})
                raise PlanInfeasible(
                    f"pod dispatch exhausted device memory and no harder "
                    f"split fits (mode={geom.mode}, batch={geom.batch})"
                ) from e
            tel.bump("plan.oom_replans", labels={"path": "serve"})
            out.extend(self._serve_planned(reqs[done:], geom, harder,
                                           replanned=True))
        return out

    def _serve_requests_once(self, reqs, force_copy: bool = False,
                             scan_chunk: int = 0) -> List:
        """``serve.QueryScheduler`` executor for the pod-sharded path: one
        coalesced batch of :class:`serve.RetrievalRequest`s becomes ONE
        distributed dispatch + ONE packed readback running the FULL
        chat-turn program — super gate, ANN top-k, CSR neighbor gather,
        shard-local boost scatters — for the whole mixed-tenant batch
        (per-query tenant column; queries with an unknown tenant match
        nothing). The kernel is keyed on the ``serve_k_max`` ceiling and
        per-request k / cap / nprobe ride as device columns.
        ``serve_fused=False`` keeps the classic gate-less multitenant
        top-k (A/B + fallback), keyed on the batch max-k (pow2-bucketed)
        so ``k`` above the construction-time default retraces once per
        bucket instead of silently truncating."""
        from lazzaro_tpu.serve.scheduler import RetrievalResult

        results = [RetrievalResult() for _ in reqs]
        nq = len(reqs)
        if nq == 0 or not self.id_to_row:
            return results
        tel = self.telemetry
        with tel.span("index.pack"):
            dim = self.dim
            fused = self.serve_fused
            cap_s = self.cap_take
            if fused:
                # static per-mode k ceiling: the kernel key never depends on
                # the batch's k mix (ISSUE 7)
                k_bucket = int(min(max(self.serve_k_max, cap_s, 1),
                                   self.capacity))
                cap_s = min(self.cap_take, k_bucket)
            # the fused dispatch's ONE host operand (ISSUE 37): the loop
            # writes each query's bits straight into it
            car = RequestCarrier(nq, dim, self.serve_pad_granularity)
            q = car.q[:nq]
            valid = np.zeros((nq,), bool)
            tids = np.full((nq,), -1, np.int32)
            gate_on = np.zeros((nq,), bool)
            boost_on = np.zeros((nq,), bool)
            k_arr = np.zeros((nq,), np.int32)
            cap_arr = np.zeros((nq,), np.int32)
            for i, r in enumerate(reqs):
                v = np.asarray(r.query, np.float32).reshape(-1)
                tid = self._tenants.get(r.tenant)
                if v.size != dim or tid is None:
                    continue                    # tenant -1 matches no rows
                q[i] = v
                valid[i] = True
                tids[i] = tid
                gate_on[i] = bool(getattr(r, "gate_enabled", False))
                boost_on[i] = bool(getattr(r, "boost", False))
                if fused:
                    k_arr[i] = min(max(int(r.k), cap_s, 1), k_bucket)
                    rc = getattr(r, "cap_take", None)
                    cap_arr[i] = min(int(rc) if rc else cap_s, cap_s)
            if not valid.any():
                return results
            if not fused:
                k_req = max((min(int(r.k), self.capacity)
                             for i, r in enumerate(reqs) if valid[i]),
                            default=1)
                k_eff = max(self.cap_take, k_req, 1)
                k_bucket = min(max(next_pow2(k_eff), 1), self.capacity)
            # Fused batches bucket LINEARLY (granularity slots of worst-case
            # padding), the classic path to the next power of two (~50%
            # worst case).
            qp = None if fused else pad_to_pow2(q)
            pad_n = car.buf.shape[0] if fused else qp.shape[0]
        # Coalesce/pad inflation: padded kernel slots vs live requests.
        tel.bump("serve.live_requests", nq)
        tel.bump("serve.padded_slots", pad_n)
        tel.gauge("serve.batch_occupancy", nq / pad_n)

        if not fused:
            return self._serve_classic(reqs, results, valid, qp, tids,
                                       k_bucket)

        with tel.span("index.stage"):
            tm = self.tiering
            tiered = tm is not None and tm.cold_count > 0
            pq_tabs = None if tiered else self._pq_tables(k_bucket)
            ivf_tabs = (None if tiered or pq_tabs is not None
                        else self._ivf_tables(k_bucket))
            use_quant = self.int8_serving
            if tiered:
                # full-corpus int8 coarse scan + tier-aware rescore: the only
                # structure that still covers demoted rows (ISSUE 8)
                nprobe = 0
                mode = "tiered"
                ivf_tabs = None
                tables = (*self._int8_shadow_for(), tm.cold_mask_dev())
            elif pq_tabs is not None:
                # m-byte ADC coarse over the shared IVF candidate assembly +
                # exact rescore — the smallest-resident pod mode (ISSUE 16)
                book_cent, codes_sh, cent, mem_sh, ext_sh, nprobe = pq_tabs
                mode = "pq"
                ivf_tabs = pq_tabs       # nprobe sidecar routing below
                tables = (book_cent, codes_sh, cent, mem_sh, ext_sh)
            elif ivf_tabs is not None:
                cent, mem_sh, ext_sh, nprobe = ivf_tabs
                mode = "ivf_quant" if use_quant else "ivf"
                tables = ((*self._int8_shadow_for(), cent, mem_sh, ext_sh)
                          if use_quant else (cent, mem_sh, ext_sh))
            else:
                nprobe = 0
                mode = "quant" if use_quant else "exact"
                tables = self._int8_shadow_for() if use_quant else ()
            # Semantic query cache (ISSUE 20): the replicated ring rides the
            # SAME distributed dispatch. Tiered pods cache the k+slack
            # candidate window, so their guard adds the slack.
            semh = self._sem_host
            sem_state = None
            if semh is not None and mode in S.SEM_MODE_IDS:
                win = k_bucket + (self.coarse_slack if tiered else 0)
                if win <= semh.width:
                    sem_state = semh.tuple_for(mode)
            sem_tail = () if sem_state is None else (sem_state,)
            kern = self._fused_kernels(mode, k_bucket, nprobe,
                                       scan_chunk=scan_chunk,
                                       sem=sem_state is not None)
            csr_i, csr_n = self._csr_sharded()
            # per-query columns (replicated over the mesh with the carrier):
            # k, retrieval cap, and — for the IVF modes — probe width
            car.fill(valid=valid, tenant=tids, gate_on=gate_on,
                     boost_on=boost_on, k=k_arr, cap=cap_arr,
                     super_gate=self.super_gate, acc_boost=self.acc_boost,
                     nbr_boost=self.nbr_boost, now=time.time() - self.epoch)
            if ivf_tabs is not None:
                np_arr = np.zeros((nq,), np.int32)
                for i, r in enumerate(reqs):
                    rn = getattr(r, "nprobe", None)
                    np_arr[i] = (min(max(int(rn), 1), nprobe) if rn
                                 else nprobe)
                np_arr[~valid] = 0
                car.fill(nprobe=np_arr)
            # the ONE host operand: the call makes the transfer (ISSUE 37)
            tel.bump("serve.h2d_puts", labels={"mode": "pod"})
            args = (tables, csr_i, csr_n, car.buf)
            self._maybe_record_hbm(mode, kern, args, k_bucket, sem_tail)
            # Fault point "plan.oom" (ISSUE 11): an HBM allocation failure the
            # admission plan missed; serve_requests answers with one replan.
            faults.fire("plan.oom", mode=f"pod_{mode}", batch=pad_n)
        with tel.span("serve.pod_" + mode, timer="serve.dispatch_ms",
                      labels={"mode": "pod_" + mode}):
            with tel.span("dispatch.launch"):
                if boost_on.any():
                    with self._state_lock:
                        cur = self._arena
                        sole = (not force_copy
                                and sys.getrefcount(cur) <= self._SOLE_REFS)
                        out = self._guarded(
                            lambda fn: self._dispatch(
                                fn, cur, *args, *sem_tail),
                            kern.serve, kern.serve_copy, sole, (cur,),
                            "serve_pod")
                        if sem_state is not None:
                            new_state, sem_ring2, packed = out
                        else:
                            new_state, packed = out
                        del cur
                        self.state = new_state
                else:
                    out = self._dispatch(kern.read, self.state, *args,
                                         *sem_tail)
                    if sem_state is not None:
                        sem_ring2, packed = out
                    else:
                        packed = out
            with tel.span("dispatch.readback"):
                host = np.asarray(packed)          # the ONE readback
        if tiered:
            from lazzaro_tpu.tier.serve import tiered_decode_and_finish
            if sem_state is not None:
                k_unpack = (host.shape[1] - 8) // 2
                g_s, g_r, a_s, a_r, _, ctr = unpack_retrieval(host[:nq],
                                                              k_unpack)
                semh.note_readback(sem_ring2, ctr[:, 4], valid, tids,
                                   g_s, g_r, a_s, a_r)
            with tel.span("index.decode", timer="serve.decode_ms"):
                return tiered_decode_and_finish(
                    self, tm, reqs, results, valid, boost_on, q, tids,
                    host, k_bucket=k_bucket, cap_take=cap_s,
                    max_nbr=self.max_nbr, acc_boost=self.acc_boost,
                    nbr_boost=self.nbr_boost,
                    now_rel=time.time() - self.epoch, cap_arr=cap_arr,
                    tel=tel)
        with tel.span("index.decode", timer="serve.decode_ms"):
            gate_s, gate_r, ann_s, ann_r, fast, counters = unpack_retrieval(
                host[:nq], k_bucket)
            for i, r in enumerate(reqs):
                if not valid[i]:
                    continue
                res = results[i]
                ids, scores = decode_topk(
                    ann_s[i:i + 1], ann_r[i:i + 1], self.row_to_id,
                    NEG_INF, limit=min(int(r.k), self.capacity),
                    lengths=counters[i:i + 1, 0])[0]
                res.ids, res.scores = ids, scores
                if gate_s[i] > NEG_INF / 2:
                    res.gate_id = self.row_to_id.get(int(gate_r[i]))
                    res.gate_score = float(gate_s[i])
                res.fast = bool(fast[i])
                res.boosted = bool(boost_on[i] and not fast[i])
            if sem_state is not None:
                semh.note_readback(sem_ring2, counters[:, 4], valid, tids,
                                   gate_s, gate_r, ann_s, ann_r)
            record_device_counters(
                tel, counters, fast, gate_on, valid,
                np.asarray([min(int(r.k), self.capacity) for r in reqs]),
                sem_active=sem_state is not None)
        return results

    def _maybe_record_hbm(self, mode: str, kern, args, k_bucket,
                          sem_tail) -> None:
        """Opt-in peak-HBM gauge for one pod serving geometry (AOT lower +
        ``memory_analysis()`` of the read twin; one extra compile, zero
        extra dispatches)."""
        if not self.telemetry_hbm or not self.telemetry.enabled:
            return    # never consume the once-key while warmup mutes the registry
        key = (mode, k_bucket)
        if key in self._hbm_recorded:
            return
        self._hbm_recorded.add(key)
        try:
            peak = peak_bytes(kern.read.lower(
                self.state, *args, *sem_tail
            ).compile().memory_analysis())
        except Exception:   # noqa: BLE001 — never fail the serve
            return
        if peak is not None:
            labels = {"mode": f"pod_{mode}", "k": str(k_bucket),
                      "rows": str(self.capacity + 1),
                      "batch": str(int(args[3].shape[0])),
                      "mesh": f"{self.n_parts}x{self.axis}"}
            if mode == "pq":
                labels["pq"] = "true"
            if self.replica_groups > 1:
                labels["groups"] = str(self.replica_groups)
            sem_on = self._sem_host is not None and bool(sem_tail)
            if sem_on:
                # ring geometry for check_hbm_budget.py's semantic-cache
                # sweep (ISSUE 20): resident ring + [batch, slots] probe
                labels["sem_slots"] = str(self._sem_host.slots)
                labels["sem_width"] = str(self._sem_host.width)
            self.telemetry.gauge("kernel.peak_hbm_bytes", peak,
                                 labels=labels)
            self.planner.observe_gauge(
                Geometry(kind="serve", mode=f"pod_{mode}",
                         batch=int(args[3].shape[0]),
                         rows=self.capacity + 1, dim=self.dim,
                         k=int(k_bucket),
                         dtype_bytes=int(np.dtype(self.dtype).itemsize),
                         mesh_parts=self.n_parts,
                         edge_cap=self.edge_capacity,
                         replica_groups=self.replica_groups,
                         sem_slots=(self._sem_host.slots if sem_on else 0),
                         sem_width=(self._sem_host.width if sem_on
                                    else 0)),
                peak)

    def warmup_serving(self, geometries=(8, 64),
                       k: Optional[int] = None) -> Dict[tuple, float]:
        """Pod twin of ``MemoryIndex.warmup_serving`` (ISSUE 7 satellite):
        pre-compile the distributed fused serving program for the given
        query-batch geometries by driving ``serve_requests`` with a
        synthetic tenant that owns no rows — a numeric no-op on the arena
        that populates exactly the jit cache entries live traffic hits.
        Telemetry counters are suppressed while warming; wall time lands
        in ``kernel.warmup_ms{mode,batch}``."""
        from lazzaro_tpu.serve.scheduler import RetrievalRequest

        out: Dict[tuple, float] = {}
        if not self.id_to_row:
            return out
        tel = self.telemetry
        mode = ("quant" if self.int8_serving else "exact")
        if self._ivf is not None:
            mode = "ivf_quant" if self.int8_serving else "ivf"
        self._tenants.setdefault("~warmup", -2)   # matches no arena row
        kk = int(k if k is not None else self.serve_k_max)
        buckets = sorted({
            (bucket_size(g, self.serve_pad_granularity)
             if self.serve_fused else next_pow2(g))
            for g in geometries if g > 0})
        for g in buckets:
            zero_q = np.zeros((self.dim,), np.float32)
            t0 = time.perf_counter()
            prev = tel.enabled
            tel.enabled = False
            try:
                # routed through the planner-gated entry (ISSUE 11): a
                # planned-split geometry warms its sub-dispatch kernels,
                # an infeasible one is skipped typed
                self.serve_requests(
                    [RetrievalRequest(query=zero_q, tenant="~warmup", k=kk,
                                      gate_enabled=True, boost=(i == 0))
                     for i in range(g)])
                self.serve_requests(
                    [RetrievalRequest(query=zero_q, tenant="~warmup", k=kk,
                                      gate_enabled=True)
                     for i in range(g)])
            except PlanInfeasible:
                tel.enabled = prev
                tel.bump("plan.warmup_skipped", labels={"path": "serve"})
                continue
            finally:
                tel.enabled = prev
            ms = (time.perf_counter() - t0) * 1e3
            tel.record("kernel.warmup_ms", ms,
                       labels={"mode": f"pod_{mode}", "batch": str(g)})
            out[(f"pod_{mode}", g)] = ms
        return out

    def _serve_classic(self, reqs, results, valid, qp, tids, k_bucket):
        """The pre-ISSUE-5 pod path, kept for A/B and fallback: ONE
        distributed multitenant top-k per batch — correct ids and scores,
        but no gate verdict, no neighbor gather, no boosts (``fast`` and
        ``boosted`` stay False; the orchestrator's classic host path pays
        any boosts)."""
        from lazzaro_tpu.ops.topk import make_sharded_multitenant_topk

        kern = self._serve_search_cache.get(k_bucket)
        if kern is None:
            kern = make_sharded_multitenant_topk(self.mesh, self.axis,
                                                 k=k_bucket)
            self._serve_search_cache.put(k_bucket, kern)
        norms = np.maximum(np.linalg.norm(qp, axis=1, keepdims=True), 1e-9)
        tp = np.full((qp.shape[0],), -1, np.int32)
        tp[:len(tids)] = tids
        st = self.state
        scores, rows = self._dispatch(kern, st.emb, st.alive, st.tenant_id,
                                      jnp.asarray(qp / norms),
                                      jnp.asarray(tp))
        nq = len(reqs)
        decoded = decode_topk(np.asarray(scores)[:nq], np.asarray(rows)[:nq],
                              self.row_to_id, NEG_INF)
        for i, (ids, sc) in enumerate(decoded):
            if not valid[i]:
                continue
            k = min(int(reqs[i].k), self.capacity)
            results[i].ids = ids[:k]
            results[i].scores = sc[:k]
        return results

    def decay(self, tenant: str, rate: float, floor: float = 0.2) -> None:
        tid = self._tenants.get(tenant)
        if tid is None:
            return
        self._apply_arena(S.arena_decay, S.arena_decay_copy,
                          jnp.int32(tid), jnp.float32(rate),
                          jnp.float32(floor))

    def partition_of(self, node_id: str) -> Optional[int]:
        row = self.id_to_row.get(node_id)
        return None if row is None else row // self.part_rows
