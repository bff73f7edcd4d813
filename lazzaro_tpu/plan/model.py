"""Analytic peak-HBM cost model for the fused serving/ingest geometries.

"Memory Safe Computations with XLA" (PAPERS.md) argues the memory bound
should be *guaranteed* before compilation, not discovered as a runtime
``RESOURCE_EXHAUSTED``. This module is the prediction half of that
guarantee: given a geometry — (kind × mode × batch × rows × k × mesh) —
it computes an analytic upper bound on the compiled program's peak HBM
from buffer accounting of what the fused kernels actually allocate:

- the RESIDENT live set every dispatch carries (arena columns + int8
  shadow + IVF tables + edge arena + CSR),
- the TRANSIENT high-water mark of the scan itself: for the exact family
  one block's ``[chunk, block]`` tile, the tier columns and the running
  top-k lists of the select-while-scanning core (``ops/pallas_topk.py``);
  for the other dense families the ``[min(batch, scan_chunk), rows]`` f32
  score tile the chunked-map structure bounds (``ops/chunking.py``); plus
  query/readback/top-k workspace terms linear in the batch.

The model is deliberately conservative and then CALIBRATED against the
measured truth: every AOT ``memory_analysis()`` gauge the PR 6/PR 9
machinery records (``kernel.peak_hbm_bytes{...}``) is fed back through
:meth:`CostModel.observe`, which inflates the per-(kind, mode) safety
multiplier until the prediction over-bounds every recorded gauge. The
multipliers and the residual log persist as JSON beside the kernel-cache
artifacts (``bench_artifacts/plan_calibration.json`` by default), so CI
(``scripts/check_hbm_budget.py``) re-checks model soundness — a gauge
exceeding its prediction fails the gate — without recompiling anything.

Pure stdlib on purpose: the CI gate loads this file directly
(``importlib`` by path) so the budget sweep never pays a jax import.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import asdict, dataclass
from typing import Dict, Optional

# Mirrors ops/chunking.QUERY_CHUNK and core/state.IVF_SERVE_CHUNK; kept as
# literals so this module stays importable without jax. A drift here only
# loosens/tightens the bound — soundness is restored by calibration.
QUERY_CHUNK = 512
IVF_SERVE_CHUNK = 32

# Per-row bytes of the non-embedding arena columns (salience, timestamp,
# last_accessed f32; access_count, type_id, shard_id, tenant_id i32;
# alive, is_super bool — padded to 4 for alignment conservatism).
ARENA_META_BYTES = 7 * 4 + 2 * 4
# Per-slot bytes of the edge arena (src, tgt i32; weight f32; co i32;
# last_updated f32; alive bool→4; tenant_id i32).
EDGE_SLOT_BYTES = 7 * 4

# Default safety multipliers per (kind, mode-family). XLA's compiled peak
# includes fusion temporaries and layout padding the analytic terms can't
# see; these start conservative and only ever grow under calibration.
_DEFAULT_MULTIPLIER = 1.25

# Fixed per-dispatch workspace floor. XLA's AOT peak carries a
# size-independent temp-buffer floor (alignment slop, collective
# scratch, the sort workspace's minimum granule) that dominates TINY
# geometries — a multiplicative model can only cover it by inflating
# the family multiplier far past what real sizes need, so it is a
# constant term instead (ISSUE 18: surfaced by the replica bench's
# 706-row ingest gauges).
DISPATCH_WORKSPACE_BYTES = 2 << 20

# Mirrors ops/pallas_topk (SELECT_BLOCK, _BLOCK_BYTES, select_block_rows),
# as literals for the same reason as the chunks above.
SELECT_BLOCK = 4096
_SELECT_BLOCK_BYTES = 8 * 1024 * 1024
# facts the ingest's link scan holds on chip at once (_MAX_QUERIES there)
LINK_SCAN_FACTS = 128


def select_block_rows(n: int, d: int, itemsize: int) -> int:
    """Rows per block of the exact serving core for a pool of ``n`` rows
    (``n`` itself: one whole-pool block)."""
    blk = SELECT_BLOCK
    while blk > 512 and blk * d * itemsize > _SELECT_BLOCK_BYTES:
        blk //= 2
    while blk >= 512 and n % blk != 0:
        blk //= 2
    return blk if 512 <= blk < n else n


@dataclass(frozen=True)
class Geometry:
    """One fused-dispatch geometry the planner reasons about.

    ``rows`` is the GLOBAL padded arena length (capacity + sentinel);
    ``mesh_parts`` divides it into the per-chip slice the shard-local
    cores scan. ``batch`` is the PADDED query (or fact) batch.
    ``scan_chunk = 0`` means the kernel's default chunk structure
    (``QUERY_CHUNK``, or ``IVF_SERVE_CHUNK`` for the IVF gather).
    ``pool_rows`` (ISSUE 17) is the PHYSICAL embedding pool length of a
    paged arena — 0 means dense (pool == rows). Only the embedding slab
    and the scan tiles that stream it scale with the pool; every other
    column stays logical-length."""

    kind: str = "serve"          # "serve" | "ingest" | "lifecycle"
    mode: str = "exact"          # exact | quant | ivf | pq | tiered
    batch: int = 8
    rows: int = 1024
    dim: int = 768
    k: int = 128
    dtype_bytes: int = 4         # master-arena embedding dtype
    mesh_parts: int = 1
    edge_cap: int = 0
    nprobe: int = 0
    scan_chunk: int = 0
    pool_rows: int = 0           # paged arena: physical emb pool length
    link_k: int = 3              # ingest link-scan width per shard mode
    # Online-IVF maintenance rides the ingest dispatch (ISSUE 12): 1 adds
    # the centroid block + member/counts tables to the resident set and
    # the [batch, C] assignment tile + [C, d] update workspace to the
    # transient (serve-side IVF geometry is carried by mode="ivf").
    ivf: int = 0
    # Member-table capacity factor (slots ≈ factor · rows total).
    ivf_cap_factor: int = 4
    # PQ code maintenance rides the ingest dispatch (ISSUE 16): 1 adds
    # the u8 code slab + codebook to the resident set and the batch
    # encode tile to the transient (serve-side PQ geometry is carried by
    # mode="pq").
    pq: int = 0
    # Exact-rescore over-fetch depth (``coarse_fetch_slack``): the PQ
    # serve kernel gathers and f32-rescores ``k + slack`` shortlist rows
    # per query, so the transient term is LINEAR in it — a per-family
    # multiplier cannot absorb a knob the operator can turn.
    slack: int = 8
    # Replica-group serving (ISSUE 18): the mesh is partitioned into G
    # groups that each hold a FULL copy of the arena, so ``mesh_parts``
    # here is already the per-GROUP shard count (chips // groups) and the
    # per-chip byte terms need no change — but admission must label the
    # geometry so a planner sweep can see that G groups multiply the
    # fleet-wide resident footprint while leaving the per-chip slice
    # rows / (chips/G).
    replica_groups: int = 1
    # Semantic query cache (ISSUE 20): ring slots per serving index.
    # 0 means the cache is off. Each slot is resident device state —
    # normalized query embedding + packed top-k result columns + the
    # condition columns the probe masks on — and the probe adds a
    # [batch, slots] similarity tile to the transient set. ``sem_width``
    # is the stored result width (k, or k + slack for tiered modes).
    sem_slots: int = 0
    sem_width: int = 0

    def with_(self, **kw) -> "Geometry":
        d = asdict(self)
        d.update(kw)
        return Geometry(**d)


def _mode_family(mode: str) -> str:
    """Collapse pod/sharded prefixes onto the core scan family — the
    calibration multiplier is per family, the rows-per-chip term already
    carries the mesh geometry."""
    m = mode.replace("sharded_", "").replace("pod_", "")
    if m.startswith("pq"):
        return "pq"
    if m.startswith("ivf"):
        return "ivf"
    return (m if m in ("exact", "quant", "tiered", "ingest", "lifecycle")
            else "exact")


class CostModel:
    """Analytic buffer accounting + per-(kind, family) calibrated
    multipliers. ``predict`` returns an over-bounding byte estimate;
    ``observe`` folds a measured AOT gauge back in, growing the
    multiplier whenever the measurement beats the analytic bound."""

    def __init__(self, multipliers: Optional[Dict[str, float]] = None):
        self.multipliers: Dict[str, float] = dict(multipliers or {})
        # (geometry-ish key) -> {"predicted": .., "observed": ..} of every
        # observe() call — the residual log CI checks and bench persists.
        self.residuals: Dict[str, Dict[str, float]] = {}

    # ------------------------------------------------------------- predict
    def _mult(self, kind: str, mode: str) -> float:
        return self.multipliers.get(f"{kind}:{_mode_family(mode)}",
                                    _DEFAULT_MULTIPLIER)

    def resident_bytes(self, g: Geometry) -> int:
        """Per-chip resident live set: every dispatch carries the whole
        of it regardless of batch, so no split can shrink it — this is
        the feasibility floor."""
        rows_pc = -(-g.rows // max(1, g.mesh_parts))
        fam = _mode_family(g.mode)
        # Paged arena (ISSUE 17): the embedding slab is pool-shaped —
        # pages-in-use, not N — while the metadata columns stay logical.
        emb_rows_pc = (-(-g.pool_rows // max(1, g.mesh_parts))
                       if g.pool_rows else rows_pc)
        total = emb_rows_pc * g.dim * g.dtype_bytes \
            + rows_pc * ARENA_META_BYTES
        if g.pool_rows:
            # row_map (logical, i32) + inv_map/free-stack (pool, i32 each)
            total += rows_pc * 4 + emb_rows_pc * 8
        if fam in ("quant", "tiered", "ivf") or g.kind == "ingest":
            # int8 shadow codes + f32 scales (maintained in-kernel by the
            # fused ingest; streamed by every coarse stage). The exact
            # serve mode carries none, but ingest always may.
            if fam != "exact" or g.kind == "ingest":
                total += rows_pc * (g.dim + 4)
        if fam == "tiered":
            total += rows_pc            # residency mask (bool→byte)
        if fam == "pq":
            # u8 code slab (m ≈ dim/8 bytes per row — the smallest
            # resident coarse representation any mode carries), the
            # replicated codebook (256·dim f32 regardless of m), the
            # coarse routing tables, and the residency byte pq_tiered
            # adds (carried unconditionally: one byte/row of slack)
            m_sub = max(1, g.dim // 8)
            n_cent = max(1, int(math.sqrt(g.rows)))
            total += rows_pc * m_sub
            total += 256 * g.dim * 4
            total += n_cent * g.dim * 4 + rows_pc * 8
            total += rows_pc
        if fam == "ivf":
            # centroids (replicated) + member/extras tables ~ one int32
            # routing entry per row plus the centroid block
            n_cent = max(1, int(math.sqrt(g.rows)))
            total += n_cent * g.dim * 4 + rows_pc * 8
        if g.kind == "ingest" and g.pq:
            # PQ pack donated through the ingest dispatch (ISSUE 16):
            # the u8 code slab (row-sharded with the master) + the
            # replicated codebook.
            total += rows_pc * max(1, g.dim // 8) + 256 * g.dim * 4
        if g.kind == "ingest" and g.ivf:
            # Online-IVF state donated through the ingest dispatch
            # (ISSUE 12): centroid block (f32, replicated), member table
            # (cap_factor int32 slots per row, row-sharded with the
            # master) and the counts column.
            n_cent = max(1, int(math.sqrt(g.rows)))
            total += n_cent * (g.dim + 1) * 4
            total += rows_pc * max(1, g.ivf_cap_factor) * 4
        total += g.edge_cap * EDGE_SLOT_BYTES
        # CSR shadow (indptr + neighbor pool ≈ 2 entries/edge, i32)
        total += (rows_pc + 2) * 4 + 2 * g.edge_cap * 4
        if g.sem_slots and g.kind == "serve":
            # Semantic ring (ISSUE 20): replicated per chip — slots+1
            # rows (sentinel scratch row included) of normalized query
            # embedding, packed (score, row) result columns at the
            # stored width, and the five condition/verdict columns.
            w = g.sem_width or g.k
            total += (g.sem_slots + 1) * (g.dim * 4 + w * 8 + 25)
        return int(total)

    def transient_bytes(self, g: Geometry) -> int:
        """Scan high-water mark: the chunk-bounded score tile plus the
        batch-linear query/readback/top-k terms. THIS is what batch
        splitting and scan chunking shrink."""
        rows_pc = -(-g.rows // max(1, g.mesh_parts))
        # The dense/link scans stream the PHYSICAL embedding pool of a
        # paged arena (scores land in pool space, decoded via inv_map).
        scan_rows_pc = (-(-g.pool_rows // max(1, g.mesh_parts))
                        if g.pool_rows else rows_pc)
        fam = _mode_family(g.mode)
        if g.kind == "lifecycle":
            # The all-tenant maintenance sweep (ISSUE 19) never streams
            # the embedding slab — its high-water mark is the [tenants,
            # rows] masked-importance tile behind the per-tenant bottom-k
            # (``batch`` carries the verdict-tenant count, ``k`` the
            # archive depth), the edge decay/prune working set (decayed
            # weight copy + cumsum positions + victim buffer), and the
            # packed payload readback.
            tv = max(1, g.batch)
            tile = tv * (rows_pc + 1) * 4 * 2
            tile += 3 * g.edge_cap * 4
            tile += (2 * tv * g.k + g.edge_cap + 8) * 4
            return int(tile + DISPATCH_WORKSPACE_BYTES)
        default_chunk = (IVF_SERVE_CHUNK if fam in ("ivf", "pq")
                         else QUERY_CHUNK)
        chunk = min(g.batch, g.scan_chunk or default_chunk)
        chunk = max(1, chunk)
        if fam == "pq":
            # ADC member scan: the per-chunk flat LUT [chunk, m·256] f32,
            # the gathered candidate codes [chunk, cands, m] u8 + their
            # coarse scores, and the exact-rescore gather of the
            # k+slack shortlist from the master
            n_cent = max(1, int(math.sqrt(g.rows)))
            m = -(-g.rows // n_cent)
            m_sub = max(1, g.dim // 8)
            cands = max(1, g.nprobe or 4) * m + g.k
            tile = chunk * m_sub * 256 * 4
            tile += chunk * cands * (m_sub + 8)
            # shortlist gather + the sorted copy XLA keeps beside it —
            # k + slack rows deep (the coarse_fetch_slack knob), f32
            tile += chunk * (g.k + max(8, g.slack) + 16) \
                * (g.dim + 2) * 4 * 2
        elif fam == "ivf":
            # the gather footprint: [chunk, nprobe·M + extras, d] f32
            # candidate block; M ≈ rows/√rows member slots per cluster
            n_cent = max(1, int(math.sqrt(g.rows)))
            m = -(-g.rows // n_cent)
            cands = max(1, g.nprobe or 4) * m + g.k
            tile = chunk * cands * (g.dim + 2) * 4
        elif fam == "ingest":
            # the blocked link scan (ISSUE 45): no [facts, rows] tile —
            # a piece of the batch's facts on chip against ONE block's
            # scores with their masked copy and
            # compare workspace, the two per-row key columns the tiers
            # mask on, and the candidate triples. A pool no block tiles
            # is one whole-pool block.
            block = select_block_rows(scan_rows_pc, g.dim,
                                      g.dtype_bytes)
            tile = min(g.batch, LINK_SCAN_FACTS) * block * 4 * 3
            tile += (scan_rows_pc + 1) * 4 * 2
            tile += g.batch * max(1, g.link_k) * 3 * 4 * 2
            if g.ivf:
                # the [batch, C] assignment tile, the [C, d] centroid
                # update workspace (sums + proposal), and the batch-wide
                # intra-cluster rank matrix (ISSUE 12)
                n_cent = max(1, int(math.sqrt(g.rows)))
                tile += g.batch * n_cent * 4
                tile += 3 * n_cent * g.dim * 4
                tile += g.batch * g.batch * 4
            if g.pq:
                # the in-dispatch batch encode (ISSUE 16): [batch, m,
                # 256] sub-distance tile against the frozen codebook
                tile += g.batch * max(1, g.dim // 8) * 256 * 4
        elif fam == "exact":
            # the blocked select-while-scanning core (ISSUE 26): no
            # [chunk, rows] tile — ONE block's scores with their masked
            # copy and compare workspace, the two per-row tenant columns
            # the tiers mask on, and the running [chunk, k] lists (score
            # + row, carried and updated). A pool no block tiles is one
            # whole-pool block, and the first term is the old tile.
            block = select_block_rows(scan_rows_pc, g.dim,
                                      g.dtype_bytes)
            tile = chunk * block * 4 * 3
            tile += (scan_rows_pc + 1) * 4 * 2
            tile += chunk * (-(-g.k // 128) * 128) * 8 * 2
        else:
            # dense scan: [chunk, rows] f32 scores + the two mask tiles
            # and the top-k workspace XLA materializes beside them
            tile = chunk * (scan_rows_pc + 1) * 4 * 3
        q_bytes = g.batch * g.dim * 4 * 2              # query + normalized
        readback = g.batch * (3 + 2 * g.k + 5) * 4 * 2
        sidecars = g.batch * 4 * 6                     # k/cap/nprobe/flags
        sem_tile = 0
        if g.sem_slots and g.kind == "serve":
            # probe similarity tile + miss-first sort workspace
            sem_tile = g.batch * (g.sem_slots + 8) * 4
        return int(tile + q_bytes + readback + sidecars + sem_tile
                   + DISPATCH_WORKSPACE_BYTES)

    def predict(self, g: Geometry) -> int:
        """Calibrated upper bound on the compiled program's peak HBM."""
        raw = self.resident_bytes(g) + self.transient_bytes(g)
        return int(raw * self._mult(g.kind, g.mode))

    # ----------------------------------------------------------- calibrate
    @staticmethod
    def _res_key(g: Geometry) -> str:
        return (f"{g.kind}:{g.mode}:b{g.batch}:r{g.rows}:k{g.k}"
                f":m{g.mesh_parts}" + (":ivf" if g.ivf else "")
                + (":pq" if g.pq else "")
                + (f":p{g.pool_rows}" if g.pool_rows else "")
                + (f":g{g.replica_groups}" if g.replica_groups > 1 else ""))

    def observe(self, g: Geometry, measured_bytes: float) -> bool:
        """Fold one measured AOT ``memory_analysis()`` peak back in.
        Returns True when the prediction already over-bounded it; False
        means the multiplier was GROWN so it does now (with 5% margin) —
        predictions must over-bound every recorded gauge."""
        measured = float(measured_bytes)
        predicted = self.predict(g)
        self.residuals[self._res_key(g)] = {
            "predicted": float(predicted), "observed": measured,
            "ratio": round(measured / max(predicted, 1.0), 4)}
        if measured <= predicted:
            return True
        raw = self.resident_bytes(g) + self.transient_bytes(g)
        key = f"{g.kind}:{_mode_family(g.mode)}"
        self.multipliers[key] = max(
            self.multipliers.get(key, _DEFAULT_MULTIPLIER),
            measured / max(raw, 1.0) * 1.05)
        return False

    def inflate(self, g: Geometry, factor: float = 2.0) -> None:
        """Post-OOM learning: the geometry OOM'd although the prediction
        said it fit, so the analytic bound under-estimated — grow the
        family multiplier until this geometry predicts ≥ factor × its
        previous estimate. The next plan for the same family will split
        harder (or declare infeasibility) instead of re-OOMing."""
        key = f"{g.kind}:{_mode_family(g.mode)}"
        self.multipliers[key] = \
            self.multipliers.get(key, _DEFAULT_MULTIPLIER) * float(factor)

    # -------------------------------------------------------------- persist
    def to_dict(self) -> dict:
        return {"multipliers": dict(self.multipliers),
                "residuals": dict(self.residuals)}

    def save(self, path: str) -> None:
        """Persist calibration beside the kernel-cache artifacts (atomic
        replace; the CI sweep and the next process both load it)."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "CostModel":
        with open(path) as f:
            data = json.load(f)
        model = cls(multipliers=data.get("multipliers") or {})
        model.residuals = dict(data.get("residuals") or {})
        return model

    @classmethod
    def load_or_default(cls, path: Optional[str]) -> "CostModel":
        if path:
            try:
                return cls.load(path)
            except (OSError, ValueError):
                pass
        return cls()


@dataclass(frozen=True)
class PlanDecision:
    """What the planner decided for one geometry: run it fused
    (``splits == 1, scan_chunk == 0``), chunk the arena scan inside the
    ONE dispatch, split the batch into ``splits`` planned sub-dispatches,
    or reject it (``feasible == False``)."""

    feasible: bool
    splits: int = 1
    scan_chunk: int = 0
    predicted_bytes: int = 0
    budget_bytes: int = 0
    reason: str = "fits"

    @property
    def fused(self) -> bool:
        return self.feasible and self.splits == 1 and self.scan_chunk == 0


def _bucket(n: int, granularity: int) -> int:
    g = max(1, granularity)
    return max(g, -(-n // g) * g)


def plan_geometry(model: CostModel, g: Geometry, budget_bytes: int,
                  headroom_fraction: float = 0.1, *,
                  chunkable: bool = True, granularity: int = 8,
                  max_splits: int = 16, min_scan_chunk: int = 8
                  ) -> PlanDecision:
    """The split decision tree (shared by the live planner and the CI
    sweep), cheapest-degradation-first:

    1. **fused** — the geometry fits as-is: ONE dispatch, default chunks.
    2. **chunk the scan** — halve the in-kernel query chunk (the
       ``[chunk, rows]`` score tile is the dominant transient) until the
       prediction fits: STILL one dispatch, ``dispatches_per_turn`` stays
       1, only the streaming granularity changes (bit-identical results).
    3. **split the batch** — sub-dispatches riding the existing linear
       pad buckets (each sub-batch re-buckets to ``granularity``),
       combined with the best scan chunk; a planned multi-dispatch turn,
       recorded as such.
    4. **infeasible** — the per-chip RESIDENT set alone (which no split
       can shrink) or even the maximally-split geometry exceeds the
       budget: typed rejection, shed like LoadShed.
    """
    if budget_bytes <= 0:
        return PlanDecision(True, 1, 0, model.predict(g), 0,
                            "planner disabled")
    eff = int(budget_bytes * (1.0 - max(0.0, headroom_fraction)))
    pred = model.predict(g)
    if pred <= eff:
        return PlanDecision(True, 1, 0, pred, eff, "fits")
    # The resident floor bounds what ANY split can reach.
    floor = int(model.resident_bytes(g) * model._mult(g.kind, g.mode))
    if floor > eff:
        return PlanDecision(False, 0, 0, floor, eff,
                            "resident live set alone exceeds the budget")
    fam = _mode_family(g.mode)
    default_chunk = IVF_SERVE_CHUNK if fam == "ivf" else QUERY_CHUNK
    best_chunk = 0
    if chunkable:
        c = min(g.batch, default_chunk)
        while c >= min_scan_chunk:
            p = model.predict(g.with_(scan_chunk=c))
            if p <= eff:
                return PlanDecision(True, 1, c, p, eff, "scan chunked")
            best_chunk = c
            c //= 2
        best_chunk = max(min_scan_chunk, best_chunk // 2 or min_scan_chunk)
    for s in range(2, max_splits + 1):
        sub = _bucket(-(-g.batch // s), granularity)
        sg = g.with_(batch=sub,
                     scan_chunk=(min(best_chunk, sub) if chunkable else 0))
        p = model.predict(sg)
        if p <= eff:
            return PlanDecision(True, s, sg.scan_chunk, p, eff,
                                f"batch split {s}-way")
        if sub <= granularity:
            break                       # can't split finer than one bucket
    return PlanDecision(False, 0, 0, pred, eff,
                        "no batch split or scan chunk fits the budget")


__all__ = ["Geometry", "CostModel", "PlanDecision", "plan_geometry",
           "QUERY_CHUNK", "IVF_SERVE_CHUNK", "ARENA_META_BYTES",
           "EDGE_SLOT_BYTES"]
