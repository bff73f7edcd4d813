"""The system under test, stood up for one configuration. The only module of
the benchmark that imports the program.

Serving rows are made on the device from the seed (``corpus.block_rows``) and
installed through ``MemoryIndex``'s ``state`` setter with the host maps filled
as ``MemoryIndex.add`` fills them — never millions of facts through
``end_conversation``. Warm-up drives the program's own ``warmup_serving`` /
conversation API with the shapes the cell's traffic will use and no others.

A configuration may name its layout, ``"mesh": {"axes": ["data"], "shape":
[4]}``: the system is then built over that mesh of the first devices and its
arena is made and filled shard by shard, never whole on one chip. Without the
key nothing here touches a mesh.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from lazzaro_tpu import MemorySystem
from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.parallel.mesh import make_mesh
from lazzaro_tpu.serve.scheduler import RetrievalRequest
from lazzaro_tpu.utils.batching import bucket_size, next_pow2
from lazzaro_tpu.utils.compile_cache import place_compile_cache
from lazzaro_tpu.utils.telemetry import Telemetry

from benchmark import corpus

SALIENCE = 0.6

RELIABILITY_COUNTERS = ("reliability.ingest_failures", "serve.dispatch_retries",
                        "reliability.poisoned", "reliability.oom",
                        "plan.split_dispatches", "reliability.load_shed",
                        "reliability.worker_restarts")


def place_cache() -> str:
    """The program places its persistent compile cache (inside the checkout
    unless the environment names a directory); the benchmark only lowers
    jax's thresholds in its own process so that the hundreds of sub-second
    programs are served from it too."""
    path = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def mesh_of(cfg: dict) -> Optional[Mesh]:
    """The mesh a configuration names, over the first devices jax has (too
    few of them is ``make_mesh``'s error); None where it names none."""
    if "mesh" not in cfg:
        return None
    axes, shape = cfg["mesh"]["axes"], cfg["mesh"]["shape"]
    return make_mesh(tuple(axes), tuple(shape),
                     devices=jax.devices()[:math.prod(shape)])


def build_system(cfg: dict, work_dir: str, embedder=None, llm=None
                 ) -> MemorySystem:
    """One ``MemorySystem`` of the configuration's deployment: its
    ``memory_config`` is the program's ``MemoryConfig``, field for field, so a
    configuration switches a serving mode on by naming the field. Only what
    belongs to this run is set here (where it writes, that it is quiet, and
    the mesh the configuration names)."""
    mc = cfg["memory_config"]
    if (mc["embed_dim"], mc["dtype"]) != (cfg["dim"], cfg["dtype"]):
        raise ValueError("memory_config's embed_dim and dtype have to be the "
                         "configuration's dim and dtype")
    kw = {}
    if embedder is not None:
        kw.update(embedding_provider=embedder, llm_provider=llm)
    mesh = mesh_of(cfg)
    if mesh is None:
        return MemorySystem(
            config=MemoryConfig(**mc, db_dir=os.path.join(work_dir, "db")),
            verbose=False, **kw)
    # MemoryIndex makes its arena whole on ONE chip and reshards it afterwards
    # (S.init_arena in __init__, S.grow_arena), so a deployment larger than a
    # chip cannot be constructed as configured (PERF.md, Open questions). The
    # system is built one select block large and handed the arena it would
    # have made, every column made in its shards.
    small = dict(mc, initial_capacity=S.TOPK_BLOCK - 1)
    ms = MemorySystem(
        config=MemoryConfig(**small, db_dir=os.path.join(work_dir, "db")),
        verbose=False, mesh=mesh, **kw)
    ms.config.initial_capacity = mc["initial_capacity"]
    _arena_in_shards(ms.index, mc["initial_capacity"])
    return ms


def _arena_in_shards(idx, capacity: int) -> None:
    """An empty arena of ``capacity`` rows (rounded as the index rounds it)
    in the index's own shardings, each shard made on its chip."""
    cap = idx._round_capacity(capacity)
    make = functools.partial(S.init_arena, cap, idx.dim, idx.dtype)
    shardings = jax.tree_util.tree_map(
        lambda a: idx._mat_sharding if a.ndim == 2 else idx._row_sharding,
        jax.eval_shape(make))
    idx.state = jax.jit(make, out_shardings=shardings)()
    idx._free_rows = list(range(cap - 1, -1, -1))


@functools.partial(jax.jit, donate_argnums=(0,))
def _place(emb, block, row0):
    return jax.lax.dynamic_update_slice(emb, block, (row0, jnp.int32(0)))


def _place_in_shards(mesh, axis: str):
    """``_place`` for an arena row-sharded over ``axis``: every chip writes
    the part of the block that falls into its own rows, in place, and no chip
    ever holds another's (left to GSPMD, ``_place`` all-gathers the arena).
    A block may straddle two shards or miss a shard altogether."""
    def local(emb, block, row0):
        n, b = emb.shape[0], block.shape[0]
        off = row0 - jax.lax.axis_index(axis) * n     # the block's first row, here
        at = jnp.clip(off, 0, n - b)
        j = jnp.arange(b, dtype=jnp.int32) + (at - off)   # block row of emb[at + i]
        cur = jax.lax.dynamic_slice(emb, (at, jnp.int32(0)), block.shape)
        new = jnp.where(((j >= 0) & (j < b))[:, None],
                        jnp.roll(block, off - at, axis=0), cur)
        return jax.lax.dynamic_update_slice(emb, new, (at, jnp.int32(0)))

    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(axis, None), P(), P()),
                                 out_specs=P(axis, None)),
                   donate_argnums=(0,))


@functools.partial(jax.jit, static_argnames=("n",))
def _columns(starts, rows, tid0, type_id, shard_id, *, n: int):
    r = jnp.arange(n, dtype=jnp.int32)
    live = r < rows
    t = jnp.clip(jnp.searchsorted(starts, r, side="right") - 1,
                 0, starts.shape[0] - 2).astype(jnp.int32)
    zf = jnp.zeros((n,), jnp.float32)
    return dict(
        salience=jnp.where(live, jnp.float32(SALIENCE), 0.0),
        timestamp=zf, last_accessed=zf,
        access_count=jnp.zeros((n,), jnp.int32),
        type_id=jnp.where(live, type_id, 0).astype(jnp.int32),
        shard_id=jnp.where(live, shard_id, -1).astype(jnp.int32),
        tenant_id=jnp.where(live, t + tid0, -1).astype(jnp.int32),
        alive=live, is_super=jnp.zeros((n,), bool))


def parse_node_id(nid: str) -> Tuple[int, int]:
    """(tenant, fact) of an id ``install_rows`` made; (-1, -1) otherwise."""
    name, _, f = nid.partition(":")
    if name[:1] == "t" and name[1:].isdigit() and f[:1] == "f" \
            and f[1:].isdigit():
        return int(name[1:]), int(f[1:])
    return -1, -1


def install_rows(ms: MemorySystem, cfg: dict, seed: int, rows: int,
                 tenant_first: int, tenants: int) -> np.ndarray:
    """Fill arena rows ``[0, rows)`` tenant-major with the seeded corpus of
    tenants ``tenant_first .. tenant_first + tenants`` and register them with
    the index as ``add`` would. Returns the tenants' row boundaries."""
    idx = ms.index
    if idx.id_to_row:
        raise RuntimeError("install_rows needs an empty index")
    block = cfg["fill_block_rows"]
    if rows % block or rows > idx.capacity:
        raise ValueError(f"{rows} rows do not divide into blocks of {block} "
                         f"inside capacity {idx.capacity}")
    starts = corpus.tenant_starts(rows, tenants)
    names = [corpus.tenant_name(tenant_first + t) for t in range(tenants)]
    tids = [idx.tenant_id(n) for n in names]
    if tids != list(range(tids[0], tids[0] + tenants)):
        raise RuntimeError("the index did not number the tenants in order")
    st = idx.state
    n = int(st.salience.shape[0])
    seed2 = jnp.asarray(corpus.seed_words(seed))
    starts_d = jnp.asarray(starts)
    emb = st.emb
    place = (_place if idx.mesh is None
             else _place_in_shards(idx.mesh, idx.shard_axis))
    for row0 in range(0, rows, block):
        blk, _, _ = corpus.block_rows(
            seed2, starts_d, jnp.int32(tenant_first), jnp.int32(row0),
            block=block, dim=cfg["dim"], dtype=cfg["dtype"])
        emb = place(emb, blk, jnp.int32(row0))
    cols = _columns(starts_d, jnp.int32(rows), jnp.int32(tids[0]),
                    jnp.int32(S.TYPE_IDS.get("semantic", 0)),
                    jnp.int32(idx.shard_id("default")), n=n)
    idx.state = st.replace(emb=emb, **cols)
    del st, emb, cols
    ids: List[str] = []
    for t in range(tenants):
        name = names[t]
        mine = [f"{name}:f{j}" for j in range(int(starts[t + 1] - starts[t]))]
        idx.tenant_nodes[name] = set(mine)
        ids.extend(mine)
    idx.id_to_row.update(zip(ids, range(rows)))
    idx.row_to_id.update(enumerate(ids))
    idx._free_rows = list(range(idx.capacity - 1, rows - 1, -1))
    idx._int8_dirty = True
    idx._emb_gen += 1
    jax.block_until_ready(idx.state.emb)
    return starts


def install_graph(ms: MemorySystem, cfg: dict, starts: np.ndarray,
                  tenant_first: int, rows_of: Callable[[int], np.ndarray]
                  ) -> int:
    """The edges the configuration's ``graph`` group names
    (``corpus.tenant_edges`` of each tenant's rows, ``rows_of(t)``), through
    ``MemoryIndex.add_edges``, tenant by tenant. Returns how many were
    installed; the configured edge arena has to hold them as it is."""
    idx = ms.index
    want = 0
    for t in range(len(starts) - 1):
        name = corpus.tenant_name(tenant_first + t)
        edges = corpus.tenant_edges(rows_of(t), cfg["graph"])
        idx.add_edges([(f"{name}:f{a}", f"{name}:f{b}", w)
                       for a, b, w in edges], name)
        want += len(edges)
    if len(idx.edge_slots) != want or want > ms.config.max_edges:
        raise RuntimeError(
            f"the graph has {want} edges, the index holds "
            f"{len(idx.edge_slots)} and memory_config.max_edges is "
            f"{ms.config.max_edges}")
    return want


def index_clock(ms: MemorySystem) -> float:
    """Seconds on the clock the index stamps ``last_accessed`` with."""
    return time.time() - ms.index.epoch


def read_state(ms: MemorySystem) -> Dict[str, np.ndarray]:
    """What the window's boosting reads left: the three columns a boost
    writes, whole, by arena row."""
    st = ms.index.state
    return {name: np.asarray(getattr(st, name))
            for name in ("access_count", "salience", "last_accessed")}


def warm_serving(ms: MemorySystem, cfg: dict) -> None:
    """Every padded batch the scheduler can dispatch up to its maximum (the
    open and closed loops both see batches of any size), and no other."""
    mc = ms.config
    ms.warmup_serving(sorted({bucket_size(b, mc.serve_pad_granularity)
                              for b in range(1, mc.serve_batch_max + 1)}))
    ms._ensure_scheduler()
    # one live request through the scheduler itself: worker thread, demux
    ms.query_scheduler.submit(RetrievalRequest(
        query=np.ones((cfg["dim"],), np.float32),
        tenant=next(iter(ms.index.tenant_nodes)), k=cfg["k"])).result()


def _with_privates(index, *names: str):
    """``index``, once it is seen to have the private attributes that set-up
    is about to write: the program has no public warm-up of these (PERF.md,
    Open questions), so a rename there has to fail here, by name, and not
    turn the override into a new attribute nothing reads."""
    missing = [n for n in names if not hasattr(index, n)]
    if missing:
        raise AttributeError(
            f"{type(index).__name__} has no {', '.join(missing)}: the "
            "benchmark's set-up writes it (deploy.presize_csr / "
            "deploy.beside_a_reader) and has to follow the program's rename")
    return index


def presize_csr(ms: MemorySystem) -> None:
    """The serving programs take the CSR's neighbour array padded to a power
    of two that only ever grows (``MemoryIndex._csr_pad_hwm``), and recompile
    each time it doubles. A deployment that has been written to for a while
    sits at its high-water mark; set-up puts a fresh one there before it
    warms the serving programs: the pad that its edge arena (``max_edges``
    keys, listed in both directions) can ever need. Under a writer nothing
    then compiles inside a window (PERF.md, Open questions)."""
    idx = _with_privates(ms.index, "_csr_pad_hwm", "_csr_dirty")
    idx._csr_pad_hwm = max(idx._csr_pad_hwm, next_pow2(2 * ms.config.max_edges))
    idx._csr_dirty = True


@contextlib.contextmanager
def beside_a_reader(ms: MemorySystem):
    """Inside, every donation gate of the index finds a second owner, as it
    does while a reader's dispatch holds the arena: a writer takes its
    COPYING twins. For warm-up alone, so that those programs are compiled
    before a window in which readers run beside the writer. (The gate
    counts references against ``MemoryIndex._SOLE_REFS``; the program has
    no switch that forces it.)"""
    idx = _with_privates(ms.index, "_SOLE_REFS")
    idx._SOLE_REFS = -1
    try:
        yield
    finally:
        del idx._SOLE_REFS


def run_conversation(ms: MemorySystem, tenant: int, conv: int = 0) -> None:
    """The conversation API, as a user's session drives it."""
    ms.switch_user(corpus.tenant_name(tenant))
    ms.start_conversation()
    ms.add_to_short_term(corpus.transcript_text(tenant, conv), "episodic", 0.7)
    ms.end_conversation()


def make_requests(queries: np.ndarray, tenants: Sequence[int], k: int,
                  boost: Optional[np.ndarray] = None) -> List[RetrievalRequest]:
    """The scheduler's own request type, one per query row; where ``boost``
    marks a row, a ``chat`` retrieval (``boost=True``): the read that bumps
    access and neighbour salience in the dispatch that serves it."""
    names = {int(t): corpus.tenant_name(t) for t in np.unique(tenants)}
    flags = [False] * len(tenants) if boost is None else boost.tolist()
    return [RetrievalRequest(query=queries[i], tenant=names[int(t)], k=k,
                             boost=flags[i])
            for i, t in enumerate(tenants)]


def read_back(ms: MemorySystem, tenant: int, facts: Sequence[int],
              vectors: np.ndarray, k: int):
    """What tenant ``tenant`` reads after its conversation was acknowledged,
    through the program's own API: (nodes it holds, search_memories hits per
    fact text, scheduler hits and scores per fact vector), each hit as the
    (tenant, fact) its content names."""
    name = corpus.tenant_name(tenant)
    ms.switch_user(name)
    nodes = ms.buffer.size()[0]
    by_text = [[corpus.fact_of(n.content) or (-1, -1) for n in
                ms.search_memories(corpus.fact_text(tenant, j), limit=k)]
               for j in facts]
    by_vec = []
    for fut in ms.query_scheduler.submit_many(
            make_requests(vectors, [tenant] * len(facts), k)):
        res = fut.result(timeout=60)
        hits = []
        for nid in res.ids:
            node = ms.buffer.get_node(nid.partition(":")[2])
            hits.append((corpus.fact_of(node.content) if node else None)
                        or (-1, -1))
        by_vec.append((hits, list(res.scores)))
    return nodes, by_text, by_vec


def telemetry_copy(ms: MemorySystem) -> Telemetry:
    """The program's registry as it stands: timers and counters copied, so
    that what runs later (the writer's read-back goes through the scheduler
    too) stays out of the metrics of the window."""
    src = ms.telemetry
    out = Telemetry(window=src.window)
    for key, values in list(src.timers.items()):
        out.timers[key].extend(values)
    out.counters.update(dict(src.counters))
    return out


def counters(ms: MemorySystem) -> dict:
    return {c: int(ms.telemetry.counter_total(c)) for c in RELIABILITY_COUNTERS}


def device_info(chips: int) -> dict:
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(
                (int(s.get("peak_bytes_in_use", 0)) for s in stats), default=0)}
