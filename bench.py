"""Benchmark: BASELINE.md's metric surface, measured through the orchestrator.

Builds a 1M-node graph by driving `MemorySystem.end_conversation` — the FULL
ingest pipeline (LLM extract → batch embed → batched dedup probe → arena
insert → link matmuls → delta-segment save), then measures:

  headline : p50 `MemorySystem.search_memories()` latency at 1M nodes
             (query embed → arena top-k → id decode → host node fetch →
             neighbor boost bookkeeping — the reference's "p50
             search_memories()" surface, memory_system.py:262-351)
  extra    : ingest_pipeline_memories_per_sec_per_chip — end-to-end
             `end_conversation` throughput (memory_system.py:651-785 analog)
  extra    : raw kernel numbers under HONEST names (arena_search_p50_ms is
             a bare matvec+top-k; arena_scatter_rows_per_sec is a scatter,
             NOT ingest).

Every timed region ends in a device→host transfer (``np.asarray`` of the
result), so it times completion and not the enqueue, and the JSON
self-reports the implied HBM bandwidth and FLOP/s against the device's
peaks (``DEVICE_PEAKS``, keyed by ``device_kind``) — any fraction > 1.0
sets ``roofline_suspect``.

Runs on the default backend, whatever it is: the artifact's ``device``
field says which, and a number from a CPU run is not a device metric. An
exception is a traceback and a non-zero exit.

Prints ONE JSON line. Env overrides:
  BENCH_N / BENCH_DIM        — graph size / embedding dim (smoke runs)
  BENCH_WORKDIR              — persistent dir: ingest once, re-run
                               search-only (default: a fresh
                               ``bench_workdir/`` under the checkout)
  BENCH_INGEST_BUDGET_S      — stop ingest early past this budget (default
                               3000 s) and bench at the size reached
  BENCH_LLM_LOOP=1           — also measure consolidation with the on-device
                               LLM (extract → constrained JSON → ingest)
"""

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from lazzaro_tpu import MemorySystem
from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.ops import backend
from lazzaro_tpu.utils.compile_cache import place_compile_cache

N = int(os.environ.get("BENCH_N", 1_000_000))
DIM = int(os.environ.get("BENCH_DIM", 768))
INGEST_BUDGET_S = float(os.environ.get("BENCH_INGEST_BUDGET_S", 3000))
FACTS_PER_CONV = min(5_000, N)
CONVS = max(1, N // FACTS_PER_CONV)
TOTAL = FACTS_PER_CONV * CONVS
K_WARM = 5
QUERIES = 50

# Per-chip peaks, keyed by ``jax.devices()[0].device_kind``: the denominators
# of the roofline self-check. Source: Google Cloud documentation, "TPU v5e".
# A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def _device_peaks() -> dict:
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no peaks recorded for device_kind {kind!r}; add it "
                       f"to DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[kind]


def _roofline(n_rows: int, dim: int, dtype_bytes: int, ms: float,
              batch: int = 1, on_tpu: bool = True):
    """Implied HBM traffic and FLOP rate of one arena scan finishing in
    ``ms``. A single query must stream the whole [n_rows, dim] arena from
    HBM (bytes independent of batch — one matmul reads it once) and spend
    2·n_rows·dim·batch FLOPs. Fractions > 1.0 of chip peak are physically
    impossible → the number is a measurement artifact, not a result."""
    sec = ms * 1e-3
    gbps = n_rows * dim * dtype_bytes / sec / 1e9
    tflops = 2.0 * n_rows * dim * batch / sec / 1e12
    out = {
        "implied_hbm_gbps": round(gbps, 1),
        "implied_bf16_tflops": round(tflops, 2),
    }
    if on_tpu:
        peaks = _device_peaks()
        out["frac_hbm_peak"] = round(gbps / peaks["hbm_gbps"], 3)
        out["frac_mxu_peak"] = round(tflops / peaks["bf16_tflops"], 3)
        out["suspect"] = bool(gbps > peaks["hbm_gbps"]
                              or tflops > peaks["bf16_tflops"])
    return out


def _telemetry_block(tel) -> dict:
    """ISSUE 6: the observability block every fused bench artifact embeds —
    the full ``Telemetry.snapshot()`` plus the derived headline numbers
    (pad-waste fraction, batch occupancy, queue-wait percentiles, peak-HBM
    gauges) that ``scripts/check_dispatch_counts.py`` requires. Batch
    occupancy / pad-waste are the measured baseline the ragged-serving
    direction (ROADMAP item 4) will be judged against."""
    snap = tel.snapshot()
    live = tel.counter_total("serve.live_requests")
    padded = tel.counter_total("serve.padded_slots")
    qw = tel.timer_values("serve.queue_wait_ms")
    peak = {k: v for k, v in snap["gauges"].items()
            if k.startswith("kernel.peak_hbm_bytes")}
    return {
        "pad_waste_fraction": (round(1.0 - live / padded, 4)
                               if padded else 0.0),
        "batch_occupancy": round(live / padded, 4) if padded else 1.0,
        "queue_wait_ms_p50": (round(float(np.percentile(qw, 50)), 3)
                              if qw else None),
        "queue_wait_ms_p95": (round(float(np.percentile(qw, 95)), 3)
                              if qw else None),
        "peak_hbm_bytes": peak or None,
        "snapshot": snap,
    }


# ---------------------------------------------------------------------------
# Synthetic corpus with REAL graph structure (near-orthogonal
# vectors produced a degenerate bench graph — links decayed+pruned to an
# empty edge arena, and consolidation had nothing to do). Geometry:
#
#   fact vec = 0.5·topic_dir + 0.794·group_dir + 0.346·noise   (unit norm)
#
#   - GROUP=4 facts share a group_dir → intra-group cosine ≈ 0.88: above
#     the 0.5 link gate (edge weight 0.88·0.8 ≈ 0.70 survives ~35 decay
#     passes before the 0.5 prune gate — the measured graph keeps a live
#     edge set), below the 0.95 dedup gate (they stay distinct nodes).
#   - 12 topic_dirs, one per shard → shard centroid ≈ topic_dir, and a
#     fact×centroid cosine ≈ 0.5 clears the 0.4 super-node gate, so the
#     hierarchy fast path actually fires in the hierarchy-on stage.
#     Inter-group same-topic cosine ≈ 0.25: below the link gate.
#   - every DUP_EVERY-th fact is a 0.97-cosine near-duplicate of its
#     predecessor → the ingest dedup-merge path does real work in the
#     measured run.
# ---------------------------------------------------------------------------
GROUP = 4
N_TOPICS = 12
DUP_EVERY = 101
TOPIC_W = 0.5
GROUP_W = float(np.sqrt(0.63))
NOISE_W = float(np.sqrt(0.12))
TOPICS = ["work", "hobbies", "family", "travel", "health", "food",
          "sports", "music", "books", "tech", "home", "finance"]


def _unit(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(DIM).astype(np.float32)
    return v / np.linalg.norm(v)


_TOPIC_DIRS = None


def _topic_dir(t: int) -> np.ndarray:
    global _TOPIC_DIRS
    if _TOPIC_DIRS is None:
        _TOPIC_DIRS = [_unit(10_000_000 + i) for i in range(N_TOPICS)]
    return _TOPIC_DIRS[t]


def _group_of(idx: int, corpus_n: int) -> int:
    # INTERLEAVED grouping: mates of group g sit at g, g+N/4, g+N/2,
    # g+3N/4 — i.e. in DIFFERENT conversations. The link scan excludes
    # same-batch rows as candidates (they are not "existing memories"
    # yet), so contiguous groups would never produce similarity links at
    # all — exactly the degeneracy this corpus exists to kill.
    return idx % max(1, corpus_n // GROUP)


def _fact_topic(idx: int, corpus_n: int) -> str:
    return TOPICS[_group_of(idx, corpus_n) % N_TOPICS]


def _is_dup(idx: int) -> bool:
    return idx % DUP_EVERY == DUP_EVERY - 1 and idx > 0


def _fact_vec(idx: int, corpus_n: int) -> np.ndarray:
    if _is_dup(idx):
        base = _fact_vec(idx - 1, corpus_n)
        v = base + 0.25 * _unit(3 * idx + 1)       # cosine ≈ 0.970 > 0.95
        return v / np.linalg.norm(v)
    g = _group_of(idx, corpus_n)
    v = (TOPIC_W * _topic_dir(g % N_TOPICS)
         + GROUP_W * _unit(1_000_000_000 + g)
         + NOISE_W * _unit(idx))
    return (v / np.linalg.norm(v)).astype(np.float32)


class BulkEmbedder:
    """Deterministic clustered vectors keyed by the fact index in the text
    ("fact <i>: ..."), so bench queries can dial up exact hits.

    ``corpus_n`` fixes the group-interleaving stride — the same value must
    feed the embedder and the payload generator of one corpus."""

    dim = DIM

    def __init__(self, corpus_n: int = None):
        self.corpus_n = corpus_n or TOTAL

    def _vec(self, text: str) -> np.ndarray:
        if text.startswith("fact"):
            idx = int(text.split(":")[0].split()[-1])
        else:
            idx = abs(hash(text)) % (1 << 31)
        return _fact_vec(idx, self.corpus_n)

    def embed(self, text):
        return self._vec(text).tolist()

    def batch_embed(self, texts):
        return [self._vec(t).tolist() for t in texts]


_PROFILE_PAYLOAD = json.dumps({
    "knowledge_domains": "Synthetic bench corpus: clustered user details "
                         "across twelve topical shards."})


class QueueLLM:
    """Pops one canned extraction payload per completion call — the LLM stage
    is deterministic; everything downstream is the production pipeline.
    Profile-extraction prompts (run_consolidation's component pass) get a
    canned profile JSON instead of consuming ingest payloads, so the deep-
    consolidation stage exercises the real profile-update path."""

    def __init__(self, payloads):
        self.payloads = list(payloads)

    def completion(self, messages, response_format=None):
        sys_msg = messages[0].get("content", "") if messages else ""
        if "personality insights" in sys_msg:
            return _PROFILE_PAYLOAD
        return self.payloads.pop(0) if self.payloads else json.dumps({"memories": []})

    def completion_stream(self, messages, response_format=None):
        yield self.completion(messages, response_format)


def _payload(conv: int, facts_per_conv: int = None,
             corpus_n: int = None) -> str:
    fpc = facts_per_conv or FACTS_PER_CONV
    cn = corpus_n or TOTAL
    base = conv * fpc
    return json.dumps({"memories": [
        {"content": f"fact {base + i}: user detail number {base + i}",
         "type": "semantic", "salience": 0.6,
         "topic": _fact_topic(base + i, cn)}
        for i in range(fpc)]})


def build_system(db_dir: str, load_from_disk: bool = False,
                 first_conv: int = CONVS) -> MemorySystem:
    # Queue only the payloads this run will actually extract (resume runs
    # start at first_conv; pure-reuse runs never call the LLM at all) —
    # don't spend minutes JSON-encoding 1M canned facts nobody pops.
    payloads = [_payload(c) for c in range(first_conv, CONVS)]
    return MemorySystem(
        enable_async=False,
        enable_hierarchy=False,
        auto_consolidate=False,
        load_from_disk=load_from_disk,
        max_buffer_size=TOTAL * 2,
        db_dir=db_dir,
        llm_provider=QueueLLM(payloads),
        embedding_provider=BulkEmbedder(),
        config=MemoryConfig(
            dtype="bfloat16",
            journal=False,
            # preallocated: program shapes follow CAPACITY, so a growing
            # arena would recompile every kernel once per doubling
            initial_capacity=TOTAL + 64,
            max_edges=2 * TOTAL + 64,
        ),
        verbose=False,
    )


def bench_kernels(on_tpu: bool):
    """Raw kernel reference numbers (honest labels: NOT the system metrics).
    A/Bs the XLA one-matmul top-k against the blocked Pallas kernel that
    ``arena_search`` auto-dispatches to on block-aligned TPU arenas.
    Timed regions end in np.asarray — forced device→host readback."""
    n_rows = -(-(N + 1) // S.TOPK_BLOCK) * S.TOPK_BLOCK  # arena alignment rule
    key = jax.random.PRNGKey(0)
    emb = S.normalize(jax.random.normal(key, (n_rows, DIM), jnp.bfloat16))
    # one DISTINCT buffer per column (donated kernels reject a pytree that
    # aliases the same buffer across leaves — init_arena's contract)
    arena = S.ArenaState(
        emb=emb,
        salience=jnp.full((n_rows,), 0.5, jnp.float32),
        timestamp=jnp.zeros((n_rows,), jnp.float32),
        last_accessed=jnp.zeros((n_rows,), jnp.float32),
        access_count=jnp.zeros((n_rows,), jnp.int32),
        type_id=jnp.zeros((n_rows,), jnp.int32),
        shard_id=jnp.zeros((n_rows,), jnp.int32),
        tenant_id=jnp.zeros((n_rows,), jnp.int32),
        alive=jnp.ones((n_rows,), bool).at[N:].set(False),
        is_super=jnp.zeros((n_rows,), bool),
    )
    np.asarray(arena.emb[:2])            # materialize before timing
    queries = jax.random.normal(jax.random.PRNGKey(7), (K_WARM + QUERIES, DIM),
                                jnp.float32)
    tenant = jnp.int32(0)
    lat_by_impl = {}
    for impl in (("xla", "pallas") if on_tpu else ("xla",)):
        for i in range(K_WARM):
            _, r = S.arena_search(arena, queries[i], tenant, 10, impl=impl)
            np.asarray(r)
        lat_by_impl[impl] = []
        for i in range(K_WARM, K_WARM + QUERIES):
            t0 = time.perf_counter()
            _, r = S.arena_search(arena, queries[i], tenant, 10, impl=impl)
            np.asarray(r)                # forced device→host sync in timed region
            lat_by_impl[impl].append((time.perf_counter() - t0) * 1e3)

    # Batched (64-query) arena scan: one matmul amortizes the HBM stream.
    qb = jax.random.normal(jax.random.PRNGKey(9), (64, DIM), jnp.float32)
    for _ in range(3):
        _, r = S.arena_search(arena, qb, tenant, 10)
        np.asarray(r)
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        _, r = S.arena_search(arena, qb, tenant, 10)
        np.asarray(r)
    batch64_ms = (time.perf_counter() - t0) * 1e3 / reps

    # Int8 serving shadow: half the scan bytes (ops/quant.py).
    from lazzaro_tpu.ops.quant import quantize_rows, quantized_topk

    q8, qsc = quantize_rows(arena.emb)
    mask = arena.alive
    for _ in range(3):
        _, r = quantized_topk(q8, qsc, mask, queries[:1], 10)
        np.asarray(r)
    lat_i8 = []
    for i in range(K_WARM, K_WARM + QUERIES):
        t0 = time.perf_counter()
        _, r = quantized_topk(q8, qsc, mask, queries[i:i + 1], 10)
        np.asarray(r)
        lat_i8.append((time.perf_counter() - t0) * 1e3)
    int8_p50 = float(np.percentile(lat_i8, 50))
    for _ in range(3):
        _, r = quantized_topk(q8, qsc, mask, qb, 10)
        np.asarray(r)
    t0 = time.perf_counter()
    for _ in range(reps):
        _, r = quantized_topk(q8, qsc, mask, qb, 10)
        np.asarray(r)
    int8_batch64_ms = (time.perf_counter() - t0) * 1e3 / reps
    del q8, qsc

    B = 1024
    add_emb = jax.random.normal(jax.random.PRNGKey(3), (B, DIM), jnp.float32)
    rows = jnp.arange(B, dtype=jnp.int32)
    args = (jnp.full((B,), 0.5), jnp.zeros((B,)), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), bool))
    reps = 20
    # A/B the donation win: the copying twin first (XLA copies the full
    # arena per scatter — the pre-donation behavior), then the donated
    # default (in-place alias; the chain threads ownership forward).
    a_copy = S.arena_add_copy(arena, rows, add_emb, *args)
    np.asarray(a_copy.emb[:2])
    t0 = time.perf_counter()
    for _ in range(reps):
        a_copy = S.arena_add_copy(a_copy, rows, add_emb, *args)
    np.asarray(a_copy.emb[:2])           # forced sync closes the timed region
    scatter_copy_rows = reps * B / (time.perf_counter() - t0)
    del a_copy
    a2 = S.arena_add(arena, rows, add_emb, *args)   # consumes `arena`
    np.asarray(a2.emb[:2])
    t0 = time.perf_counter()
    for _ in range(reps):
        a2 = S.arena_add(a2, rows, add_emb, *args)
    np.asarray(a2.emb[:2])               # forced sync closes the timed region
    scatter_rows = reps * B / (time.perf_counter() - t0)
    del arena, a2, emb
    p50s = {impl: float(np.percentile(l, 50)) for impl, l in lat_by_impl.items()}
    p50s["int8"] = int8_p50
    return (p50s, batch64_ms, int8_batch64_ms, n_rows, scatter_rows,
            scatter_copy_rows)


def bench_fused_ingest(on_tpu: bool):
    """Fused single-dispatch ingest rate: batches of B facts through
    ``MemoryIndex.ingest_batch`` — node scatter + dedup merge touch +
    two-mode link scan + gated edge insert, ONE donated dispatch + ONE
    packed readback per batch. Timed to the readback inside ingest_batch
    (its host decode runs after fetch_packed), honest by construction."""
    from lazzaro_tpu.core.index import MemoryIndex

    n_rows = min(N, 65_536)
    B = 1024
    reps = 3
    rng = np.random.default_rng(17)
    idx = MemoryIndex(dim=DIM, capacity=n_rows + 64,
                      edge_capacity=65_535, dtype=jnp.bfloat16)

    def batch(c):
        emb = rng.standard_normal((B, DIM)).astype(np.float32)
        ids = [f"f{c}_{i}" for i in range(B)]
        chains = list(zip(ids, ids[1:]))
        return ids, emb, chains

    def run(c):
        ids, emb, chains = batch(c)
        idx.ingest_batch(ids, emb, [0.5] * B, [0.0] * B, ["semantic"] * B,
                         ["default"] * B, "u0", chain_pairs=chains)

    # precompile the ingest kernels (ISSUE 9 satellite) plus one real
    # warm batch, so the timed section never includes cold-compile time
    idx.warmup_ingest((B,))
    run(0)
    t0 = time.perf_counter()
    for c in range(1, reps + 1):
        run(c)
    return reps * B / (time.perf_counter() - t0)


def bench_fused_retrieval(on_tpu: bool):
    """Fused vs classic serving A/B at batch 64 (ISSUE 2 acceptance): the
    per-chat-turn retrieval sequence — super gate + ANN top-k + neighbor
    boost + access boost — as ONE ``search_fused_ragged`` dispatch per batch
    (``MemoryIndex.search_fused_requests``) against the classic sequence
    (two ``search_batch`` dispatches + ``update_access`` + ``boost``
    scatters + the host neighbor walk). Both sides serve the same arena,
    same queries, same boost semantics; timings close with the host-side
    result decode, honest by construction."""
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    n_rows = min(N, 65_536)
    B = 64
    reps = 5
    rng = np.random.default_rng(23)
    tel = Telemetry()
    idx = MemoryIndex(dim=DIM, capacity=n_rows + 64,
                      edge_capacity=max(65_535, 2 * n_rows - 1),
                      dtype=jnp.bfloat16, telemetry=tel,
                      telemetry_hbm=True,
                      # k=10 traffic: a 16 ceiling keeps the ragged kernel
                      # workload identical to the PR 6 k-bucket's
                      serve_k_max=16)
    for c in range(0, n_rows, 8192):
        m = min(8192, n_rows - c)
        emb = rng.standard_normal((m, DIM)).astype(np.float32)
        ids = [f"f{c + i}" for i in range(m)]
        idx.ingest_batch(ids, emb, [0.5] * m, [0.0] * m, ["semantic"] * m,
                         ["default"] * m, "u0",
                         chain_pairs=list(zip(ids, ids[1:])))
    # host adjacency for the classic neighbor walk (the serving-time analog
    # of buffer.get_neighbors; built once like the host graph would be)
    nbr_map = {}
    for (s, t) in idx.edge_slots:
        nbr_map.setdefault(s, []).append(t)
        nbr_map.setdefault(t, []).append(s)
    queries = rng.standard_normal((B, DIM)).astype(np.float32)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=10,
                             gate_enabled=True, boost=True)
            for i in range(B)]
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)

    def run_fused():
        return idx.search_fused_requests(reqs, **kw)

    def run_classic():
        # the per-turn chat sequence, batched where the classic path can:
        # gate search + ANN search + access boost + neighbor boost = 4
        # dispatches per batch (vs 1 fused)
        idx.search_batch(queries, "u0", k=1, super_filter=1, exact=True)
        per = idx.search_batch(queries, "u0", k=10, super_filter=-1)
        hit_ids = [i for ids_, _sc in per for i in ids_[:5]]
        idx.update_access(hit_ids, boost=0.05)
        retrieved = set(hit_ids)
        nbrs = {n for i in hit_ids for n in nbr_map.get(i, ())} - retrieved
        if nbrs:
            idx.boost(sorted(nbrs), 0.02)
        return per

    # warm/compile outside the timers (ISSUE 7 satellite: warmup_serving
    # pre-compiles the serving kernels and records kernel.warmup_ms)
    idx.warmup_serving((B,), cap_take=5, max_nbr=16)
    run_fused()
    run_classic()
    t0 = time.perf_counter()
    for _ in range(reps):
        run_fused()
    fused_ms = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_classic()
    classic_ms = (time.perf_counter() - t0) * 1e3 / reps
    return {
        "fused_retrieval_qps": round(reps and B / (fused_ms / 1e3), 1),
        "classic_retrieval_qps": round(B / (classic_ms / 1e3), 1),
        "fused_batch64_ms": round(fused_ms, 3),
        "classic_batch64_ms": round(classic_ms, 3),
        "fused_vs_classic_speedup": round(classic_ms / fused_ms, 2),
        "batch": B,
        "arena_rows": n_rows,
        "telemetry": _telemetry_block(tel),
        "roofline": {
            "fused_retrieval_batch64": _roofline(n_rows, DIM, 2, fused_ms,
                                                 B, on_tpu),
            "classic_retrieval_batch64": _roofline(n_rows, DIM, 2,
                                                   classic_ms, B, on_tpu),
        },
    }


def bench_fused_quant(on_tpu: bool, rows: int, reps: int = 3,
                      edge_rows: int = 100_000):
    """Quantized fused serving A/B (ISSUE 3 acceptance): batch-64 chat-turn
    retrieval through three paths over the SAME bf16 arena —

      classic_int8 : the classic multi-dispatch int8 sequence (exact gate
                     search + int8-shadow ANN scan + access/neighbor boost
                     scatters + host neighbor walk)
      fused_bf16   : ONE ``search_fused_ragged`` dispatch (exact
                     full-precision arena stream)
      fused_quant  : ONE ``search_fused_quant_ragged`` dispatch (int8 coarse
                     scan + exact rescore of k+slack survivors)

    The arena is populated by direct scatters (the serving A/B needs rows
    and a CSR edge band, not the link matmuls), and the fused-path
    dispatch count is MEASURED by wrapping the jit entry points — the
    artifact's ``dispatches_per_turn`` feeds scripts/
    check_dispatch_counts.py. Timed regions close with the host-side
    result decode (a real readback), honest by construction."""
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    B = 64
    rng = np.random.default_rng(31)
    tel = Telemetry()
    idx = MemoryIndex(dim=DIM, capacity=rows + 64,
                      edge_capacity=2 * edge_rows + 64, dtype=jnp.bfloat16,
                      int8_serving=True, telemetry=tel, telemetry_hbm=True)
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        emb = rng.standard_normal((m, DIM)).astype(np.float32)
        idx.add([f"f{c + i}" for i in range(m)], emb, [0.5] * m, [0.0] * m,
                ["semantic"] * m, ["default"] * m, "u0")
    fill_s = time.perf_counter() - t0
    # an edge band so the fused CSR gather and the classic neighbor walk
    # both do real work
    ne = min(edge_rows, rows - 1)
    idx.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)], "u0")
    nbr_map = {}
    for (s, t) in idx.edge_slots:
        nbr_map.setdefault(s, []).append(t)
        nbr_map.setdefault(t, []).append(s)
    queries = rng.standard_normal((B, DIM)).astype(np.float32)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=10,
                             gate_enabled=True, boost=True)
            for i in range(B)]
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)

    # measured dispatch counter over the fused-quant jit entry points
    quant_calls = {"n": 0}
    wrapped = {}
    for name in ("search_fused_quant_ragged",
                 "search_fused_quant_ragged_copy",
                 "search_fused_quant_ragged_read"):
        orig = getattr(S_mod, name)
        wrapped[name] = orig

        def counting(*a, __orig=orig, **k2):
            quant_calls["n"] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)

    def run_quant():
        return idx.search_fused_requests(reqs, **kw)

    def run_exact():
        idx.int8_serving = False
        try:
            return idx.search_fused_requests(reqs, **kw)
        finally:
            idx.int8_serving = True

    def run_classic():
        # gate search + int8 ANN search + access boost + neighbor boost =
        # 4 dispatches per batch (vs 1 fused)
        idx.search_batch(queries, "u0", k=1, super_filter=1, exact=True)
        per = idx.search_batch(queries, "u0", k=10, super_filter=-1)
        hit_ids = [i for ids_, _sc in per for i in ids_[:5]]
        idx.update_access(hit_ids, boost=0.05)
        retrieved = set(hit_ids)
        nbrs = {x for i in hit_ids for x in nbr_map.get(i, ())} - retrieved
        if nbrs:
            idx.boost(sorted(nbrs), 0.02)
        return per

    t0 = time.perf_counter()
    run_quant()                          # warm/compile + shadow build
    warm_quant_s = time.perf_counter() - t0
    run_exact()
    run_classic()
    quant_calls["n"] = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        run_quant()
    quant_ms = (time.perf_counter() - t0) * 1e3 / reps
    dispatches_per_turn = quant_calls["n"] / reps
    for name, orig in wrapped.items():
        setattr(S_mod, name, orig)
    t0 = time.perf_counter()
    for _ in range(reps):
        run_exact()
    exact_ms = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_classic()
    classic_ms = (time.perf_counter() - t0) * 1e3 / reps
    n_rows = idx.state.emb.shape[0]
    out = {
        "arena_rows": n_rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "edge_band": ne,
        "fill_s": round(fill_s, 1),
        "warm_quant_s": round(warm_quant_s, 1),
        "dispatches_per_turn": dispatches_per_turn,
        "fused_quant_retrieval_qps": round(B / (quant_ms / 1e3), 1),
        "fused_bf16_retrieval_qps": round(B / (exact_ms / 1e3), 1),
        "classic_int8_retrieval_qps": round(B / (classic_ms / 1e3), 1),
        "fused_quant_batch64_ms": round(quant_ms, 3),
        "fused_bf16_batch64_ms": round(exact_ms, 3),
        "classic_int8_batch64_ms": round(classic_ms, 3),
        "quant_vs_classic_speedup": round(classic_ms / quant_ms, 2),
        "quant_vs_bf16_speedup": round(exact_ms / quant_ms, 2),
        "telemetry": _telemetry_block(tel),
        "roofline": {
            # int8 coarse scan streams 1 byte/row-dim, bf16 streams 2
            "fused_quant_batch64": _roofline(n_rows, DIM, 1, quant_ms, B,
                                             on_tpu),
            "fused_bf16_batch64": _roofline(n_rows, DIM, 2, exact_ms, B,
                                            on_tpu),
        },
    }
    del idx
    return out


def bench_fused_ivf(on_tpu: bool, rows: int, reps: int = 3,
                    edge_rows: int = 100_000, recall_floor: float = 0.9,
                    nprobe_ladder=(4, 8, 16, 32)):
    """Fused IVF serving A/B (ISSUE 4 acceptance): batch-64 chat-turn
    retrieval through three paths over the SAME clustered bf16 arena —

      fused_ivf    : ONE ``search_fused_ivf_ragged`` dispatch (centroid
                     prefilter + member gather + exact candidate scan + gate/CSR/
                     boost tail, all in-kernel)
      classic_ivf  : the classic multi-dispatch IVF sequence (exact gate
                     search + ``_ivf_search`` prefilter scan + access/
                     neighbor boost scatters + host neighbor walk)
      fused_quant  : ONE ``search_fused_quant_ragged`` dispatch (dense int8
                     coarse scan + exact rescore — the PR 3 density
                     champion the IVF gather must beat at this scale)

    The corpus is clustered (spread-scaled noise around √N-ish centers —
    IVF recall on isotropic noise is meaningless) and queries are
    perturbed arena rows; recall@10 is measured against the EXACT master
    scan oracle, and ``nprobe`` walks a ladder until the fused path clears
    ``recall_floor``. The artifact records the measured
    ``dispatches_per_turn`` (jit-entry wrap) AND the recall/floor pair —
    scripts/check_dispatch_counts.py fails CI on either regressing."""
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    B = 64
    k = 10
    rng = np.random.default_rng(47)
    n_centers = max(64, 1 << int(np.sqrt(rows)).bit_length() >> 1)
    centers = rng.standard_normal((n_centers, DIM)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    spread = 0.5 / np.sqrt(DIM)
    tel = Telemetry()
    idx = MemoryIndex(dim=DIM, capacity=rows + 64,
                      edge_capacity=2 * edge_rows + 64, dtype=jnp.bfloat16,
                      ivf_nprobe=nprobe_ladder[0], telemetry=tel,
                      telemetry_hbm=True)
    q_rows = rng.integers(0, rows, size=B)
    q_base = np.zeros((B, DIM), np.float32)
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        lbl = rng.integers(0, n_centers, m)
        emb = centers[lbl] + spread * rng.standard_normal(
            (m, DIM)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        sel = (q_rows >= c) & (q_rows < c + m)
        q_base[sel] = emb[q_rows[sel] - c]
        idx.add([f"f{c + i}" for i in range(m)], emb, [0.5] * m, [0.0] * m,
                ["semantic"] * m, ["default"] * m, "u0")
    fill_s = time.perf_counter() - t0
    ne = min(edge_rows, rows - 1)
    idx.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)], "u0")
    nbr_map = {}
    for (s, t) in idx.edge_slots:
        nbr_map.setdefault(s, []).append(t)
        nbr_map.setdefault(t, []).append(s)
    t0 = time.perf_counter()
    assert idx.ivf_maintenance(iters=4)   # short refine: centroids only
    ivf_build_s = time.perf_counter() - t0   # steer the coarse routing

    queries = q_base + (0.3 / np.sqrt(DIM)) * rng.standard_normal(
        (B, DIM)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k,
                             gate_enabled=True, boost=True)
            for i in range(B)]
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)
    # exact oracle for recall@10, from the same master the kernels scan
    oracle = idx.search_batch(queries, "u0", k=k, exact=True)
    truth = [[idx.id_to_row[i] for i in ids_] for ids_, _ in oracle]

    def run_fused():
        return idx.search_fused_requests(reqs, **kw)

    def recall_of(res):
        hits = sum(len(set(idx.id_to_row[i] for i in r.ids) & set(t))
                   for r, t in zip(res, truth))
        return hits / (k * B)

    # nprobe ladder: smallest probe count that clears the recall floor
    # (each step recompiles — done before any timer starts)
    recall = 0.0
    recall_by_nprobe = {}
    for p in nprobe_ladder:
        idx.ivf_nprobe = p
        recall = recall_of(run_fused())
        recall_by_nprobe[p] = round(recall, 4)
        print(f"[bench] fused-ivf nprobe={p}: recall@10={recall:.3f}",
              file=sys.stderr, flush=True)
        if recall >= recall_floor:
            break
    nprobe = idx.ivf_nprobe

    def run_classic():
        # exact gate search + IVF prefilter ANN + access boost + neighbor
        # boost = 4 dispatches per batch (vs 1 fused)
        idx.search_batch(queries, "u0", k=1, super_filter=1, exact=True)
        per = idx.search_batch(queries, "u0", k=k, super_filter=-1)
        hit_ids = [i for ids_, _sc in per for i in ids_[:5]]
        idx.update_access(hit_ids, boost=0.05)
        retrieved = set(hit_ids)
        nbrs = {x for i in hit_ids for x in nbr_map.get(i, ())} - retrieved
        if nbrs:
            idx.boost(sorted(nbrs), 0.02)
        return per

    def run_quant():
        # PR 3's dense two-stage path over the same arena (IVF sidelined,
        # int8 shadow on) — the fused-quant comparator
        idx.ivf_nprobe = 0
        idx.int8_serving = True
        try:
            return idx.search_fused_requests(reqs, **kw)
        finally:
            idx.int8_serving = False
            idx.ivf_nprobe = nprobe

    # measured dispatch counter over the fused-ivf jit entry points
    ivf_calls = {"n": 0}
    wrapped = {}
    for name in ("search_fused_ivf_ragged", "search_fused_ivf_ragged_copy",
                 "search_fused_ivf_ragged_read"):
        orig = getattr(S_mod, name)
        wrapped[name] = orig

        def counting(*a, __orig=orig, **k2):
            ivf_calls["n"] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)

    run_fused()                          # warm (already compiled above)
    t0 = time.perf_counter()
    run_quant()                          # warm/compile + shadow build
    warm_quant_s = time.perf_counter() - t0
    run_classic()
    ivf_calls["n"] = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        res = run_fused()
    fused_ms = (time.perf_counter() - t0) * 1e3 / reps
    dispatches_per_turn = ivf_calls["n"] / reps
    recall_measured = recall_of(res)
    for name, orig in wrapped.items():
        setattr(S_mod, name, orig)
    t0 = time.perf_counter()
    for _ in range(reps):
        run_classic()
    classic_ms = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_quant()
    quant_ms = (time.perf_counter() - t0) * 1e3 / reps
    n_rows = idx.state.emb.shape[0]
    ivf = idx._ivf
    tabs = idx._ivf_fused_pack(k)
    cand_rows = (tabs[3] * tabs[1].shape[1] + tabs[2].shape[0]
                 if tabs is not None else n_rows)
    out = {
        "arena_rows": n_rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "edge_band": ne,
        "n_centers": n_centers,
        "fill_s": round(fill_s, 1),
        "ivf_build_s": round(ivf_build_s, 1),
        "warm_quant_s": round(warm_quant_s, 1),
        "nprobe": nprobe,
        "n_clusters": ivf.n_clusters if ivf is not None else None,
        "candidate_rows_per_query": int(cand_rows),
        "recall_by_nprobe": recall_by_nprobe,
        "recall_at_10": round(recall_measured, 4),
        "recall_floor": recall_floor,
        "dispatches_per_turn": dispatches_per_turn,
        "fused_ivf_retrieval_qps": round(B / (fused_ms / 1e3), 1),
        "classic_ivf_retrieval_qps": round(B / (classic_ms / 1e3), 1),
        "fused_quant_retrieval_qps": round(B / (quant_ms / 1e3), 1),
        "fused_ivf_batch64_ms": round(fused_ms, 3),
        "classic_ivf_batch64_ms": round(classic_ms, 3),
        "fused_quant_batch64_ms": round(quant_ms, 3),
        "ivf_vs_classic_speedup": round(classic_ms / fused_ms, 2),
        "ivf_vs_fused_quant_speedup": round(quant_ms / fused_ms, 2),
        "telemetry": _telemetry_block(tel),
        "roofline": {
            # the IVF win is structural: candidate bytes per query vs the
            # dense scans' whole-arena stream
            "fused_ivf_batch64": _roofline(int(cand_rows), DIM, 2, fused_ms,
                                           B, on_tpu),
            "fused_quant_batch64": _roofline(n_rows, DIM, 1, quant_ms, B,
                                             on_tpu),
        },
    }
    del idx
    return out


def bench_online_ivf(on_tpu: bool, rows: int, rounds: int = 6,
                     batch: int = 256, serve_b: int = 16,
                     staleness_max: float = 0.02):
    """Online IVF acceptance stage (ISSUE 12): sustained clustered churn
    through the fused ingest dispatch with in-kernel IVF maintenance,
    A/B'd against the offline-rebuild world it replaces —

      online   : every ingest batch scores against the centroids, appends
                 to the member tables and blends the mini-batch centroid
                 step INSIDE the one dispatch; ``ivf_maintenance`` never
                 rebuilds (measured ``dispatches_per_conversation`` == 1)
      baseline : ``ivf_online=off`` — fresh rows pile into the exact-scan
                 residual and a stop-the-world ``build_ivf`` re-clusters
                 the arena on the classic 25% trigger

    A background thread serves fixed-cadence chat turns against the same
    device THROUGHOUT both churn runs, so the baseline's k-means pause
    shows up where it hurts: serving p99. The stage also measures the
    ingest-overhead fraction of the in-dispatch maintenance (online vs
    maintenance-free ingest over the same stream), the final
    ``assignment_staleness_fraction`` (online tables probed against their
    own current centroids — gated ≤ ``staleness_max``), and recall@10 of
    the online tables vs a from-scratch offline rebuild over the final
    corpus. ``scripts/check_dispatch_counts.py`` gates the artifact
    (``"ivf_online": true``): measured dispatches_per_conversation == 1,
    recall ≥ floor, staleness ≤ 0.02."""
    import threading

    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.ops.ivf import build_ivf
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    k = 10
    rng = np.random.default_rng(12)
    n_centers = max(64, 1 << (int(np.sqrt(rows)).bit_length() - 1))
    centers = rng.standard_normal((n_centers, DIM)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    spread = 0.5 / np.sqrt(DIM)

    def corpus_fill(idx, tag):
        for c in range(0, rows, 65_536):
            m = min(65_536, rows - c)
            lbl = rng.integers(0, n_centers, m)
            emb = centers[lbl] + spread * rng.standard_normal(
                (m, DIM)).astype(np.float32)
            emb /= np.linalg.norm(emb, axis=1, keepdims=True)
            idx.add([f"{tag}{c + i}" for i in range(m)], emb, [0.5] * m,
                    [0.0] * m, ["semantic"] * m, ["default"] * m, "u0")

    def churn_batches(seed):
        """The same drifting clustered fact stream for every arm."""
        r2 = np.random.default_rng(seed)
        cent = centers.copy()
        out = []
        for _ in range(rounds):
            cent = cent + 0.02 * r2.standard_normal(cent.shape)
            cent /= np.linalg.norm(cent, axis=1, keepdims=True)
            lbl = r2.integers(0, n_centers, batch)
            emb = cent[lbl] + spread * r2.standard_normal(
                (batch, DIM)).astype(np.float32)
            out.append((emb / np.linalg.norm(emb, axis=1,
                                             keepdims=True)).astype(
                np.float32))
        return out

    def make_index(online, tag, tel, hbm=False):
        # hbm=True AOT-records the ingest kernel's peak-HBM gauge with
        # the ivf="true" label — the calibration point the ivf-aware
        # ingest cost model (plan/model.py) is swept against in CI
        idx = MemoryIndex(dim=DIM, capacity=rows + (rounds + 1) * batch
                          + 64,
                          edge_capacity=4 * (rounds + 1) * batch + 1024,
                          dtype=jnp.bfloat16, ivf_nprobe=4,
                          ivf_online=online, telemetry=tel,
                          telemetry_hbm=hbm)
        corpus_fill(idx, tag)
        assert idx.ivf_maintenance(iters=4)
        return idx

    def ingest_round(idx, emb, prefix):
        n = len(emb)
        pending = idx.ingest_batch_dedup(
            emb, [0.5] * n, [1.0] * n, ["semantic"] * n, ["default"] * n,
            "u0", dedup_gate=1.01)
        idx.commit_ingest_dedup(pending,
                                [f"{prefix}{i}" for i in range(n)])

    def churn_run(idx, label, force_rebuild):
        """Drive the churn stream while a serving thread hammers chat
        turns at a fixed cadence; returns (per-turn latencies ms,
        ingest wall s, rebuilds, max rebuild pause s)."""
        q = centers[rng.integers(0, n_centers, serve_b)] \
            + spread * rng.standard_normal((serve_b, DIM)).astype(
                np.float32)
        reqs = [RetrievalRequest(query=q[i], tenant="u0", k=k)
                for i in range(serve_b)]
        kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
                  nbr_boost=0.02)
        idx.search_fused_requests(reqs, **kw)      # warm the serve kernel
        lat, stop = [], threading.Event()

        def serve_loop():
            while not stop.is_set():
                t0 = time.perf_counter()
                idx.search_fused_requests(reqs, **kw)
                lat.append((time.perf_counter() - t0) * 1e3)
                stop.wait(0.05)

        # warm the ingest kernel variant OUTSIDE the timers: the overhead
        # fraction must compare steady-state dispatches, not who paid the
        # one-time XLA compile of their (with/without-IVF) program
        warm = churn_batches(7)[0]
        ingest_round(idx, warm, f"{label}warm_")
        th = threading.Thread(target=serve_loop, daemon=True)
        th.start()
        rebuilds, pause_max = 0, 0.0
        t_ing = 0.0
        for r, emb in enumerate(churn_batches(99)):
            t0 = time.perf_counter()
            ingest_round(idx, emb, f"{label}r{r}_")
            t_ing += time.perf_counter() - t0
            # maintenance runs every round in BOTH arms: online it must
            # be a no-op (assignments already live in the tables); the
            # classic arm gets the 25% trigger forced every other round
            # so the pause is measured at bench scale, not dodged by a
            # small stream
            if force_rebuild and r % 2 == 1:
                idx._ivf_stale = 10 ** 9
            t0 = time.perf_counter()
            if idx.ivf_maintenance(iters=4):
                rebuilds += 1
                pause_max = max(pause_max, time.perf_counter() - t0)
        stop.set()
        th.join(timeout=10)
        return lat, t_ing, rebuilds, pause_max

    # ---- online arm -----------------------------------------------------
    tel = Telemetry()
    idx = make_index(True, "f", tel, hbm=True)
    before = idx.ingest_dispatch_count
    on_lat, on_ing_s, on_rebuilds, _ = churn_run(idx, "on", False)
    # rounds + the warm batch: every conversation through the path,
    # including the untimed one, must have cost exactly one dispatch
    dispatches_per_conversation = (idx.ingest_dispatch_count
                                   - before) / (rounds + 1)
    staleness = idx.ivf_staleness_probe()
    occupancy = float(idx._ivf_dev[2].sum()) / max(
        1, int(np.prod(idx._ivf_dev[1].shape)))

    # recall: online tables vs a from-scratch offline rebuild on the SAME
    # final corpus (the acceptance comparison)
    qn = centers[rng.integers(0, n_centers, 64)] \
        + spread * rng.standard_normal((64, DIM)).astype(np.float32)
    qn /= np.linalg.norm(qn, axis=1, keepdims=True)
    truth = [set(ids) for ids, _ in
             idx.search_batch(qn, "u0", k=k, exact=True)]

    def recall_now():
        got = idx.search_batch(qn, "u0", k=k)
        return sum(len(set(ids[:k]) & t) for (ids, _), t
                   in zip(got, truth)) / (k * len(qn))

    recall_online = recall_now()
    t0 = time.perf_counter()
    idx._ivf = build_ivf(idx.state.emb, np.asarray(idx.state.alive),
                         iters=4)
    offline_rebuild_s = time.perf_counter() - t0
    recall_offline = recall_now()
    del idx

    # ---- maintenance-free ingest (overhead denominator) -----------------
    idx0 = make_index(True, "g", Telemetry())
    idx0.ivf_online = False
    idx0._ivf_dev = None        # same stream, zero in-dispatch maintenance
    _, off_ing_s, _, _ = churn_run(idx0, "off", False)
    del idx0

    # ---- rebuild-pause baseline arm -------------------------------------
    idx2 = make_index(False, "h", Telemetry())
    base_lat, base_ing_s, base_rebuilds, pause_max = churn_run(
        idx2, "base", True)
    del idx2

    def pct(xs, p):
        return (round(float(np.percentile(xs, p)), 2) if xs else None)

    n_facts = rounds * batch
    overhead = (on_ing_s - off_ing_s) / max(off_ing_s, 1e-9)
    recall_floor = round(max(0.5, recall_offline - 0.05), 4)
    return {
        "ivf_online": True,
        "arena_rows": rows,
        "dim": DIM,
        "rounds": rounds,
        "batch": batch,
        "n_centers": n_centers,
        "dispatches_per_conversation": dispatches_per_conversation,
        "online_rebuilds_during_churn": on_rebuilds,
        "baseline_rebuilds_during_churn": base_rebuilds,
        "baseline_rebuild_pause_max_s": round(pause_max, 2),
        "offline_rebuild_s": round(offline_rebuild_s, 2),
        "online_ingest_memories_per_sec": round(n_facts / on_ing_s, 1),
        "plain_ingest_memories_per_sec": round(n_facts / off_ing_s, 1),
        "ingest_overhead_fraction": round(max(0.0, overhead), 4),
        "serving_p50_ms_during_churn": pct(on_lat, 50),
        "serving_p99_ms_during_churn": pct(on_lat, 99),
        "baseline_serving_p50_ms": pct(base_lat, 50),
        "baseline_serving_p99_ms": pct(base_lat, 99),
        "serving_turns_online": len(on_lat),
        "serving_turns_baseline": len(base_lat),
        "assignment_staleness_fraction": round(float(staleness), 4),
        "assignment_staleness_max": staleness_max,
        "member_pool_occupancy": round(occupancy, 4),
        "recall_at_10": round(recall_online, 4),
        "recall_offline_rebuild": round(recall_offline, 4),
        "recall_floor": recall_floor,
        "telemetry": _telemetry_block(tel),
    }


def bench_fused_pq(on_tpu: bool, rows: int, reps: int = 10,
                   edge_rows: int = 2048, nprobe_ladder=(4, 8, 16, 32),
                   recall_floor: float = 0.97, ingest_convs: int = 4,
                   coarse_slack: int = 512):
    """Fused IVF-PQ serving A/B (ISSUE 16) on one clustered arena:

      fused_pq     : ONE ``search_fused_pq_ragged`` dispatch (per-query ADC
                     table + m-byte member scan over the top-nprobe clusters +
                     exact f32 shortlist rescore + gate/CSR/boost tail,
                     all in-kernel)
      classic_pq   : the classic multi-dispatch PQ sequence this PR
                     retires from the serving path (exact gate search +
                     ``ivf_pq_search`` prefilter + access/neighbor boost
                     scatters + host neighbor walk)
      fused_quant  : the dense int8 two-stage comparator (PR 3) — the
                     footprint PQ's m bytes/row undercuts 8×

    ``recall_at_10`` holds the fused path to the EXACT master-scan
    oracle (floor 0.97); ``classic_recall_at_10`` holds the classic
    ``ivf_pq_search`` comparator to the SAME oracle on the SAME fixture,
    so the artifact shows fused recall ≥ classic recall directly
    (``recall_vs_classic_top10`` records the raw top-10 overlap too).
    ``coarse_slack`` is the load-bearing recall knob here, NOT nprobe:
    the clustered fixture packs each query's true top-10 into one tight
    ~512-row cluster whose cosine gaps sit below the u8 ADC ranking
    noise, so the m-byte coarse order scrambles within the cluster and
    the exact f32 rescore must reach ``k + coarse_slack`` deep to
    recover the floor — exactly the trade the serving knob exists for.
    The stage then drives ``ingest_convs`` fused-ingest conversations
    with the pack live and records ``dispatches_per_conversation`` — the
    in-kernel ``_pq_scatter`` must keep the codes current at ZERO added
    dispatches (verified bit-exact against a host re-encode).
    ``scripts/check_dispatch_counts.py`` gates the artifact
    (``"pq_fused": true``): dispatches_per_turn == 1, recall ≥ floor,
    ``bytes_per_row`` recorded and below ``int8_bytes_per_row``;
    ``scripts/check_hbm_budget.py`` sweeps the ``pq="true"`` peak-HBM
    gauge labels the serve/ingest compiles record."""
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.ops.pq import encode_pq
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    B = 64
    k = 10
    rng = np.random.default_rng(61)
    n_centers = max(64, 1 << int(np.sqrt(rows)).bit_length() >> 1)
    centers = rng.standard_normal((n_centers, DIM)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    spread = 0.5 / np.sqrt(DIM)
    tel = Telemetry()
    idx = MemoryIndex(dim=DIM, capacity=rows + ingest_convs * B + 64,
                      edge_capacity=2 * edge_rows + 64, dtype=jnp.bfloat16,
                      ivf_nprobe=nprobe_ladder[0], pq_serving=True,
                      coarse_slack=coarse_slack, telemetry=tel,
                      telemetry_hbm=True)
    q_rows = rng.integers(0, rows, size=B)
    q_base = np.zeros((B, DIM), np.float32)
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        lbl = rng.integers(0, n_centers, m)
        emb = centers[lbl] + spread * rng.standard_normal(
            (m, DIM)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        sel = (q_rows >= c) & (q_rows < c + m)
        q_base[sel] = emb[q_rows[sel] - c]
        idx.add([f"f{c + i}" for i in range(m)], emb, [0.5] * m, [0.0] * m,
                ["semantic"] * m, ["default"] * m, "u0")
    fill_s = time.perf_counter() - t0
    ne = min(edge_rows, rows - 1)
    idx.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)], "u0")
    nbr_map = {}
    for (s, t) in idx.edge_slots:
        nbr_map.setdefault(s, []).append(t)
        nbr_map.setdefault(t, []).append(s)
    t0 = time.perf_counter()
    assert idx.ivf_maintenance(iters=4)  # coarse build + codebook train +
    build_s = time.perf_counter() - t0   # the ONE full encode (publish)
    pack = idx._pq_pack
    assert pack is not None and pack[1] is not None
    m_sub = int(pack[1].shape[1])

    queries = q_base + (0.3 / np.sqrt(DIM)) * rng.standard_normal(
        (B, DIM)).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=k,
                             gate_enabled=True, boost=True)
            for i in range(B)]
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)
    oracle = idx.search_batch(queries, "u0", k=k, exact=True)
    truth_exact = [[idx.id_to_row[i] for i in ids_] for ids_, _ in oracle]

    def run_fused():
        return idx.search_fused_requests(reqs, **kw)

    def classic_topk():
        # the classic IVF-PQ prefilter the fused path replaces
        return idx.search_batch(queries, "u0", k=k, super_filter=-1)

    def run_classic():
        # exact gate search + PQ prefilter ANN + access boost + neighbor
        # boost = 4 dispatches per batch (vs 1 fused)
        idx.search_batch(queries, "u0", k=1, super_filter=1, exact=True)
        per = classic_topk()
        hit_ids = [i for ids_, _sc in per for i in ids_[:5]]
        idx.update_access(hit_ids, boost=0.05)
        retrieved = set(hit_ids)
        nbrs = {x for i in hit_ids for x in nbr_map.get(i, ())} - retrieved
        if nbrs:
            idx.boost(sorted(nbrs), 0.02)
        return per

    def run_quant():
        # PR 3's dense int8 two-stage comparator (PQ sidelined)
        idx.pq_serving = False
        idx.ivf_nprobe = 0
        idx.int8_serving = True
        try:
            return idx.search_fused_requests(reqs, **kw)
        finally:
            idx.int8_serving = False
            idx.ivf_nprobe = nprobe
            idx.pq_serving = True

    def recall_vs(res_rows, truth):
        hits = sum(len(set(r) & set(t)) for r, t in zip(res_rows, truth))
        return hits / (k * B)

    def fused_rows_of(res):
        return [[idx.id_to_row[i] for i in r.ids] for r in res]

    # nprobe ladder: smallest probe count where the fused path clears the
    # recall floor against the EXACT master-scan oracle (each step
    # recompiles — done before any timer starts). The classic
    # ``ivf_pq_search`` comparator is held to the same oracle below, so
    # the artifact shows fused recall ≥ classic recall on one fixture.
    recall = 0.0
    recall_by_nprobe = {}
    for p in nprobe_ladder:
        idx.ivf_nprobe = p
        recall = recall_vs(fused_rows_of(run_fused()), truth_exact)
        recall_by_nprobe[p] = round(recall, 4)
        print(f"[bench] fused-pq nprobe={p}: recall@10={recall:.3f}",
              file=sys.stderr, flush=True)
        if recall >= recall_floor:
            break
    nprobe = idx.ivf_nprobe

    # measured dispatch counter over the fused-pq jit entry points
    pq_calls = {"n": 0}
    wrapped = {}
    for name in ("search_fused_pq_ragged",
                 "search_fused_pq_ragged_copy",
                 "search_fused_pq_ragged_read"):
        orig = getattr(S_mod, name)
        wrapped[name] = orig

        def counting(*a, __orig=orig, **k2):
            pq_calls["n"] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)

    run_fused()                          # warm (already compiled above)
    t0 = time.perf_counter()
    run_quant()                          # warm/compile + shadow build
    warm_quant_s = time.perf_counter() - t0
    run_classic()
    pq_calls["n"] = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        res = run_fused()
    fused_ms = (time.perf_counter() - t0) * 1e3 / reps
    dispatches_per_turn = pq_calls["n"] / reps
    for name, orig in wrapped.items():
        setattr(S_mod, name, orig)
    fused_rows = fused_rows_of(res)
    classic_res = classic_topk()
    classic_rows = [[idx.id_to_row[i] for i in ids_]
                    for ids_, _ in classic_res]
    recall_measured = recall_vs(fused_rows, truth_exact)
    classic_recall = recall_vs(classic_rows, truth_exact)
    t0 = time.perf_counter()
    for _ in range(reps):
        run_classic()
    classic_ms = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_quant()
    quant_ms = (time.perf_counter() - t0) * 1e3 / reps

    # ---- incremental codes: ingest conversations with the pack live ----
    before = idx.ingest_dispatch_count
    new_ids = []
    for conv in range(ingest_convs):
        lbl = rng.integers(0, n_centers, B)
        emb = centers[lbl] + spread * rng.standard_normal(
            (B, DIM)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        pending = idx.ingest_batch_dedup(
            emb.astype(np.float32), [0.5] * B, [1.0] * B,
            ["semantic"] * B, ["default"] * B, "u0", dedup_gate=1.01)
        ids = [f"w{conv}_{i}" for i in range(B)]
        idx.commit_ingest_dedup(pending, ids)
        new_ids.extend(ids)
    dispatches_per_conversation = (idx.ingest_dispatch_count
                                   - before) / ingest_convs
    pack = idx._pq_pack
    codes_complete = pack is not None and pack[1] is not None
    new_rows = np.asarray([idx.id_to_row[i] for i in new_ids])
    want = np.asarray(encode_pq(pack[0].centroids, idx.state.emb[new_rows]))
    codes_exact = bool(np.array_equal(np.asarray(pack[1])[new_rows], want))

    n_rows = idx.state.emb.shape[0]
    tabs = idx._pq_fused_pack(k)
    cand_rows = (tabs[3] * tabs[1].shape[1] + tabs[2].shape[0]
                 if tabs is not None else n_rows)
    # peak-HBM gauges for the footprint headline: the pq="true"-labeled
    # serve geometry vs the int8 comparator's quant geometry
    gauges = tel.snapshot()["gauges"]
    peak_pq = max((v for g_, v in gauges.items()
                   if g_.startswith("kernel.peak_hbm_bytes")
                   and 'pq="true"' in g_), default=None)
    peak_quant = max((v for g_, v in gauges.items()
                      if g_.startswith("kernel.peak_hbm_bytes")
                      and 'mode="quant"' in g_), default=None)
    out = {
        "pq_fused": True,
        "arena_rows": n_rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "edge_band": ne,
        "n_centers": n_centers,
        "fill_s": round(fill_s, 1),
        "build_s": round(build_s, 1),
        "warm_quant_s": round(warm_quant_s, 1),
        "nprobe": nprobe,
        "coarse_slack": coarse_slack,
        "m_subquantizers": m_sub,
        "bytes_per_row": m_sub,                   # u8 codes, m bytes
        "int8_bytes_per_row": DIM + 4,            # codes + f32 scale
        "candidate_rows_per_query": int(cand_rows),
        "recall_by_nprobe": recall_by_nprobe,
        "recall_at_10": round(recall_measured, 4),
        "recall_floor": recall_floor,
        "classic_recall_at_10": round(classic_recall, 4),
        "recall_vs_classic_top10": round(
            recall_vs(fused_rows, classic_rows), 4),
        "dispatches_per_turn": dispatches_per_turn,
        "dispatches_per_conversation": dispatches_per_conversation,
        "incremental_codes": {"complete": codes_complete,
                              "bit_exact": codes_exact},
        "fused_pq_retrieval_qps": round(B / (fused_ms / 1e3), 1),
        "classic_pq_retrieval_qps": round(B / (classic_ms / 1e3), 1),
        "fused_quant_retrieval_qps": round(B / (quant_ms / 1e3), 1),
        "fused_pq_batch64_ms": round(fused_ms, 3),
        "classic_pq_batch64_ms": round(classic_ms, 3),
        "fused_quant_batch64_ms": round(quant_ms, 3),
        "fused_vs_classic_speedup": round(classic_ms / fused_ms, 2),
        "speedup_floor": 2.0,
        "pq_vs_fused_quant_speedup": round(quant_ms / fused_ms, 2),
        "peak_hbm_pq_bytes": peak_pq,
        "peak_hbm_quant_bytes": peak_quant,
        "telemetry": _telemetry_block(tel),
        "roofline": {
            # the PQ win is structural: m bytes per candidate row vs the
            # int8 shadow's full-dim codes over the whole arena
            "fused_pq_batch64": _roofline(int(cand_rows),
                                          m_sub, 1, fused_ms, B, on_tpu),
            "fused_quant_batch64": _roofline(n_rows, DIM, 1, quant_ms, B,
                                             on_tpu),
        },
    }
    del idx
    return out


def bench_fused_sharded(on_tpu: bool, rows: int, reps: int = 3,
                        n_parts: int = 4, edge_rows: int = 100_000,
                        recall_floor: float = 0.99,
                        speedup_floor: float = 1.5):
    """Pod-scale fused serving A/B (ISSUE 5 acceptance): batch-64 chat-turn
    retrieval over a ``n_parts``-way host-device mesh through three paths —

      fused_sharded  : ONE distributed shard_map dispatch running the FULL
                       chat-turn program (gate + ANN + CSR gather +
                       shard-local boost scatters;
                       ``ShardedMemoryIndex.serve_requests``)
      classic_sharded: the semantics-EQUIVALENT multi-dispatch pod
                       sequence the old path needed for a chat turn — a
                       ``make_sharded_multitenant_topk`` dispatch per
                       retrieval tier (super gate + main ANN: the arena
                       streams from HBM twice) + access-boost and
                       neighbor-boost scatter dispatches with the host
                       neighbor walk between them
      plain_topk     : the OLD pod ``serve_requests`` body — one
                       multitenant top-k dispatch that silently DROPPED
                       the gate/neighbor/boost semantics (recorded for
                       honesty: it does strictly less work)

    plus the single-chip fused path over the same data on one device
    (the pod-vs-chip scaling datapoint). ``dispatches_per_turn`` is
    MEASURED by counting the index's ``_dispatch`` entries per serve, and
    recall@10 of the fused-sharded results is scored against the classic
    multitenant top-k oracle (both exact → floor 0.99 guards the merge)."""
    import jax as _jax
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh
    from lazzaro_tpu.serve import RetrievalRequest

    n_dev = len(_jax.devices())
    if n_dev < n_parts:
        print(f"[bench] fused-sharded: only {n_dev} devices (wanted "
              f"{n_parts}); set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={n_parts} for the "
              f"CPU mesh", file=sys.stderr, flush=True)
        n_parts = max(1, n_dev)
    mesh = make_mesh(("data",), (n_parts,),
                     devices=_jax.devices()[:n_parts])
    B = 64
    rng = np.random.default_rng(41)
    from lazzaro_tpu.utils.telemetry import Telemetry
    tel = Telemetry()
    idx = ShardedMemoryIndex(mesh, dim=DIM, capacity=rows + 64,
                             dtype=jnp.bfloat16, k=10, cap_take=5,
                             max_nbr=16, telemetry=tel,
                             telemetry_hbm=True)
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        emb = rng.standard_normal((m, DIM)).astype(np.float32)
        idx.add([f"f{c + i}" for i in range(m)], emb, "u0")
    fill_s = time.perf_counter() - t0
    ne = min(edge_rows, rows - 1)
    idx.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)])
    nbr_map = {}
    for (s, t) in idx.edges:
        nbr_map.setdefault(s, []).append(t)
        nbr_map.setdefault(t, []).append(s)
    queries = rng.standard_normal((B, DIM)).astype(np.float32)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=10,
                             gate_enabled=True, boost=True)
            for i in range(B)]
    read_reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=10)
                 for i in range(B)]

    # classic pod kernels: one multitenant top-k dispatch per retrieval
    # tier (the old path had no super column in the kernel, so the gate
    # tier re-streams the arena with a super-masked alive column)
    from lazzaro_tpu.ops.topk import make_sharded_multitenant_topk
    classic_kern = make_sharded_multitenant_topk(mesh, "data", k=16)
    st0 = idx.state
    tid = np.full((B,), idx._tenants["u0"], np.int32)
    qn = queries / np.maximum(
        np.linalg.norm(queries, axis=1, keepdims=True), 1e-9)
    qn_dev = jnp.asarray(qn)
    tid_dev = jnp.asarray(tid)
    sup_alive = st0.alive & st0.is_super      # gate-tier mask column
    main_alive = st0.alive & ~st0.is_super
    # a live snapshot would trip the donation gate and force the copying
    # kernels on BOTH sides of the A/B (boost scatters don't touch these
    # mask sources, so the derived columns stay valid)
    del st0

    def run_fused():
        return idx.serve_requests(reqs)

    def run_plain():
        idx.serve_fused = False
        try:
            return idx.serve_requests(read_reqs)
        finally:
            idx.serve_fused = True

    def run_classic():
        st = idx.state
        idx._dispatch(classic_kern, st.emb, sup_alive, st.tenant_id,
                      qn_dev, tid_dev)                       # gate tier
        scores, rows_d = idx._dispatch(classic_kern, st.emb, main_alive,
                                       st.tenant_id, qn_dev, tid_dev)
        del st          # let the boost scatters take the donated twins
        from lazzaro_tpu.utils.batching import decode_topk
        per = decode_topk(np.asarray(scores), np.asarray(rows_d),
                          idx.row_to_id, -1e30, limit=10)
        hit_ids = [i for ids_, _sc in per for i in ids_[:5]]
        hit_rows = np.asarray([idx.id_to_row[i] for i in hit_ids], np.int32)
        now_rel = time.time() - idx.epoch
        idx._apply_arena(S_mod.arena_update_access,
                         S_mod.arena_update_access_copy,
                         jnp.asarray(S_mod.pad_rows(hit_rows, idx.capacity)),
                         jnp.float32(now_rel), jnp.float32(0.05))
        retrieved = set(hit_ids)
        nbrs = sorted({x for i in hit_ids for x in nbr_map.get(i, ())}
                      - retrieved)
        if nbrs:
            nrows = np.asarray([idx.id_to_row[i] for i in nbrs], np.int32)
            idx._apply_arena(S_mod.arena_boost, S_mod.arena_boost_copy,
                             jnp.asarray(S_mod.pad_rows(nrows, idx.capacity)),
                             jnp.float32(now_rel), jnp.float32(0.02))
        return per

    t0 = time.perf_counter()
    fused_res = run_fused()                   # warm/compile
    warm_s = time.perf_counter() - t0
    oracle = run_plain()
    run_classic()
    # recall@10 of the fused pod results vs the classic multitenant top-k
    hits = total = 0
    for r_f, r_o in zip(fused_res, oracle):
        want = set(r_o.ids[:10])
        total += len(want)
        hits += len(want & set(r_f.ids[:10]))
    recall = hits / max(total, 1)

    calls = {"n": 0}
    orig_dispatch = idx._dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig_dispatch(fn, *a, **kw)

    idx._dispatch = counting
    t0 = time.perf_counter()
    for _ in range(reps):
        run_fused()
    fused_ms = (time.perf_counter() - t0) * 1e3 / reps
    dispatches_per_turn = calls["n"] / reps
    idx._dispatch = orig_dispatch
    t0 = time.perf_counter()
    for _ in range(reps):
        run_classic()
    classic_ms = (time.perf_counter() - t0) * 1e3 / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run_plain()
    plain_ms = (time.perf_counter() - t0) * 1e3 / reps

    # single-chip fused over the same corpus on ONE device (the
    # pod-vs-chip scaling datapoint; same kernel family, no mesh)
    rng2 = np.random.default_rng(41)
    chip = MemoryIndex(dim=DIM, capacity=rows + 64,
                       edge_capacity=2 * ne + 64, dtype=jnp.bfloat16,
                       telemetry=Telemetry())   # keep the pod block clean
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        emb = rng2.standard_normal((m, DIM)).astype(np.float32)
        chip.add([f"f{c + i}" for i in range(m)], emb, [0.5] * m, [0.0] * m,
                 ["semantic"] * m, ["default"] * m, "u0")
    chip.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)], "u0")
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)
    chip.search_fused_requests(reqs, **kw)    # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        chip.search_fused_requests(reqs, **kw)
    chip_ms = (time.perf_counter() - t0) * 1e3 / reps
    del chip

    n_rows = rows
    out = {
        "mesh": {"n_parts": n_parts, "axis": "data",
                 "rows_per_chip": (idx.capacity + 1) // n_parts},
        "arena_rows": n_rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "edge_band": ne,
        "fill_s": round(fill_s, 1),
        "warm_s": round(warm_s, 1),
        "dispatches_per_turn": dispatches_per_turn,
        "recall_at_10": round(recall, 4),
        "recall_floor": recall_floor,
        "speedup_floor": speedup_floor,
        "fused_sharded_retrieval_qps": round(B / (fused_ms / 1e3), 1),
        "classic_sharded_retrieval_qps": round(B / (classic_ms / 1e3), 1),
        "plain_topk_retrieval_qps": round(B / (plain_ms / 1e3), 1),
        "single_chip_fused_qps": round(B / (chip_ms / 1e3), 1),
        "fused_sharded_batch64_ms": round(fused_ms, 3),
        "classic_sharded_batch64_ms": round(classic_ms, 3),
        "plain_topk_batch64_ms": round(plain_ms, 3),
        "single_chip_fused_batch64_ms": round(chip_ms, 3),
        "fused_vs_classic_speedup": round(classic_ms / fused_ms, 2),
        "fused_vs_plain_ratio": round(plain_ms / fused_ms, 2),
        "sharded_vs_single_chip_speedup": round(chip_ms / fused_ms, 2),
        "telemetry": _telemetry_block(tel),
        "roofline": {
            # aggregate HBM across the pod: one batch streams the whole
            # arena once (fused) vs twice (classic's two tiers)
            "fused_sharded_batch64": _roofline(n_rows, DIM, 2, fused_ms,
                                               B, on_tpu),
            "classic_sharded_batch64": _roofline(2 * n_rows, DIM, 2,
                                                 classic_ms, B, on_tpu),
        },
    }
    del idx
    return out


def bench_replica_serving(on_tpu: bool, rows: int, reps: int = 16,
                          group_counts=(1, 2, 4), dim: int = None,
                          recall_floor: float = 0.97,
                          qps_scaling_floor: float = 2.5,
                          staleness_bound_s: float = 5.0):
    """Replica-group serving acceptance (ISSUE 18): aggregate QPS vs
    group count on the SAME device fleet, with freshness floors.

    For each G in ``group_counts`` the fleet is partitioned into G
    replica groups (``ReplicaPlacement``), each holding a FULL copy of
    the corpus row-sharded over ``chips/G`` devices, and the rig drives
    routed batch-64 turns through ``ReplicaPlacement.serve`` —
    tenant-affine/least-loaded routing, ONE group-local dispatch + ONE
    packed readback per turn (MEASURED by counting every group's
    ``_dispatch`` entries). Aggregate QPS = routed turns served per
    wall-second; ``qps_scaling`` = aggregate at max(G) over the 1-group
    baseline. The rig is a single host, so the measured scaling is the
    latency-bound regime's: a group-local turn pays the dispatch fan-out
    + ``sharded_topk_merge`` of chips/G devices instead of the whole
    fleet (on a real pod the groups ALSO overlap across hosts — the rig
    number is the conservative floor). ``dim`` defaults to
    min(BENCH_DIM, 128) to stay in that regime: at CPU-compute-bound
    sizes the one-core rig serializes all groups and measures its own
    matmul throughput, not the placement.

    Freshness cells (largest G): recall@10 of routed turns vs the exact
    numpy oracle; a deferred-replication write burst whose measured
    ``staleness()`` window must close under ``staleness_bound_s``
    (mirrors config ``serve_replica_staleness_s``); an overlay tenant
    whose rows exist ONLY on its home group; and a crash injected
    mid-replay (``replica.mid_replay``) that must recover by journal
    catch-up with zero lost and zero double-ingested facts."""
    import jax as _jax
    from lazzaro_tpu.parallel.replica import ReplicaPlacement
    from lazzaro_tpu.reliability import faults as _faults
    from lazzaro_tpu.reliability.faults import InjectedFault
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    dim_ = dim or min(DIM, 128)
    n_dev = len(_jax.devices())
    counts = [g for g in group_counts if g <= n_dev and n_dev % g == 0]
    if counts != list(group_counts):
        print(f"[bench] replica: {n_dev} devices support groups {counts} "
              f"(wanted {list(group_counts)}); set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 for the CPU "
              f"mesh", file=sys.stderr, flush=True)
    B = 64
    rng = np.random.default_rng(53)
    emb = rng.standard_normal((rows, dim_)).astype(np.float32)
    ids = [f"f{i}" for i in range(rows)]
    queries = rng.standard_normal((B, dim_)).astype(np.float32)
    reqs = [RetrievalRequest(query=queries[i], tenant="u0", k=10)
            for i in range(B)]
    # exact numpy oracle over the fill corpus (cosine top-10); the bf16
    # arena rounds, so near-ties may swap — hence the 0.97 floor
    embn = emb / np.maximum(
        np.linalg.norm(emb, axis=1, keepdims=True), 1e-9)
    qn = queries / np.maximum(
        np.linalg.norm(queries, axis=1, keepdims=True), 1e-9)
    oracle = np.argsort(-(qn @ embn.T), axis=1)[:, :10]

    per_group, qps_by_g, geoms = [], {}, []
    keep = {}                      # largest-G placement: freshness cells
    for G in counts:
        tel = Telemetry()
        # +192 headroom: the staleness/overlay/crash cells add 112 rows
        # on top of the fill, and the tenant-affine partitioner needs
        # spill room past the probe tenants' home partitions
        pl = ReplicaPlacement(G, dim_, capacity=rows + 192,
                              dtype=jnp.bfloat16, k=10, cap_take=5,
                              max_nbr=16, telemetry=tel,
                              telemetry_hbm=True)
        t0 = time.perf_counter()
        for c in range(0, rows, 2048):
            pl.ingest(ids[c:c + 2048], emb[c:c + 2048], "u0")
        fill_s = time.perf_counter() - t0
        for g in pl.groups:
            g.serve_requests(reqs)               # warm/compile every group
        turns = reps * G
        t0 = time.perf_counter()
        for _ in range(turns):
            res = pl.serve(reqs)
        wall = time.perf_counter() - t0
        qps = turns * B / wall
        qps_by_g[G] = qps
        # measured dispatch count: EVERY group's entries over routed turns
        calls = {"n": 0}
        origs = [g._dispatch for g in pl.groups]

        def counting_wrap(orig):
            def counting(fn, *a, **kw):
                calls["n"] += 1
                return orig(fn, *a, **kw)
            return counting

        for g, orig in zip(pl.groups, origs):
            g._dispatch = counting_wrap(orig)
        for _ in range(reps):
            pl.serve(reqs)
        for g, orig in zip(pl.groups, origs):
            g._dispatch = orig
        dpt = calls["n"] / reps
        hits = sum(len(set(r.ids[:10])
                       & {f"f{j}" for j in oracle[i]})
                   for i, r in enumerate(res))
        recall = hits / (B * 10)
        per_group.append({
            "groups": G, "devices_per_group": n_dev // G,
            "routed_turns": turns, "aggregate_qps": round(qps, 1),
            "turn_batch64_ms": round(wall * 1e3 / turns, 3),
            "measured_dispatches_per_turn": dpt,
            "recall_at_10": round(recall, 4),
            "fill_s": round(fill_s, 1),
            "journal_pending_after_fill": pl.journal.pending_count,
        })
        geoms.append({"kind": "serve", "mode": "exact", "batch": B,
                      "rows": rows + 193, "dim": dim_, "k": 16,
                      "dtype_bytes": 2, "mesh_parts": n_dev // G,
                      "replica_groups": G})
        if G == counts[-1]:
            keep = {"pl": pl, "tel": tel}
        else:
            del pl

    pl, tel = keep["pl"], keep["tel"]
    Gmax = counts[-1]
    # --- bounded staleness: defer the fan-out, measure the open window
    st_emb = rng.standard_normal((64, dim_)).astype(np.float32)
    pl.ingest([f"st{i}" for i in range(64)], st_emb, "staleness-probe",
              replicate=False)
    time.sleep(0.05)
    staleness_open = pl.staleness()          # window while replicas lag
    lag_open = pl.lag()
    pl.catch_up()
    staleness_closed = pl.staleness()
    # --- overlay tenant: rows exist ONLY on the home group
    ov_emb = rng.standard_normal((16, dim_)).astype(np.float32)
    pl.ingest([f"ov{i}" for i in range(16)], ov_emb, "agent-ov",
              overlay=True)
    home = pl.group_for_tenant("agent-ov")
    ov_copies = sum(1 for g in pl.groups
                    if any(i.startswith("ov") for i in g.id_to_row))
    # --- crash mid-replay: recovery must lose and double NOTHING
    cr_emb = rng.standard_normal((32, dim_)).astype(np.float32)
    cr_ids = [f"cr{i}" for i in range(32)]
    crashed = False
    with _faults.INJECTOR.armed("replica.mid_replay", times=1):
        try:
            pl.ingest(cr_ids, cr_emb, "crash-probe")
        except InjectedFault:
            crashed = True
    lag_after_crash = pl.lag()
    pl.catch_up()
    lost = sum(1 for g in pl.groups for i in cr_ids if i not in g.id_to_row)
    doubled = sum(1 for g in pl.groups
                  if len(g.row_to_id) != len(g.id_to_row))
    scaling = qps_by_g[Gmax] / qps_by_g[counts[0]]

    out = {
        "replica": True,
        "group_counts": counts,
        "devices": n_dev,
        "arena_rows": rows,
        "dim": dim_,
        "batch": B,
        "reps": reps,
        "per_group": per_group,
        "qps_scaling": round(scaling, 2),
        "qps_scaling_floor": qps_scaling_floor,
        "recall_at_10": min(p["recall_at_10"] for p in per_group),
        "recall_floor": recall_floor,
        "dispatches_per_turn": max(p["measured_dispatches_per_turn"]
                                   for p in per_group),
        "replica_staleness_s": round(staleness_open, 3),
        "staleness_bound_s": staleness_bound_s,
        "staleness_after_catchup_s": round(staleness_closed, 3),
        "lag_during_window": lag_open,
        "overlay": {"home_group": home, "groups_holding_rows": ov_copies},
        "crash_replay": {"fault_fired": crashed,
                         "lag_after_crash": lag_after_crash,
                         "lost_facts": lost, "doubled_facts": doubled},
        "geometries_exercised": geoms,
        "telemetry": _telemetry_block(tel),
        "roofline": {
            "routed_turn_batch64": _roofline(
                rows, dim_, 2,
                per_group[-1]["turn_batch64_ms"], B, on_tpu),
        },
    }
    del pl, keep
    return out


def bench_sharded_ingest(on_tpu: bool, rows: int, n_parts: int = 4,
                         batch: int = 1024, reps: int = 3,
                         speedup_floor: float = 1.5,
                         write_scaling_floor: float = 0.5):
    """Pod-scale fused INGEST A/B (ISSUE 9 acceptance): coalesced
    mega-batches of ``batch`` facts through the pod write path —

      fused pod     : ONE distributed shard_map dispatch running the FULL
                      write program (dedup probe + intra-batch resolve +
                      node scatter + merge touch + link scans + gated
                      edge insert with pool compaction;
                      ``ShardedMemoryIndex.ingest``) — the probe and the
                      link scan share ONE arena stream
      host-driven   : the semantics-EQUIVALENT classic pod sequence
                      (``ingest_fused=False``): probe dispatch → host
                      dedup resolve → add scatter → merge-touch scatter →
                      link-scan dispatch → host gate → edge-insert
                      dispatch (two full arena streams + per-step
                      round trips)
      single chip   : ``MemoryIndex.ingest_batch_dedup`` over the same
                      corpus on ONE device (the pod-vs-chip write-scaling
                      datapoint; on a shared-socket CPU mesh the chips
                      share cores, so ~1.0 is the honest expectation —
                      the floor guards against the composition REGRESSING
                      below the single chip, real scaling needs ROADMAP
                      item 1's TPU window)

    Batches carry real structure: group-clustered vectors whose
    ~0.86 intra-group cosine passes the 0.5 link gate against earlier
    batches' rows (gated edge inserts do real work), plus ~2% near-dups
    of existing rows (the dedup resolve does real work).
    ``dispatches_per_conversation`` is MEASURED by counting the pod
    index's ``_ingest_dispatch`` entries per ingest call. Link-scan cost
    scales with CAPACITY (masked dead rows still stream), so the few
    thousand rows the A/B itself adds do not skew the comparison."""
    import jax as _jax
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh
    from lazzaro_tpu.utils.telemetry import Telemetry

    n_dev = len(_jax.devices())
    if n_dev < n_parts:
        print(f"[bench] sharded-ingest: only {n_dev} devices (wanted "
              f"{n_parts}); set XLA_FLAGS="
              f"--xla_force_host_platform_device_count={n_parts} for the "
              f"CPU mesh", file=sys.stderr, flush=True)
        n_parts = max(1, n_dev)
    mesh = make_mesh(("data",), (n_parts,),
                     devices=_jax.devices()[:n_parts])
    rng = np.random.default_rng(47)
    n_groups = max(1, batch // 4)
    dirs = rng.standard_normal((n_groups, DIM)).astype(np.float32)
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)

    def clustered(n, seed):
        # group dir (0.88) + unit-norm noise (0.35): intra-group cosine
        # ~0.86 — above the 0.5 link gate, below the 0.95 dedup gate
        # (per-row NORMALIZED noise, so the geometry is dim-independent)
        r = np.random.default_rng(seed)
        g = np.arange(n) % n_groups
        noise = r.standard_normal((n, DIM)).astype(np.float32)
        noise /= np.maximum(np.linalg.norm(noise, axis=1, keepdims=True),
                            1e-9)
        v = dirs[g] * 0.88 + 0.35 * noise
        return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(
            np.float32)

    n_batches = 2 * (reps + 1)             # classic + fused, warm + timed
    total_cap = rows + n_batches * batch + 64
    edge_cap = max(1 << 17, 4 * n_batches * batch * 3 + 64)
    tel = Telemetry()
    idx = ShardedMemoryIndex(mesh, dim=DIM, capacity=total_cap,
                             dtype=jnp.bfloat16, telemetry=tel,
                             telemetry_hbm=True, edge_capacity=edge_cap)
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        idx.add([f"p{c + i}" for i in range(m)], clustered(m, 100 + c),
                "u0")
    fill_s = time.perf_counter() - t0

    def make_batch(bi, seed):
        emb = clustered(batch, 1000 + seed)
        # ~2% near-dups of the prefill head (clustered() is deterministic
        # per seed, so these reproduce prefill rows exactly): the dedup
        # probe + merge touch do real work in the measured run
        if rows >= batch:
            dup_rows = clustered(batch, 100)   # == prefill chunk 0 head
            for j in range(0, batch, 50):
                noise = np.random.default_rng(
                    seed * batch + j).standard_normal(DIM)
                noise *= 0.25 / max(np.linalg.norm(noise), 1e-9)
                emb[j] = (dup_rows[j] + noise).astype(np.float32)  # ~0.97
        ids = [f"b{bi}_{i}" for i in range(batch)]
        return ids, emb

    def run(bi, seed):
        ids, emb = make_batch(bi, seed)
        return idx.ingest(ids, emb, "u0", dedup_gate=0.95, link_k=3,
                          link_gate=0.5, link_scale=0.8)

    # ---- classic (host-driven) baseline first: identical capacity, so
    # the corpus the two sides scan costs the same
    idx.ingest_fused = False
    run(0, 0)                              # warm the classic kernels
    t0 = time.perf_counter()
    for r in range(reps):
        run(1 + r, 1 + r)
    classic_s = time.perf_counter() - t0
    classic_dispatches = idx.ingest_dispatch_count

    # ---- fused pod path
    idx.ingest_fused = True
    warm_ms = idx.warmup_ingest((batch,))
    run(100, 100)                          # one real warm batch
    calls = {"n": 0, "batches": 0}
    orig = idx._ingest_dispatch

    def counting(fn, *a, **kw):
        calls["n"] += 1
        return orig(fn, *a, **kw)

    idx._ingest_dispatch = counting
    counters = {"dedup_hits": 0, "links_accepted": 0, "overflow": 0}
    t0 = time.perf_counter()
    for r in range(reps):
        got = run(101 + r, 101 + r)
        calls["batches"] += 1
        counters["dedup_hits"] += got["counters"]["dedup_hits"]
        counters["links_accepted"] += got["counters"]["links_accepted"]
        counters["overflow"] += int(got["counters"]["overflow"])
    fused_s = time.perf_counter() - t0
    idx._ingest_dispatch = orig
    dispatches_per_conv = calls["n"] / max(calls["batches"], 1)
    fused_mps = reps * batch / fused_s
    classic_mps = reps * batch / classic_s
    pod_hbm = {k: v for k, v in tel.snapshot()["gauges"].items()
               if k.startswith("kernel.peak_hbm_bytes")}
    del idx

    # ---- single-chip fused write path over the same corpus (one device)
    chip = MemoryIndex(dim=DIM, capacity=total_cap, edge_capacity=edge_cap,
                       dtype=jnp.bfloat16, telemetry=Telemetry())
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        ids = [f"p{c + i}" for i in range(m)]
        chip.add(ids, clustered(m, 100 + c), [0.5] * m, [0.0] * m,
                 ["semantic"] * m, ["default"] * m, "u0")
    chip.warmup_ingest((batch,), shard_modes=(0,))

    def chip_run(bi, seed):
        ids, emb = make_batch(bi, seed)
        pending = chip.ingest_batch_dedup(
            emb, [0.5] * batch, [0.0] * batch, ["semantic"] * batch,
            ["default"] * batch, "u0", dedup_gate=0.95, link_k=3,
            link_gate=0.5, link_scale=0.8, shard_modes=(0,))
        if pending is not None:
            chip.commit_ingest_dedup(
                pending, [None if pending["dup"][i] else ids[i]
                          for i in range(batch)])

    chip_run(200, 200)                     # warm
    t0 = time.perf_counter()
    for r in range(reps):
        chip_run(201 + r, 201 + r)
    chip_s = time.perf_counter() - t0
    chip_mps = reps * batch / chip_s
    del chip

    write_scaling = fused_mps / chip_mps
    out = {
        "mesh": {"n_parts": n_parts, "axis": "data",
                 "rows_per_chip": (total_cap + 1) // n_parts},
        "ingest_sharded": True,
        "arena_rows": rows,
        "dim": DIM,
        "batch": batch,
        "reps": reps,
        "fill_s": round(fill_s, 1),
        "warmup_ms": {str(k): round(v, 1) for k, v in warm_ms.items()},
        "dispatches_per_conversation": dispatches_per_conv,
        "classic_dispatches_per_conversation": round(
            classic_dispatches / (reps + 1), 2),
        "sharded_ingest_memories_per_sec": round(fused_mps, 1),
        "host_driven_memories_per_sec": round(classic_mps, 1),
        "single_chip_fused_memories_per_sec": round(chip_mps, 1),
        "fused_vs_classic_speedup": round(classic_s / fused_s, 2),
        "speedup_floor": speedup_floor,
        "write_scaling": round(write_scaling, 2),
        "write_scaling_floor": write_scaling_floor,
        "dedup_hits": counters["dedup_hits"],
        "links_accepted": counters["links_accepted"],
        "link_pool_overflows": counters["overflow"],
        "parity": "tests/test_sharded_ingest.py pins bit-identical "
                  "sharded-vs-single-chip state and semantic fused-vs-"
                  "classic parity",
        "telemetry": _telemetry_block(tel),
        "peak_hbm_gauges": pod_hbm or None,
        "roofline": {
            # one fused mega-batch streams the whole (capacity-wide)
            # arena ONCE (shared probe+link matmul); classic streams it
            # twice
            "fused_ingest_batch": _roofline(total_cap, DIM, 2,
                                            fused_s * 1e3 / reps, batch,
                                            on_tpu),
            "classic_ingest_batch": _roofline(2 * total_cap, DIM, 2,
                                              classic_s * 1e3 / reps,
                                              batch, on_tpu),
        },
    }
    return out


def bench_tiered_serving(on_tpu: bool, rows: int = 65_536,
                         hot_budget: int = None, reps: int = 5,
                         recall_floor: float = 0.95):
    """Tiered-memory acceptance bench (ISSUE 8): serve a corpus 4× the
    configured hot-row budget through the two-tier stack and measure

      - hot-only probe: queries whose coarse candidates are all hot must
        cost exactly ONE dispatch per coalesced turn (the generic
        dispatch gate pins the artifact's ``dispatches_per_turn``),
      - cold probe: queries hitting demoted rows pay the coarse scan plus
        ONE bounded finish dispatch (``cold_hit_dispatches_per_turn``),
      - recall@10 of mixed traffic against the exact numpy ground truth
        over the FULL corpus (floor 0.95 — tiering must not silently
        trade recall for capacity),
      - pump overlap: p95 turn latency while the async pump is actively
        demoting must stay within 1.5× the quiescent p95.

    Corpus geometry: the hot set and the cold tail live in near-
    orthogonal subspaces, so probe traffic can be aimed (a hot-subspace
    query's top-(k+slack) candidate window stays entirely hot); the decay
    signals (salience + last_accessed) are set so the WATERMARK POLICY —
    not an explicit row list — selects exactly the designed cold tail,
    i.e. the artifact exercises the real demotion path end to end."""
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.tier import TierPump
    from lazzaro_tpu.utils.telemetry import Telemetry

    B = 64
    hot_budget = hot_budget or rows // 4
    n_cold_design = rows - hot_budget
    rng = np.random.default_rng(47)
    tel = Telemetry()
    idx = MemoryIndex(dim=DIM, capacity=rows + 64, dtype=jnp.bfloat16,
                      int8_serving=True, telemetry=tel, telemetry_hbm=True,
                      coarse_slack=32)
    # two near-orthogonal unit directions for the hot set / cold tail
    a_dir = np.zeros(DIM, np.float32); a_dir[0] = 1.0
    b_dir = np.zeros(DIM, np.float32); b_dir[1] = 1.0

    def make_vecs(n, base, seed, spread=0.5):
        # noise scaled to a FIXED norm relative to the unit base (at
        # d=768 a raw 0.3·N(0,1) vector has norm ~8 and would swamp the
        # subspace structure): cos(v, base) ≈ 1/sqrt(1+spread²) ≈ 0.89
        r = np.random.default_rng(seed)
        nz = r.standard_normal((n, DIM)).astype(np.float32)
        nz *= spread / np.linalg.norm(nz, axis=1, keepdims=True)
        v = base[None, :] + nz
        return v / np.linalg.norm(v, axis=1, keepdims=True)

    hot_emb = make_vecs(hot_budget, a_dir, 1)
    cold_emb = make_vecs(n_cold_design, b_dir, 2)
    emb = np.concatenate([hot_emb, cold_emb])
    now0 = time.time()
    t0 = time.perf_counter()
    for c in range(0, rows, 65_536):
        m = min(65_536, rows - c)
        sal = np.where(np.arange(c, c + m) < hot_budget, 0.9, 0.1)
        ts = np.where(np.arange(c, c + m) < hot_budget, now0, now0 - 30 * 86400.0)
        idx.add([f"f{c + i}" for i in range(m)], emb[c:c + m],
                sal.tolist(), ts.tolist(), ["semantic"] * m,
                ["default"] * m, "u0")
    fill_s = time.perf_counter() - t0
    ne = min(50_000, rows - 1)
    idx.add_edges([(f"f{i}", f"f{i + 1}", 0.7) for i in range(ne)], "u0")

    # ---- demotion via the WATERMARK POLICY (not an explicit list) -------
    # promote_hits is effectively off: the probe waves re-hit the same
    # cold rows dozens of times, and access-driven promotion churn would
    # contaminate the overlap measurement (the promotion path is driven
    # explicitly below; the hit-threshold machinery is unit-tested).
    tm = idx.enable_tiering(hot_budget, high_watermark=1.0,
                            low_watermark=1.0, chunk_rows=512,
                            hysteresis_s=0.0, promote_hits=1_000_000)
    t0 = time.perf_counter()
    pump_stats = tm.run_once(now=now0)
    demote_s = time.perf_counter() - t0
    hot_fraction = tm.hot_rows / rows

    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)

    def reqs_for(queries, boost=True):
        return [RetrievalRequest(query=queries[i], tenant="u0", k=10,
                                 gate_enabled=True, boost=boost)
                for i in range(len(queries))]

    hot_q = make_vecs(B, a_dir, 3).astype(np.float32)
    cold_q = make_vecs(B, b_dir, 4).astype(np.float32)
    mix_rows = rng.integers(0, rows, B)
    mix_nz = rng.standard_normal((B, DIM)).astype(np.float32)
    mix_nz *= 0.3 / np.linalg.norm(mix_nz, axis=1, keepdims=True)
    mix_q = emb[mix_rows] + mix_nz

    # warm every path once (compiles, and the opt-in peak-HBM gauge
    # records here — BEFORE the counting wrappers replace the jit entry
    # points) — including the *_copy twins the ownership gate falls back
    # to while the pump holds a snapshot (their first-use compile would
    # otherwise land inside the overlap measurement)
    idx.search_fused_requests(reqs_for(hot_q), **kw)
    idx.search_fused_requests(reqs_for(cold_q), **kw)
    idx.search_fused_requests(reqs_for(mix_q), **kw)
    snap = idx.state
    idx.search_fused_requests(reqs_for(mix_q), **kw)
    del snap

    # measured dispatch counters over the tiered jit entry points
    calls = {"scan": 0, "finish": 0}
    wrapped = {}
    scan_names = ("search_fused_tiered_ragged",
                  "search_fused_tiered_ragged_copy",
                  "search_fused_tiered_ragged_read")
    fin_names = ("tier_cold_finish", "tier_cold_finish_copy",
                 "tier_cold_rescore")
    for name in scan_names + fin_names:
        orig = getattr(S_mod, name)
        wrapped[name] = orig
        key = "finish" if name in fin_names else "scan"

        def counting(*a, __orig=orig, __key=key, **k2):
            calls[__key] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)
    try:
        calls["scan"] = calls["finish"] = 0
        t0 = time.perf_counter()
        for _ in range(reps):
            idx.search_fused_requests(reqs_for(hot_q), **kw)
        hot_ms = (time.perf_counter() - t0) * 1e3 / reps
        hot_dispatches = (calls["scan"] + calls["finish"]) / reps

        calls["scan"] = calls["finish"] = 0
        t0 = time.perf_counter()
        for _ in range(reps):
            idx.search_fused_requests(reqs_for(cold_q), **kw)
        cold_ms = (time.perf_counter() - t0) * 1e3 / reps
        cold_dispatches = (calls["scan"] + calls["finish"]) / reps

        # recall@10 of mixed traffic vs exact full-corpus ground truth
        res = idx.search_fused_requests(reqs_for(mix_q, boost=False), **kw)
        # ground truth mirrors the arena's storage numerics: normalized
        # rows cast to bf16, query likewise (the fused rescore computes
        # bf16×bf16 with f32 accumulation)
        qn = mix_q / np.linalg.norm(mix_q, axis=1, keepdims=True)
        qn = qn.astype(ml_dtypes.bfloat16).astype(np.float32)
        emb_st = emb.astype(ml_dtypes.bfloat16).astype(np.float32)
        truth = np.argsort(-(qn @ emb_st.T), axis=1)[:, :10]
        hits = 0
        for i, r in enumerate(res):
            got = {idx.id_to_row[g] for g in r.ids[:10]}
            hits += len(got & set(truth[i].tolist()))
        recall = hits / (10 * B)
        cold_hit_rate = tm.cold_turns / max(tm.turns, 1)

        # ---- pump overlap: serve while the pump demotes ------------------
        quiescent = []
        for _ in range(10):
            t0 = time.perf_counter()
            idx.search_fused_requests(reqs_for(mix_q), **kw)
            quiescent.append((time.perf_counter() - t0) * 1e3)
        # re-heat a slab so the pump has real demotion work, then serve
        # against the moving residency state
        # warm the pump's copy-twin scatters at chunk granularity: while
        # serving holds state snapshots the ownership gate routes demote/
        # promote through the *_copy kernels, and their first-use compile
        # would otherwise spike one measured overlap turn
        snap = idx.state
        warm_rows = [idx.id_to_row[f"f{hot_budget + i}"]
                     for i in range(tm.chunk_rows)]
        tm.promote_rows(warm_rows, now=now0)
        tm.demote_rows(warm_rows, now=now0)
        del snap
        reheated = [idx.id_to_row[f"f{hot_budget + i}"]
                    for i in range(8192)]
        tm.promote_rows(reheated, now=now0)
        idx.state.emb.block_until_ready()     # drain the promote backlog
        tm.max_demote_per_pass = tm.chunk_rows   # spread the drain
        pump = TierPump(tm, interval_s=0.25).start()
        active = []
        try:
            deadline = time.time() + 60.0
            while tm.hot_rows > hot_budget and time.time() < deadline:
                t0 = time.perf_counter()
                idx.search_fused_requests(reqs_for(mix_q), **kw)
                active.append((time.perf_counter() - t0) * 1e3)
            # p95 needs a real sample count; trailing turns still run with
            # the pump thread live
            while len(active) < 20:
                t0 = time.perf_counter()
                idx.search_fused_requests(reqs_for(mix_q), **kw)
                active.append((time.perf_counter() - t0) * 1e3)
        finally:
            pump.stop()
    finally:
        for name, orig in wrapped.items():
            setattr(S_mod, name, orig)
    q_p95 = float(np.percentile(quiescent, 95))
    a_p95 = float(np.percentile(active, 95))
    out = {
        "tiered": True,
        "corpus_rows": rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "fill_s": round(fill_s, 1),
        "demote_s": round(demote_s, 2),
        "pump_first_pass": pump_stats,
        "hot_budget_rows": hot_budget,
        "corpus_to_hot_ratio": round(rows / hot_budget, 2),
        "hot_fraction": round(hot_fraction, 4),
        "cold_rows": tm.cold_count,
        "cold_hit_rate": round(cold_hit_rate, 4),
        "recall_at_10": round(recall, 4),
        "recall_floor": recall_floor,
        "dispatches_per_turn": hot_dispatches,      # hot-only probe
        "cold_hit_dispatches_per_turn": cold_dispatches,
        "hot_turn_batch64_ms": round(hot_ms, 3),
        "cold_turn_batch64_ms": round(cold_ms, 3),
        "tiered_hot_qps": round(B / (hot_ms / 1e3), 1),
        "tiered_cold_qps": round(B / (cold_ms / 1e3), 1),
        "pump_overlap": {
            "quiescent_p95_ms": round(q_p95, 2),
            "active_demotion_p95_ms": round(a_p95, 2),
            "ratio": round(a_p95 / q_p95, 3),
            "ratio_ceiling": 1.5,
            "active_turns_measured": len(active),
        },
        "tier": tm.stats(),
        "telemetry": _telemetry_block(tel),
        "roofline": {
            # the tiered coarse scan streams 1 byte/row-dim (int8 shadow)
            "tiered_hot_batch64": _roofline(rows, DIM, 1, hot_ms, B,
                                            on_tpu),
        },
    }
    del idx
    return out


def bench_paged_arena(on_tpu: bool, rows: int = 16_384, reps: int = 5,
                      qps_floor: float = 0.9):
    """Paged-arena acceptance bench (ISSUE 17): the SAME corpus served
    dense and through the page-table indirection, then a grow → demote →
    re-ingest churn on the paged variant. The artifact pins the four
    claims the feature makes:

      - serving parity cost: paged QPS ≥ ``qps_floor``× dense QPS and
        still exactly ONE fused dispatch per turn (the indirection is a
        gather INSIDE the kernel, not a sibling dispatch),
      - reclamation: watermark demotion PUSHES freed slots
        (``pages_free`` rises by exactly the demoted count / page math),
        and the re-ingest after it POPS them back (no pool growth),
      - copy-free growth: logical capacity growth past the initial
        allocation reuses the emb pool buffer BY REFERENCE — zero
        embedding bytes copied — while the dense twin reallocates its
        whole table,
      - planner honesty: the admission model's resident-bytes prediction
        for the paged geometry (pool + row_map + inv_map) undercuts the
        dense geometry the moment the pool lags capacity, and stays
        BELOW the dense prediction after the growth step.
    """
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.plan.model import CostModel
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    B = 64
    page_rows = max(256, rows // 16)
    rng = np.random.default_rng(17)
    emb = rng.standard_normal((rows, DIM)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    probe = rng.integers(0, rows, B)
    nz = rng.standard_normal((B, DIM)).astype(np.float32)
    nz *= 0.3 / np.linalg.norm(nz, axis=1, keepdims=True)
    queries = (emb[probe] + nz).astype(np.float32)

    def build(paged):
        tel = Telemetry()
        idx = MemoryIndex(dim=DIM, capacity=rows + 64, dtype=jnp.bfloat16,
                          telemetry=tel, paged=paged, page_rows=page_rows)
        t0 = time.perf_counter()
        for c in range(0, rows, 65_536):
            m = min(65_536, rows - c)
            idx.add([f"f{c + i}" for i in range(m)], emb[c:c + m],
                    [0.5] * m, [0.0] * m, ["semantic"] * m,
                    ["default"] * m, "u0")
        return idx, tel, time.perf_counter() - t0

    dense, _, dense_fill_s = build(False)
    paged, tel, paged_fill_s = build(True)

    # ---- serving: QPS ratio + dispatch counter ----------------------
    # measured over the production fused serving surface (same entry the
    # tiered artifacts gate) — the page indirection must ride
    # INSIDE the one fused program, so the counted dispatch total per
    # turn is identical to dense and exactly 1.
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)

    def reqs_for(qs):
        return [RetrievalRequest(query=qs[i], tenant="u0", k=10,
                                 gate_enabled=True, boost=False)
                for i in range(len(qs))]

    scan_names = ("search_fused_ragged", "search_fused_ragged_copy",
                  "search_fused_ragged_read", "arena_search")
    calls = {"n": 0}
    wrapped = {}
    for name in scan_names:
        orig = getattr(S_mod, name)
        wrapped[name] = orig

        def counting(*a, __orig=orig, **k2):
            calls["n"] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)
    try:
        dense.search_fused_requests(reqs_for(queries), **kw)   # compile
        paged.search_fused_requests(reqs_for(queries), **kw)
        res_d, res_p, times = {}, {}, {}
        for tag, idx in (("dense", dense), ("paged", paged)):
            calls["n"] = 0
            t0 = time.perf_counter()
            for _ in range(reps):
                res = idx.search_fused_requests(reqs_for(queries), **kw)
            times[tag] = (time.perf_counter() - t0) * 1e3 / reps
            (res_d if tag == "dense" else res_p)["r"] = res
            if tag == "paged":
                dispatches_per_turn = calls["n"] / reps
    finally:
        for name, orig in wrapped.items():
            setattr(S_mod, name, orig)
    # parity spot-check rides the artifact (the bit-parity suite is tier-1)
    agree = sum(1 for a, b in zip(res_d["r"], res_p["r"])
                if a.ids[:5] == b.ids[:5]) / B
    dense_qps = B / (times["dense"] / 1e3)
    paged_qps = B / (times["paged"] / 1e3)

    # ---- planner resident-bytes: paged pool vs dense table ----------
    cm = CostModel()
    g_dense = dense._serve_geometry(B, "exact", 16)
    g_paged = paged._serve_geometry(B, "exact", 16)
    res_bytes_dense = cm.resident_bytes(g_dense)
    res_bytes_paged = cm.resident_bytes(g_paged)

    # ---- churn: demote reclaims pages, re-ingest reuses them --------
    before = paged.stats()["paged"]
    tm = paged.enable_tiering(rows // 2, high_watermark=1.0,
                              low_watermark=1.0, chunk_rows=4096,
                              hysteresis_s=0.0, promote_hits=1_000_000)
    t0 = time.perf_counter()
    tm.run_once(now=time.time() + 60 * 86400.0)
    demote_s = time.perf_counter() - t0
    after_demote = paged.stats()["paged"]
    pool_grows_before_reingest = paged.telemetry.counter_total(
        "arena.pool_grows")
    m = min(4096, tm.demoted_total)
    paged.add([f"r{i}" for i in range(m)],
              emb[:m], [0.9] * m, [time.time()] * m,
              ["semantic"] * m, ["default"] * m, "u0")
    after_reingest = paged.stats()["paged"]
    reingest_grew_pool = (paged.telemetry.counter_total("arena.pool_grows")
                          > pool_grows_before_reingest)

    # ---- copy-free growth: metadata realloc, pool by reference ------
    # the REAL grow step on the live state: logical capacity doubles,
    # the emb pool is the SAME buffer (is-identity — zero embedding
    # bytes moved), and the planner's resident prediction for the grown
    # paged geometry stays flat while the dense twin's doubles.
    cap0, pool0 = paged.capacity, paged.state.emb.shape[0]
    st = paged.state
    grown = S_mod.grow_arena_paged(st, cap0 * 2 + 1)
    grow_copied_pool = grown.emb is not st.emb
    cap1, pool1 = int(grown.capacity), grown.emb.shape[0]
    g_paged_grown = dataclasses.replace(g_paged, rows=cap1 + 1)
    g_dense_grown = dataclasses.replace(g_dense, rows=cap1 + 1)
    res_bytes_paged_grown = cm.resident_bytes(g_paged_grown)
    res_bytes_dense_grown = cm.resident_bytes(g_dense_grown)

    out = {
        "paged": True,
        "corpus_rows": rows,
        "dim": DIM,
        "batch": B,
        "reps": reps,
        "page_rows": page_rows,
        "dense_fill_s": round(dense_fill_s, 1),
        "paged_fill_s": round(paged_fill_s, 1),
        "dense_turn_batch64_ms": round(times["dense"], 3),
        "paged_turn_batch64_ms": round(times["paged"], 3),
        "dense_qps": round(dense_qps, 1),
        "paged_qps": round(paged_qps, 1),
        "paged_qps_ratio": round(paged_qps / dense_qps, 3),
        "paged_qps_floor": qps_floor,
        "top5_agreement": round(agree, 4),
        "dispatches_per_turn": dispatches_per_turn,
        "page_stats_initial": before,
        "page_stats_after_demote": after_demote,
        "page_stats_after_reingest": after_reingest,
        "demoted_rows": tm.demoted_total,
        "demote_s": round(demote_s, 2),
        "reingest_rows": m,
        "reingest_grew_pool": reingest_grew_pool,
        "growth": {
            "capacity_before": cap0, "capacity_after": cap1,
            "pool_rows_before": pool0, "pool_rows_after": pool1,
            "grow_copied_pool": grow_copied_pool,
        },
        "planner": {
            "resident_bytes_dense": res_bytes_dense,
            "resident_bytes_paged": res_bytes_paged,
            "resident_bytes_dense_after_grow": res_bytes_dense_grown,
            "resident_bytes_paged_after_grow": res_bytes_paged_grown,
        },
        "mirror_mismatches": paged.telemetry.counter_total(
            "arena.page_mirror_mismatches"),
        "telemetry": _telemetry_block(tel),
        "roofline": {
            "paged_batch64": _roofline(rows, DIM, 2, times["paged"], B,
                                       on_tpu),
        },
    }
    del dense, paged
    return out


def bench_lifecycle(on_tpu: bool, rows: int = 8_192, tenants: int = 16,
                    rounds: int = 6, serve_turns: int = 480,
                    p99_bound: float = 2.0, stall_floor: float = 1.5):
    """Device-side lifecycle acceptance bench (ISSUE 19): decay + prune +
    archive for ALL tenants as ONE fused sweep, exercised under a LIVE
    serving thread. The artifact pins the four claims:

      - one dispatch: the counted jit entries per sweep == 1 (the
        ``lifecycle_dispatch_count`` delta agrees),
      - bit-parity: a fused-swept twin and a classic-loop twin of the
        same churn fixture end with bit-identical salience columns, edge
        pools, and per-tenant archive verdicts,
      - serving tail: p99 serve latency while sweeps run concurrently
        stays within ``p99_bound``× the maintenance-free baseline
        (maintenance never stalls the serving path on the host),
      - host-stall elimination: one fused sweep vs the classic
        3-dispatches-per-tenant host loop (each with its own readback
        stall) — wall-clock speedup ≥ ``stall_floor`` at this tenant
        count, and the dispatch count drops 3·T → 1.
    """
    import threading

    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.plan.model import CostModel
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    dim = min(DIM, 128)
    B = 32
    per = rows // tenants
    edges_per = max(8, per // 4)
    rate, floor, thresh = 0.01, 0.2, 0.35
    rng = np.random.default_rng(19)
    emb = rng.standard_normal((rows, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)

    def build(tel=None):
        idx = MemoryIndex(dim=dim, capacity=rows + 64,
                          edge_capacity=max(4096, tenants * edges_per * 4),
                          telemetry=tel, telemetry_hbm=tel is not None,
                          epoch=0.0)
        for t in range(tenants):
            ids = [f"t{t}:n{i}" for i in range(per)]
            lo = t * per
            idx.add(ids, emb[lo:lo + per],
                    [0.25 + 0.5 * (i / per) for i in range(per)],
                    [100.0] * per, ["semantic"] * per, ["default"] * per,
                    f"t{t}")
            idx.add_edges([(ids[i], ids[(i + 1) % per],
                            0.30 + 0.4 * (i / edges_per))
                           for i in range(edges_per)], f"t{t}", now=100.0)
        return idx

    def churn(idx, round_i, only=None):
        # fresh weak-ish edges each round so every sweep has prune
        # victims; ``only`` restricts to one tenant (the concurrent
        # maintainer churns round-robin so write pressure stays steady
        # without a per-tick all-tenant host loop drowning the sweep)
        for t in range(tenants) if only is None else (only,):
            ids = [f"t{t}:n{i}" for i in range(per)]
            idx.add_edges([(ids[(round_i * 7 + i) % per],
                            ids[(round_i * 7 + i + 2) % per],
                            0.30 + 0.02 * (i % 8))
                           for i in range(8)], f"t{t}", now=100.0 + round_i)

    def sweep(idx, k=8, now=200.0):
        return idx.lifecycle_sweep(
            {f"t{t}": 1 for t in range(tenants)}, rate=rate,
            salience_floor=floor, prune_threshold=thresh,
            weights=(0.5, 0.3, 0.2), archive_k=k, now=now)

    def classic(idx, k=8, now=200.0):
        removed, verdicts = [], {}
        for t in range(tenants):
            idx.decay(f"t{t}", rate, floor)
            removed.extend(idx.prune_edges(f"t{t}", thresh))
            verdicts[f"t{t}"] = idx.evict_candidates(
                f"t{t}", k, now=now, weights=(0.5, 0.3, 0.2))
        return removed, verdicts

    # ---- bit-parity twin run (the tier-1 suite gates this too) ------
    a, b = build(), build()
    removed_a, verdicts_a = classic(a)
    out_b = sweep(b)
    sal_a = np.asarray(a.state.salience)[:rows].view(np.int32)
    sal_b = np.asarray(b.state.salience)[:rows].view(np.int32)
    w_a = np.asarray(a.edge_state.weight)[:-1].view(np.int32)
    w_b = np.asarray(b.edge_state.weight)[:-1].view(np.int32)
    bit_parity = bool(
        np.array_equal(sal_a, sal_b) and np.array_equal(w_a, w_b)
        and sorted(removed_a) == sorted(out_b["removed_edges"])
        and all(verdicts_a[t] == [(n, i) for n, i, _r in
                                  out_b["verdicts"][t]]
                for t in verdicts_a))
    del a, b

    # ---- host-stall elimination: classic loop vs fused sweep --------
    tel = Telemetry()
    idx = build(tel)
    sweep(idx)                                        # compile fused
    classic(idx)                                      # compile classic
    classic_ms, fused_ms = [], []
    for r in range(rounds):
        churn(idx, r)
        t0 = time.perf_counter()
        classic(idx, now=200.0 + r)
        classic_ms.append((time.perf_counter() - t0) * 1e3)
        churn(idx, r + rounds)
        before = idx.lifecycle_dispatch_count
        t0 = time.perf_counter()
        sweep(idx, now=200.0 + r)
        fused_ms.append((time.perf_counter() - t0) * 1e3)
        assert idx.lifecycle_dispatch_count - before == 1
    classic_sweep_ms = float(np.median(classic_ms))
    fused_sweep_ms = float(np.median(fused_ms))

    # counted jit entries for ONE more sweep (the CI gate's number)
    counted = ("lifecycle_sweep", "lifecycle_sweep_copy", "decay_fused",
               "decay_fused_copy", "edges_prune", "edges_prune_copy",
               "arena_decay", "arena_decay_copy", "edges_decay",
               "edges_decay_copy")
    calls = {"n": 0}
    saved = {name: getattr(S_mod, name) for name in counted}
    try:
        for name, orig in saved.items():
            def counting(*a2, __orig=orig, **k2):
                calls["n"] += 1
                return __orig(*a2, **k2)
            setattr(S_mod, name, counting)
        churn(idx, 2 * rounds)
        sweep(idx, now=300.0)
        dispatches_per_sweep = calls["n"]
    finally:
        for name, orig in saved.items():
            setattr(S_mod, name, orig)

    # ---- serving tail under concurrent maintenance ------------------
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02)
    probe = rng.integers(0, per, B)
    nz = rng.standard_normal((B, dim)).astype(np.float32)
    nz *= 0.3 / np.linalg.norm(nz, axis=1, keepdims=True)
    queries = (emb[probe] + nz).astype(np.float32)

    def reqs_for():
        return [RetrievalRequest(query=queries[i], tenant="t0", k=10,
                                 gate_enabled=True, boost=False)
                for i in range(B)]

    idx.search_fused_requests(reqs_for(), **kw)       # compile serve

    # Maintenance runs on a cadence, mirroring the MemorySystem pump
    # (``lifecycle_interval_s``) — a back-to-back sweep loop would measure
    # full-duty-cycle contention no deployment exhibits, and on a shared
    # CPU "mesh" it starves the serving thread outright.
    maint_interval_s = 0.05

    def serve_phase(maintain):
        lat, stop = [], threading.Event()
        ticks = [0]

        def maintainer():
            r = 0
            while not stop.wait(maint_interval_s):
                churn(idx, 100 + r, only=r % tenants)
                sweep(idx, now=400.0 + r)
                r += 1
            ticks[0] = r

        th = None
        if maintain:
            # warm every sweep/serve program the maintainer can hit
            # (prune_cap pow2 buckets flip as churn and pruning move the
            # live-edge count) so the timed phase measures steady-state
            # contention, not one-off compiles; pinning an arena
            # reference trips the refcount gate onto the copying twin —
            # the program every concurrent sweep actually runs
            for w in range(3):
                churn(idx, 90 + w, only=w % tenants)
                pin = (idx.state, idx.edge_state)
                sweep(idx, now=390.0 + w)
                del pin
                idx.search_fused_requests(reqs_for(), **kw)
            th = threading.Thread(target=maintainer, daemon=True)
            th.start()
        for _ in range(serve_turns):
            t0 = time.perf_counter()
            idx.search_fused_requests(reqs_for(), **kw)
            lat.append((time.perf_counter() - t0) * 1e3)
        if th is not None:
            stop.set()
            th.join(timeout=30.0)
        return lat, ticks[0]

    base_lat, _ = serve_phase(False)
    maint_lat, maint_ticks = serve_phase(True)
    p99_base = float(np.percentile(base_lat, 99))
    p99_maint = float(np.percentile(maint_lat, 99))

    cm = CostModel()
    g = idx._lifecycle_geometry(tenants, 8)
    out = {
        "lifecycle": True,
        "corpus_rows": rows,
        "dim": dim,
        "tenants": tenants,
        "edges_initial": tenants * edges_per,
        "rounds": rounds,
        "serve_turns": serve_turns,
        "dispatches_per_sweep": dispatches_per_sweep,
        "classic_dispatches_per_sweep": 3 * tenants,
        "bit_parity": bit_parity,
        "pruned_edges_first_sweep": out_b["pruned_edges"],
        "prune_overflow": out_b["prune_overflow"],
        "classic_sweep_ms": round(classic_sweep_ms, 3),
        "fused_sweep_ms": round(fused_sweep_ms, 3),
        "host_stall_speedup": round(classic_sweep_ms / fused_sweep_ms, 3),
        "host_stall_floor": stall_floor,
        "serve_p99_baseline_ms": round(p99_base, 3),
        "serve_p99_under_maintenance_ms": round(p99_maint, 3),
        "serve_p99_ratio": round(p99_maint / p99_base, 3),
        "serve_p99_bound": p99_bound,
        "maintenance_interval_s": maint_interval_s,
        "maintenance_sweeps_during_serve": maint_ticks,
        "serve_p50_baseline_ms": round(float(np.percentile(base_lat, 50)), 3),
        "serve_p50_under_maintenance_ms": round(
            float(np.percentile(maint_lat, 50)), 3),
        "planner": {
            "transient_bytes_lifecycle": cm.transient_bytes(g),
            "resident_bytes": cm.resident_bytes(g),
        },
        "telemetry": _telemetry_block(tel),
        "roofline": {
            "fused_sweep": _roofline(rows, dim, 4, fused_sweep_ms, 1,
                                     on_tpu),
        },
    }
    del idx
    return out


def bench_semantic_cache(on_tpu: bool, rows: int = 65_536, tenants: int = 4,
                         turns: int = 16, batch: int = 32,
                         zipf_s: float = 1.1, pool: int = 16,
                         speedup_floor: float = 1.5,
                         hit_rate_floor: float = 0.5,
                         recall_floor: float = 0.999):
    """Semantic query cache acceptance bench (ISSUE 20): a Zipf-shaped
    multi-tenant chat workload (repeated intent plus near-dup paraphrase
    mass) served through the fused path with the device-resident similarity
    ring ON vs OFF. The artifact pins the five claims:

      - one dispatch: hits ride the SAME fused dispatch — the counted jit
        entries per served turn stay exactly 1.0 with the cache on,
      - throughput: QPS over the Zipf workload ≥ ``speedup_floor``× the
        cache-off twin (hit queries early-out their scan blocks, so the
        win scales with hit rate × scan fraction),
      - hit rate: measured semantic hit rate over the steady-state phase
        ≥ ``hit_rate_floor`` (Zipf s≈1.1 over ``pool`` intents/tenant),
      - no stale hits: under ingest/delete churn the cache-on results
        stay identical to a churned cache-off twin — ``stale_hits == 0``,
      - miss parity: a never-seen query population returns bit-identical
        ids AND scores on both twins (a cold probe is a pure pass-through).
    """
    from lazzaro_tpu.core import state as S_mod
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.serve import RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    dim = min(DIM, 128)
    per = rows // tenants
    slots = max(128, 2 * tenants * pool)
    rng = np.random.default_rng(20)
    emb = rng.standard_normal((rows, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    # intent pool: per tenant, ``pool`` base query vectors; every served
    # query is a paraphrase (tiny jitter, cosine >> threshold) of one,
    # drawn Zipf(s) — the repeated-intent mass real agent traffic shows
    intents = rng.standard_normal((tenants, pool, dim)).astype(np.float32)
    intents /= np.linalg.norm(intents, axis=2, keepdims=True)
    zp = (1.0 / np.arange(1, pool + 1) ** zipf_s)
    zp /= zp.sum()
    kw = dict(cap_take=5, max_nbr=16, super_gate=0.4,
              acc_boost=0.05, nbr_boost=0.02, now=500.0)

    def build(sem: bool):
        tel = Telemetry()
        idx = MemoryIndex(dim=dim, capacity=rows + 255, telemetry=tel,
                          epoch=0.0, semantic_cache=sem,
                          semantic_cache_slots=slots)
        for t in range(tenants):
            lo = t * per
            idx.add([f"t{t}:n{i}" for i in range(per)], emb[lo:lo + per],
                    [0.5] * per, [100.0] * per, ["semantic"] * per,
                    ["default"] * per, f"t{t}")
        return idx, tel

    def turn_reqs(seed):
        r = np.random.default_rng(seed)
        out = []
        for j in range(batch):
            t = int(r.integers(tenants))
            i = int(r.choice(pool, p=zp))
            q = intents[t, i] + 0.003 * r.standard_normal(dim).astype(
                np.float32)
            out.append(RetrievalRequest(query=q, tenant=f"t{t}", k=10,
                                        gate_enabled=True))
        return out

    t0 = time.perf_counter()
    idx_on, tel_on = build(True)
    idx_off, tel_off = build(False)
    fill_s = time.perf_counter() - t0

    # measured dispatch counter over the exact-family jit entries — the
    # fused-serving invariant, cache ON
    calls = {"n": 0}
    wrapped = {}
    for name in ("search_fused_ragged", "search_fused_ragged_copy",
                 "search_fused_ragged_read"):
        orig = getattr(S_mod, name)
        wrapped[name] = orig

        def counting(*a, __orig=orig, **k2):
            calls["n"] += 1
            return __orig(*a, **k2)

        setattr(S_mod, name, counting)

    # warm/compile both twins AND pre-seat the steady-state working set
    t0 = time.perf_counter()
    for s in (0, 1):
        idx_on.search_fused_requests(turn_reqs(s), **kw)
        idx_off.search_fused_requests(turn_reqs(s), **kw)
    warm_s = time.perf_counter() - t0

    h0 = tel_on.counter_total("serve.semantic_hits")
    m0 = tel_on.counter_total("serve.semantic_misses")
    calls["n"] = 0
    t0 = time.perf_counter()
    for s in range(turns):
        idx_on.search_fused_requests(turn_reqs(s), **kw)
    on_s = time.perf_counter() - t0
    dispatches_per_turn = calls["n"] / turns
    for name, orig in wrapped.items():
        setattr(S_mod, name, orig)
    hits = tel_on.counter_total("serve.semantic_hits") - h0
    misses = tel_on.counter_total("serve.semantic_misses") - m0
    hit_rate = hits / max(1, hits + misses)

    t0 = time.perf_counter()
    for s in range(turns):
        idx_off.search_fused_requests(turn_reqs(s), **kw)
    off_s = time.perf_counter() - t0
    qps_on = turns * batch / on_s
    qps_off = turns * batch / off_s

    # miss parity: a NEVER-seen population (novel random directions, far
    # below threshold of anything cached) must be bit-identical on both
    fr = np.random.default_rng(777)
    fq = fr.standard_normal((batch, dim)).astype(np.float32)
    fq /= np.linalg.norm(fq, axis=1, keepdims=True)
    fresh = [RetrievalRequest(query=fq[j],
                              tenant=f"t{int(fr.integers(tenants))}",
                              k=10, gate_enabled=True)
             for j in range(batch)]
    ra = idx_on.search_fused_requests(list(fresh), **kw)
    rb = idx_off.search_fused_requests(list(fresh), **kw)
    miss_parity = all(a.ids == b.ids and a.scores == b.scores
                      for a, b in zip(ra, rb))

    # recall@10 of the warm (hit-serving) turn vs exact brute force over
    # the master matrix — a cached window must BE the exact answer
    probe = turn_reqs(0)
    res = idx_on.search_fused_requests(list(probe), **kw)
    got, want = 0, 0
    for r_i, rq in zip(res, probe):
        t = int(rq.tenant[1:])
        qn = rq.query / np.linalg.norm(rq.query)
        sims = emb[t * per:(t + 1) * per] @ qn
        top = {f"t{t}:n{i}" for i in np.argsort(-sims)[:10]}
        got += len(top & set(r_i.ids))
        want += len(top)
    recall = got / max(1, want)

    # churn: fresh ingest + a delete per round, then the SAME popular
    # queries on both twins. Staleness is content-level: a served window
    # containing a DELETED row, or a churned tenant's queries diverging
    # from the cache-off twin (its entries were invalidated, so those
    # MUST be fresh scans). Unchurned tenants may legitimately serve the
    # cached intent's ranking for a near-dup paraphrase — that is the
    # cache's contracted approximation, not staleness.
    stale_hits = 0
    churn_rounds = 4
    dead: set = set()
    for c in range(churn_rounds):
        t = c % tenants
        nv = intents[t, 0] + 0.01 * rng.standard_normal(dim).astype(
            np.float32)
        nv /= np.linalg.norm(nv)
        for ix in (idx_on, idx_off):
            ix.add([f"t{t}:new{c}"], nv.reshape(1, -1), [0.9], [200.0],
                   ["semantic"], ["default"], f"t{t}")
        victim = f"t{t}:n{c}"
        dead.add(victim)
        idx_on.delete([victim])
        idx_off.delete([victim])
        creqs = turn_reqs(c)
        qa = idx_on.search_fused_requests(list(creqs), **kw)
        qb = idx_off.search_fused_requests(list(creqs), **kw)
        for a, b, rq in zip(qa, qb, creqs):
            if dead & set(a.ids):
                stale_hits += 1          # deleted row still served
            elif rq.tenant == f"t{t}" and a.ids != b.ids:
                stale_hits += 1          # invalidated entry survived

    sem_stats = idx_on.stats().get("semantic_cache") or {}
    out = {
        "semantic_cache": True,
        "arena_rows": rows, "dim": dim, "tenants": tenants,
        "batch": batch, "turns": turns,
        "zipf_s": zipf_s, "intent_pool_per_tenant": pool,
        "ring_slots": slots,
        "ring_occupied": sem_stats.get("occupied"),
        "fill_s": round(fill_s, 1), "warm_s": round(warm_s, 1),
        "dispatches_per_turn": dispatches_per_turn,
        "semantic_hit_rate": round(hit_rate, 4),
        "hit_rate_floor": hit_rate_floor,
        "semantic_qps": round(qps_on, 1),
        "cache_off_qps": round(qps_off, 1),
        "semantic_vs_off_speedup": round(qps_on / qps_off, 2),
        "speedup_floor": speedup_floor,
        "miss_parity": bool(miss_parity),
        "stale_hits": int(stale_hits),
        "churn_rounds": churn_rounds,
        "recall_at_10": round(recall, 4),
        "recall_floor": recall_floor,
        "stale_evictions": tel_on.counter_total(
            "serve.semantic_stale_evictions"),
        # ring-geometry sweep for check_hbm_budget.py (ISSUE 20): every
        # (slots × width) a deployment might configure must either fit
        # the per-chip budget or have a feasible planned split — swept
        # through the cost model's sem terms, not just the one geometry
        # this stage happened to compile
        "geometries_exercised": [
            {"kind": "serve", "mode": "exact", "batch": batch,
             "rows": rows + 256, "dim": dim, "k": 10, "dtype_bytes": 4,
             "sem_slots": s, "sem_width": w}
            for s in (64, 256, 1024)
            for w in (64, 136, 264)],
        "telemetry": _telemetry_block(tel_on),
        "baseline_telemetry": _telemetry_block(tel_off),
        "roofline": _roofline(rows, dim, 2, on_s * 1e3 / turns, batch,
                              on_tpu),
    }
    del idx_on, idx_off
    return out


def bench_reference_default(on_tpu: bool):
    """Reference-DEFAULT configuration, measured: hierarchy
    ON (super-node creation + the 0.4-gated fast path, ref
    memory_system.py:464-482) and auto_consolidate ON (deep consolidation
    every 3rd conversation, ref :505-512) — the headline pipeline disables
    both for ingest-throughput isolation, so this variant is where they
    get a measured number. Runs at a side size (the periodic all-pairs
    merge is ~N²·d FLOPs, tractable on the MXU, hours on a 1-core CPU);
    retrieval is timed through ``_optimized_retrieval`` — the chat-path
    surface whose latency the reference's ⚡/✓/⏱ tiers gate (:332-337)."""
    import tempfile
    from lazzaro_tpu.config import MemoryConfig as MC

    n = min(100_000 if on_tpu else 20_000, TOTAL)
    fpc = min(5_000, n)
    convs = n // fpc
    payloads = [_payload(c, fpc, n) for c in range(convs)]
    with tempfile.TemporaryDirectory() as tmp:
        ms = MemorySystem(
            enable_async=False, enable_hierarchy=True, auto_consolidate=True,
            load_from_disk=False, max_buffer_size=n * 2, db_dir=tmp,
            llm_provider=QueueLLM(payloads),
            embedding_provider=BulkEmbedder(n),
            config=MC(dtype="bfloat16", journal=False,
                      initial_capacity=n + 64, max_edges=2 * n + 64),
            verbose=False)
        t0 = time.perf_counter()
        for c in range(convs):
            ms.start_conversation()
            ms.add_to_short_term(f"conversation {c} transcript",
                                 "episodic", 0.7)
            ms.end_conversation()
        ingest_s = time.perf_counter() - t0
        nodes, edges = ms.buffer.size()
        supers = len(ms.super_nodes)

        rng = np.random.default_rng(123)
        probe = rng.integers(0, n, size=2 * (K_WARM + QUERIES))
        probe = probe[~((probe % DUP_EVERY) == DUP_EVERY - 1)][:K_WARM + QUERIES]
        emb = BulkEmbedder(n)
        texts = [f"fact {p}: user detail number {p}" for p in probe]
        vecs = [emb.embed(t) for t in texts]
        for i in range(K_WARM):
            ms._optimized_retrieval(vecs[i], texts[i])
        lat = []
        fast_hits = 0
        for i in range(K_WARM, K_WARM + QUERIES):
            t0 = time.perf_counter()
            got = ms._optimized_retrieval(vecs[i], texts[i])
            lat.append((time.perf_counter() - t0) * 1e3)
            # fast-path signature: the first result is a super-node child
            # returned in child-list order (the 0.4-gated branch), not an
            # ANN rank order
            if got:
                node = ms.buffer.get_node(got[0])
                sup = (ms.super_nodes.get(node.parent_id)
                       if node is not None and node.parent_id else None)
                if sup is not None and sup.child_ids[:1] == [got[0]]:
                    fast_hits += 1
        ms.close()
    return {"graph_nodes": nodes, "graph_edges_live": edges,
            "super_nodes": supers,
            "ingest_memories_per_sec": round(nodes / ingest_s, 1),
            "retrieval_p50_ms": round(float(np.percentile(lat, 50)), 4),
            "retrieval_p95_ms": round(float(np.percentile(lat, 95)), 4),
            "super_fast_path_hit_rate": round(fast_hits / QUERIES, 3),
            "auto_consolidations": convs // 3}


def bench_multi_tenant(on_tpu: bool):
    """BASELINE configs[1]: 1,000 tenants sharing one arena (ref analog:
    LanceDB BTREE partitioning on user_id, vector_store.py:55; here the
    tenant is an arena column masked inside the same top-k kernel, so
    isolation costs nothing extra per query). Reports per-tenant search
    p50 across sampled tenants and asserts zero cross-tenant hits."""
    from lazzaro_tpu.core.index import MemoryIndex

    n_t, rows = 1000, 100
    rng = np.random.default_rng(5)
    idx = MemoryIndex(dim=DIM, capacity=n_t * rows + 64)
    t0 = time.perf_counter()
    for t in range(n_t):
        emb = rng.standard_normal((rows, DIM)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        idx.add([f"t{t}:m{i}" for i in range(rows)], emb, [0.5] * rows,
                [0.0] * rows, ["semantic"] * rows, ["default"] * rows,
                f"user{t}")
    fill_s = time.perf_counter() - t0

    sample = rng.integers(0, n_t, size=K_WARM + 30)
    emb_dev = idx.state.emb
    qrows = np.asarray([idx.id_to_row[f"t{t}:m1"] for t in sample])
    queries = np.asarray(emb_dev[jnp.asarray(qrows)], np.float32)
    for i in range(K_WARM):
        idx.search(queries[i], f"user{sample[i]}", k=5)
    lat = []
    violations = 0
    for i in range(K_WARM, len(sample)):
        t0 = time.perf_counter()
        ids, _ = idx.search(queries[i], f"user{sample[i]}", k=5)
        lat.append((time.perf_counter() - t0) * 1e3)
        if not ids or any(not x.startswith(f"t{sample[i]}:") for x in ids):
            violations += 1
    return {"tenants": n_t, "rows_per_tenant": rows,
            "fill_s": round(fill_s, 1),
            "per_tenant_search_p50_ms": round(float(np.percentile(lat, 50)), 4),
            "isolation_violations": violations}


def bench_llm_loop(on_tpu: bool):
    """Consolidation with the LLM stage ON-DEVICE: extract facts from a
    transcript with the in-tree decoder via grammar-constrained JSON
    (models/llm.py generate_json), then run the production ingest. Reports
    facts/sec with the LLM in the loop — BASELINE.md's north-star stage
    (reference analog memory_system.py:651-785, where this is an API call)."""
    import tempfile
    from lazzaro_tpu.core.providers import OnDeviceLLM
    from lazzaro_tpu.models.llm import LanguageModel, LMConfig

    # Default geometry is the compile-cheap "small" even on TPU;
    # BENCH_LLM_GEOMETRY=base2b opts into the 2B geometry explicitly.
    geometry = os.environ.get("BENCH_LLM_GEOMETRY", "small")
    cfg = getattr(LMConfig, geometry)()
    lm = LanguageModel(cfg, seed=0)

    # Raw constrained-decode rate of the extraction call (prefill+decode),
    # timed to the finished host-side string — an honest device sync.
    prompt = ("System: Extract memories as JSON.\nUser: I work on TPU "
              "systems, live in Lisbon, and my dog is named Mika.\nAssistant:")
    # The stem after "content": guarantees a non-degenerate fact even if the
    # (random-weight) model closes the string immediately — the pipeline's
    # >= 5-char content filter would otherwise drop it.
    scaffold = '{"memories": [{"content": "extracted: '
    t0 = time.perf_counter()
    doc = lm.generate_json(prompt, max_new_tokens=64, scaffold=scaffold)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    reps = 3
    gen_bytes = 0
    for _ in range(reps):
        doc = lm.generate_json(prompt, max_new_tokens=64, scaffold=scaffold)
        # honest numerator: bytes actually produced past the forced scaffold
        # (generation can stop early on EOS / grammar completion — assuming
        # the full 64-token budget would overstate the rate)
        gen_bytes += len(doc.encode()) - len(scaffold.encode())
    decode_tok_s = gen_bytes / (time.perf_counter() - t0)
    try:
        json.loads(doc)
        json_valid = True
    except ValueError:
        json_valid = False

    # Schema-scaffolded decode pins the {"memories": [{"content": ...
    # shape, so even random weights yield parseable extraction payloads —
    # the facts/sec number below exercises the REAL pipeline shape with
    # BOTH model stages on device (decoder extraction + encoder embedding):
    # the BASELINE.md north star, "no external API in the loop".
    from lazzaro_tpu.core.providers import EncoderEmbedder
    from lazzaro_tpu.models.encoder import EncoderConfig, TextEncoder

    enc_geometry = "base" if on_tpu else "tiny"
    embedder = EncoderEmbedder(
        TextEncoder(getattr(EncoderConfig, enc_geometry)()))
    # Compile OUTSIDE the timer, in the pow2 batch buckets the pipeline
    # actually hits (encode_batch pads to pow2: 6 facts -> bucket 8; the
    # single-query retrieval path uses bucket 1).
    embedder.batch_embed([f"warmup {i}" for i in range(8)])
    embedder.embed("warmup single")

    class RecordingLLM:
        """Pass-through that keeps the last payload, so the bench can
        report extraction candidates vs nodes surviving dedup (untrained-
        encoder embeddings can legitimately collapse near-identical noise
        strings into one node — that must be visible, not silent)."""

        def __init__(self, inner):
            self.inner = inner
            self.last = None

        def completion(self, messages, response_format=None):
            self.last = self.inner.completion(messages, response_format)
            return self.last

        def completion_stream(self, messages, response_format=None):
            yield self.completion(messages, response_format)

    llm = RecordingLLM(OnDeviceLLM(lm=lm, max_new_tokens=192,
                                   json_scaffold=scaffold))
    with tempfile.TemporaryDirectory() as tmp:
        ms = MemorySystem(
            enable_async=False, auto_consolidate=False, load_from_disk=False,
            db_dir=tmp, llm_provider=llm, embedding_provider=embedder,
            config=MemoryConfig(dtype="bfloat16", journal=False),
            verbose=False)
        ms.start_conversation()
        for i in range(6):
            ms.add_to_short_term(
                f"I am user detail {i}: I work on TPU systems and like hiking.",
                "episodic", 0.7)
        t0 = time.perf_counter()
        ms.end_conversation()            # LLM extract → JSON → full ingest
        dt = time.perf_counter() - t0
        facts = ms.buffer.size()[0]
        try:
            candidates = len(json.loads(llm.last).get("memories", []))
        except (TypeError, ValueError, AttributeError):
            candidates = None
        # BASELINE configs[4]: serving p50 WITH the on-device encoder in
        # the query path (tokenize → encoder forward → arena top-k, no
        # external API anywhere). Distinct strings each rep so no host or
        # embedding cache can short-circuit the encode.
        ms.search_memories("warm the search path 0")
        lat_enc = []
        for i in range(15):
            t0 = time.perf_counter()
            ms.search_memories(f"what does the user work on, rep {i}?")
            lat_enc.append((time.perf_counter() - t0) * 1e3)
        p50_enc = float(np.percentile(lat_enc, 50))
        ms.close()
    return {"geometry": geometry, "encoder_geometry": enc_geometry,
            "p50_search_with_encoder_ms": round(p50_enc, 2),
            "json_valid": json_valid,
            "constrained_decode_tok_per_sec": round(decode_tok_s, 1),
            "first_call_compile_s": round(compile_s, 1),
            "extraction_candidates": candidates,
            "facts_in_graph": int(facts),
            "llm_loop_facts_per_sec": round(facts / dt, 3) if facts else 0.0,
            "llm_loop_total_s": round(dt, 2)}


def main():
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    on_tpu = backend.on_tpu()
    # BENCH_WORKDIR is the caller's to keep (ingest once, re-run
    # search-only); without it the run owns a fixed directory under the
    # checkout and starts it empty — nothing left there by an earlier run
    # is reused.
    persist = bool(os.environ.get("BENCH_WORKDIR"))
    workdir = os.environ.get("BENCH_WORKDIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_workdir")
    if not persist:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    # Per-(size, dim) db + progress marker: a smaller run can never
    # clobber the expensive 1M artifact, and the marker records
    # convs_done after EVERY conversation so an interrupted or
    # budget-truncated ingest RESUMES instead of restarting (each
    # end_conversation already delta-saved the graph).
    # "g2" = corpus-generator version (clustered embeddings + near-dups):
    # a workdir ingested under the old near-orthogonal generator must never
    # be mistaken for this corpus.
    db_dir = os.path.join(workdir, f"db_{TOTAL}_{DIM}_g2")
    marker = os.path.join(workdir, f"INGESTED_{TOTAL}_{DIM}_g2")

    def write_marker(convs_done, t_ingest, edges_linked_cum):
        tmp = marker + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"convs_done": convs_done,
                       "t_ingest": round(t_ingest, 3),
                       "edges_linked": edges_linked_cum}, f)
        os.replace(tmp, marker)

    # --- ingest: the full end_conversation pipeline at TOTAL facts --------
    ingest_truncated = False
    prior_edges_linked = 0
    saved = {}
    if os.path.exists(marker):
        with open(marker) as f:
            saved = json.load(f)
    elif os.path.exists(db_dir):
        # db without a marker = state from a crashed pre-marker run; the
        # last-wins-by-id merge would silently blend graphs. Start clean.
        import shutil
        print(f"[bench] wiping unmarked db_dir {db_dir}", file=sys.stderr,
              flush=True)
        shutil.rmtree(db_dir)

    start_conv = min(int(saved.get("convs_done", 0)), CONVS)
    t_ingest = float(saved.get("t_ingest", 0)) if start_conv else 0.0
    prior_edges_linked = int(saved.get("edges_linked", 0))
    if start_conv:
        print(f"[bench] reusing ingested graph in {db_dir} "
              f"({start_conv}/{CONVS} convs done)", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        ms = build_system(db_dir, load_from_disk=True, first_conv=start_conv)
        print(f"[bench] reload took {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    else:
        ms = build_system(db_dir, first_conv=0)
    convs_done = start_conv
    t_this_run = 0.0       # the budget bounds THIS process's wall-clock —
    for c in range(start_conv, CONVS):   # resumes get a fresh budget
        ms.start_conversation()
        ms.add_to_short_term(f"conversation {c} transcript", "episodic", 0.7)
        t0 = time.perf_counter()
        ms.end_conversation()
        dt = time.perf_counter() - t0
        t_ingest += dt
        t_this_run += dt
        convs_done = c + 1
        if persist:
            write_marker(convs_done, t_ingest,
                         ms.metrics.get("edges_linked", 0) + prior_edges_linked)
        if convs_done % 20 == 0 or convs_done == CONVS:
            # liveness to stderr only — stdout stays ONE JSON line
            print(f"[bench] conv {convs_done}/{CONVS}, "
                  f"{convs_done * FACTS_PER_CONV / t_ingest:.0f} facts/s, "
                  f"{t_ingest:.0f}s elapsed",
                  file=sys.stderr, flush=True)
        if t_this_run > INGEST_BUDGET_S and convs_done < CONVS:
            ingest_truncated = True
            print(f"[bench] ingest budget {INGEST_BUDGET_S:.0f}s exhausted "
                  f"at {convs_done}/{CONVS} convs — benching at the size "
                  f"reached (resumable: marker records progress)",
                  file=sys.stderr, flush=True)
            break
    nodes, edges = ms.buffer.size()
    edges_linked = ms.metrics.get("edges_linked", 0) + prior_edges_linked
    ingest_per_s = nodes / t_ingest if t_ingest else None
    n_facts = convs_done * FACTS_PER_CONV
    # facts the dedup-merge path absorbed instead of inserting (the seeded
    # ~1% near-duplicates): proof the merge path ran in the measured ingest
    merged_at_ingest = max(0, n_facts - nodes)

    # --- headline: search_memories p50/p95 through the orchestrator ------
    t_search_phase = time.perf_counter()
    rng = np.random.default_rng(99)
    # near-duplicate facts merged at ingest have no node of their own — an
    # exact-hit probe on one would top-1 its 0.97-cosine twin and misread
    # as a miss, so probes sample the non-duplicate indices only
    probe = rng.integers(0, n_facts, size=2 * (K_WARM + QUERIES))
    probe = probe[~((probe % DUP_EVERY) == DUP_EVERY - 1)][:K_WARM + QUERIES]
    for i in range(K_WARM):
        ms.search_memories(f"fact {probe[i]}: user detail number {probe[i]}")
    lat = []
    hits_ok = 0
    for i in range(K_WARM, K_WARM + QUERIES):
        q = f"fact {probe[i]}: user detail number {probe[i]}"
        t0 = time.perf_counter()
        hits = ms.search_memories(q)     # decodes ids to numpy = real sync
        lat.append((time.perf_counter() - t0) * 1e3)
        if hits and hits[0].content.startswith(f"fact {probe[i]}:"):
            hits_ok += 1
    p50 = float(np.percentile(lat, 50))
    p95 = float(np.percentile(lat, 95))

    # Same surface with the int8 serving shadow on (exact master retained
    # for consolidation; single-chip only — the headline above stays exact).
    p50_int8 = None
    if ms.mesh is None:
        ms.index.int8_serving = True
        for i in range(K_WARM):          # warm + build the shadow
            ms.search_memories(f"fact {probe[i]}: user detail number {probe[i]}")
        lat8 = []
        for i in range(K_WARM, K_WARM + QUERIES):
            q = f"fact {probe[i]}: user detail number {probe[i]}"
            t0 = time.perf_counter()
            ms.search_memories(q)
            lat8.append((time.perf_counter() - t0) * 1e3)
        p50_int8 = float(np.percentile(lat8, 50))
        ms.index.int8_serving = False
        # drop the ~0.77 GB quantized shadow before consolidation and the
        # kernel section allocate their own arenas
        ms.index._int8_shadow = None
        ms.index._int8_dirty = True

    # And once more through the IVF coarse stage (centroid prefilter +
    # member gather, ops/ivf.py). TPU only: the k-means build over the
    # full arena is pointless wall-clock on a CPU.
    p50_ivf = None
    ivf_build_s = None
    p50_pq = None
    pq_recall = None
    pq_build_s = None
    if ms.mesh is None and on_tpu:
        ms.index.ivf_nprobe = 8
        t0 = time.perf_counter()
        built = ms.index.ivf_maintenance()   # explicit build (background-
        ivf_build_s = time.perf_counter() - t0   # maintenance analog)
        if not built:
            # arena below the build threshold: searches would silently fall
            # through to the exact path — labeling those latencies "IVF"
            # would be exactly the mislabeling this bench exists to prevent
            ivf_build_s = None
        else:
            for i in range(K_WARM):
                ms.search_memories(
                    f"fact {probe[i]}: user detail number {probe[i]}")
            lat_ivf = []
            ivf_hits = 0
            for i in range(K_WARM, K_WARM + QUERIES):
                q = f"fact {probe[i]}: user detail number {probe[i]}"
                t0 = time.perf_counter()
                hits = ms.search_memories(q)
                lat_ivf.append((time.perf_counter() - t0) * 1e3)
                if hits and hits[0].content.startswith(f"fact {probe[i]}:"):
                    ivf_hits += 1
            p50_ivf = float(np.percentile(lat_ivf, 50))
            ivf_recall = ivf_hits / QUERIES

            # IVF-PQ over the SAME coarse build: m-byte member scan +
            # exact shortlist refine (ops/pq.py). Train+encode timed to a
            # forced readback, SEPARATE from the warm-up call (whose first
            # dispatch pays the kernel compile — not a build cost).
            from lazzaro_tpu.ops.pq import encode_pq, train_pq
            t0 = time.perf_counter()
            book = train_pq(ms.index.state.emb,
                            np.asarray(ms.index.state.alive))
            codes = encode_pq(book.centroids, ms.index.state.emb)
            np.asarray(codes[:1])
            pq_build_s = time.perf_counter() - t0
            ms.index._pq_pack = (book, codes)
            ms.index.pq_serving = True
            ms.search_memories(      # warm/compile outside every timer
                f"fact {probe[0]}: user detail number {probe[0]}")
            lat_pq = []
            pq_hits = 0
            for i in range(K_WARM, K_WARM + QUERIES):
                q = f"fact {probe[i]}: user detail number {probe[i]}"
                t0 = time.perf_counter()
                hits = ms.search_memories(q)
                lat_pq.append((time.perf_counter() - t0) * 1e3)
                if hits and hits[0].content.startswith(f"fact {probe[i]}:"):
                    pq_hits += 1
            p50_pq = float(np.percentile(lat_pq, 50))
            pq_recall = pq_hits / QUERIES
            ms.index.pq_serving = False
            ms.index._pq_pack = None     # free book + codes
        ms.index.ivf_nprobe = 0
        ms.index._ivf = None             # free members/centroids/residual
        ms.index._ivf_res_cache = None

    # --- fleet serving: batched query path through the orchestrator ------
    # One dispatch serves the whole batch, so throughput scales with batch
    # size: measure 64 and 512.
    batch_qps = {}
    if hasattr(ms, "search_memories_batch"):
        for bsz, reps in ((64, 5), (512, 3)):
            qb = [f"fact {j}: user detail number {j}"
                  for j in rng.integers(0, n_facts, size=bsz)]
            ms.search_memories_batch(qb)      # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                ms.search_memories_batch(qb)  # returns host nodes = real sync
            batch_qps[bsz] = reps * bsz / (time.perf_counter() - t0)
    t_search_phase = time.perf_counter() - t_search_phase

    # --- deep consolidation at full scale: the chunked all-pairs merge ---
    # (the merge stage must be exercised AT the bench size, not only in
    # the 100k test). Facts are unique vectors, so this measures
    # the full [N, N]-semantics scan without mutating the graph.
    t_consolidation = None
    consolidation_msg = None
    want_consolidate = os.environ.get("BENCH_CONSOLIDATE", "1") != "0"
    if want_consolidate and not on_tpu and nodes > 50_000:
        # the all-pairs merge scan is ~N²·d FLOPs — fine on the MXU at 1M
        # (~15 s), ~hours on a single-core CPU. Skipping is reported,
        # never silent.
        consolidation_msg = (f"skipped: all-pairs merge at {nodes} nodes "
                             f"is TPU-only (this run is on a CPU)")
        want_consolidate = False
    if want_consolidate:
        t0 = time.perf_counter()
        # persist=False: the reusable BENCH_WORKDIR artifact must not
        # accumulate consolidation mutations across repeated runs
        consolidation_msg = ms.run_consolidation(persist=False)
        t_consolidation = time.perf_counter() - t0

    # The scan streams the FULL allocated arena (capacity+1 rows), not just
    # the live nodes — a truncated ingest still pays full-capacity HBM
    # traffic, and the roofline denominator must reflect that or the
    # suspect flag understates implied bandwidth.
    arena_rows = ms.index.state.emb.shape[0]
    # ISSUE 6: the system registry's view of the whole measured run —
    # pad-waste / batch-occupancy (the ragged-serving before-number),
    # queue-wait percentiles, device counters — captured before close()
    sys_telemetry = _telemetry_block(ms.telemetry)
    ms.close()

    # Snapshot the measurements gathered so far to stderr + a sidecar file:
    # if an external window kills this process during the remaining stages
    # (kernel A/Bs, the multi-minute LLM compile), the captured artifact's
    # stderr tail still carries every system-level number instead of
    # losing the whole run.
    partial = {
        "partial": True, "p50_ms": round(p50, 4), "p95_ms": round(p95, 4),
        "p50_int8_serving_ms": p50_int8, "p50_ivf_serving_ms": p50_ivf,
        "exact_hit_rate": hits_ok / QUERIES, "graph_nodes": nodes,
        "ingest_total_s": round(t_ingest, 1),
        "batched_search_qps": {str(b): round(v, 1)
                               for b, v in batch_qps.items()},
        "deep_consolidation_s": (round(t_consolidation, 1)
                                 if t_consolidation is not None else None),
    }
    print(f"[bench] partial results: {json.dumps(partial)}",
          file=sys.stderr, flush=True)
    partial_path = os.path.join(workdir, f"bench_partial_{TOTAL}_{DIM}.json")
    try:
        with open(partial_path, "w") as f:
            json.dump(partial, f)
    except OSError:
        pass

    t_kernel_phase = time.perf_counter()
    (kernel_p50s, batch64_ms, int8_batch64_ms, kernel_rows,
     scatter_rows, scatter_copy_rows) = bench_kernels(on_tpu)
    try:
        fused_ingest_rate = bench_fused_ingest(on_tpu)
    except Exception as e:   # a failed extra stage must not void the run
        print(f"[bench] fused-ingest stage failed: {e}", file=sys.stderr,
              flush=True)
        fused_ingest_rate = None
    try:
        fused_retrieval = bench_fused_retrieval(on_tpu)
    except Exception as e:   # a failed extra stage must not void the run
        print(f"[bench] fused-retrieval stage failed: {e}", file=sys.stderr,
              flush=True)
        fused_retrieval = None
    try:
        # quantized fused serving A/B at a side size that fits any driver
        # window; the full 256k/1M pair ships via BENCH_FUSED_QUANT runs
        # (bench_artifacts/pr3_fused_quant_*.json)
        fused_quant = bench_fused_quant(on_tpu, min(N, 65_536),
                                        edge_rows=20_000)
    except Exception as e:   # a failed extra stage must not void the run
        print(f"[bench] fused-quant stage failed: {e}", file=sys.stderr,
              flush=True)
        fused_quant = None
    try:
        # fused-IVF serving A/B at a side size; the full 256k/1M pair
        # ships via BENCH_FUSED_IVF runs (bench_artifacts/
        # pr4_fused_ivf_*.json)
        fused_ivf = bench_fused_ivf(on_tpu, min(N, 65_536),
                                    edge_rows=20_000)
    except Exception as e:   # a failed extra stage must not void the run
        print(f"[bench] fused-ivf stage failed: {e}", file=sys.stderr,
              flush=True)
        fused_ivf = None
    t_kernel_phase = time.perf_counter() - t_kernel_phase

    # Reference-default configuration (hierarchy + auto-consolidate ON) as
    # a measured side variant; BENCH_REFDEFAULT=0 skips (e.g. ingest-only
    # prebuild runs).
    ref_default = None
    if os.environ.get("BENCH_REFDEFAULT", "1") != "0":
        print("[bench] reference-default stage starting", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        try:
            ref_default = bench_reference_default(on_tpu)
        except Exception as e:   # a failed extra stage must not void the run
            ref_default = {"error": f"{type(e).__name__}: {e}"[:300]}
        ref_default["stage_total_s"] = round(time.perf_counter() - t0, 1)

    # 1k-tenant serving stage (BASELINE configs[1]); BENCH_TENANTS=0 skips.
    tenants = None
    if os.environ.get("BENCH_TENANTS", "1") != "0":
        print("[bench] multi-tenant stage starting", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        try:
            tenants = bench_multi_tenant(on_tpu)
        except Exception as e:
            tenants = {"error": f"{type(e).__name__}: {e}"[:300]}
        tenants["stage_total_s"] = round(time.perf_counter() - t0, 1)

    # LLM-in-the-loop stage (BASELINE.md north star): ON by default on a
    # healthy TPU; set BENCH_LLM_LOOP=0 to skip, =1 to force (e.g. on CPU).
    llm_loop = None
    llm_flag = os.environ.get("BENCH_LLM_LOOP", "").strip().lower()
    force_on = llm_flag in ("1", "true", "yes", "on")
    force_off = llm_flag in ("0", "false", "no", "off")
    if force_on or (not force_off and on_tpu):
        print("[bench] LLM-loop stage starting", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        try:
            llm_loop = bench_llm_loop(on_tpu)
        except Exception as e:   # a failed extra stage must not void the run
            llm_loop = {"error": f"{type(e).__name__}: {e}"[:300]}
        llm_loop["stage_total_s"] = round(time.perf_counter() - t0, 1)

    # --- roofline self-check: impossible numbers must flag themselves ----
    rl_headline = _roofline(arena_rows, DIM, 2, p50, 1, on_tpu)
    rl_xla = _roofline(kernel_rows, DIM, 2, kernel_p50s["xla"], 1, on_tpu)
    rl = {"headline_search": rl_headline, "arena_search_xla": rl_xla,
          "arena_search_batch64": _roofline(kernel_rows, DIM, 2, batch64_ms,
                                            64, on_tpu)}
    if "pallas" in kernel_p50s:
        rl["arena_search_pallas"] = _roofline(kernel_rows, DIM, 2,
                                              kernel_p50s["pallas"], 1, on_tpu)
    # int8 shadow scans HALF the bytes per row (dtype_bytes=1)
    rl["arena_search_int8"] = _roofline(kernel_rows, DIM, 1,
                                        kernel_p50s["int8"], 1, on_tpu)
    rl["arena_search_int8_batch64"] = _roofline(kernel_rows, DIM, 1,
                                                int8_batch64_ms, 64, on_tpu)
    for bsz, qps in batch_qps.items():
        rl[f"batched_search_qps_{bsz}"] = _roofline(
            arena_rows, DIM, 2, bsz * 1000.0 / qps, bsz, on_tpu)
    suspect = any(v.get("suspect") for v in rl.values())

    size_tag = "1M" if nodes >= 1_000_000 else f"{nodes // 1000}k"
    out = {
        "metric": f"search_memories_p50_latency_{size_tag}_nodes",
        "value": round(p50, 4),
        "unit": "ms",
        "vs_baseline": round(100.0 / p50, 2),   # reference bar: <100ms ⚡ tier
        "roofline_suspect": suspect,
        "telemetry": sys_telemetry,
        "extra": {
            "p95_ms": round(p95, 4),
            "p50_int8_serving_ms": (round(p50_int8, 4)
                                    if p50_int8 is not None else None),
            "p50_ivf_serving_ms": (round(p50_ivf, 4)
                                   if p50_ivf is not None else None),
            "ivf_build_s": (round(ivf_build_s, 2)
                            if ivf_build_s is not None else None),
            "ivf_exact_hit_rate": (round(ivf_recall, 3)
                                   if p50_ivf is not None else None),
            "p50_ivf_pq_serving_ms": (round(p50_pq, 4)
                                      if p50_pq is not None else None),
            "ivf_pq_exact_hit_rate": (round(pq_recall, 3)
                                      if pq_recall is not None else None),
            "ivf_pq_train_encode_s": (round(pq_build_s, 2)
                                      if pq_build_s is not None else None),
            "exact_hit_rate": round(hits_ok / QUERIES, 3),
            "ingest_pipeline_memories_per_sec_per_chip": (
                round(ingest_per_s, 1) if ingest_per_s else None),
            "ingest_total_s": round(t_ingest, 1),
            "ingest_truncated_at_budget": ingest_truncated,
            "graph_nodes": nodes,
            "graph_edges_live": edges,     # group links outlive decay+prune
            "edges_linked_total": edges_linked,
            "ingest_merged_duplicates": merged_at_ingest,
            "bench_graph": {"group_size": GROUP, "n_topics": N_TOPICS,
                            "dup_every": DUP_EVERY,
                            "intra_group_cos": 0.88, "dup_cos": 0.97},
            "batched_search_qps_64": (round(batch_qps[64], 1)
                                      if 64 in batch_qps else None),
            "batched_search_qps_512": (round(batch_qps[512], 1)
                                       if 512 in batch_qps else None),
            # raw kernels, honest names — NOT the system metrics:
            "arena_search_xla_p50_ms": round(kernel_p50s["xla"], 4),
            "arena_search_pallas_p50_ms": (
                round(kernel_p50s["pallas"], 4)
                if "pallas" in kernel_p50s else None),
            "arena_search_batch64_ms": round(batch64_ms, 4),
            "arena_search_int8_p50_ms": round(kernel_p50s["int8"], 4),
            "arena_search_int8_batch64_ms": round(int8_batch64_ms, 4),
            # donated (in-place) scatter vs the pre-donation copying twin —
            # the zero-copy win, tracked per round:
            "arena_scatter_rows_per_sec": round(scatter_rows, 1),
            "arena_scatter_donated_rows_per_sec": round(scatter_rows, 1),
            "arena_scatter_copy_rows_per_sec": round(scatter_copy_rows, 1),
            # fused single-dispatch ingest (scatter + merge touch + 2-mode
            # link scan + gated edge insert per 1024-fact batch):
            "ingest_fused_memories_per_sec_per_chip": (
                round(fused_ingest_rate, 1)
                if fused_ingest_rate is not None else None),
            # fused single-dispatch serving vs the classic multi-dispatch
            # chat-turn sequence, batch 64 (ISSUE 2 A/B; rooflines inside):
            "fused_retrieval_qps": (
                fused_retrieval["fused_retrieval_qps"]
                if fused_retrieval is not None else None),
            "fused_retrieval_ab": fused_retrieval,
            # quantized fused serving (int8 coarse scan + exact rescore in
            # the single dispatch) vs fused bf16 and the classic int8
            # sequence (ISSUE 3; the 256k/1M artifacts ride
            # bench_artifacts/pr3_fused_quant_*.json):
            "fused_quant_retrieval_qps": (
                fused_quant["fused_quant_retrieval_qps"]
                if fused_quant is not None else None),
            "fused_quant_ab": fused_quant,
            # fused IVF serving (centroid prefilter + member gather inside
            # the single dispatch) vs the classic multi-dispatch IVF path
            # and the dense fused-quant scan (ISSUE 4; the 256k/1M
            # artifacts ride bench_artifacts/pr4_fused_ivf_*.json):
            "fused_ivf_retrieval_qps": (
                fused_ivf["fused_ivf_retrieval_qps"]
                if fused_ivf is not None else None),
            "fused_ivf_ab": fused_ivf,
            "roofline": rl,
            "phase_s": {"ingest": round(t_ingest, 1),
                        "search": round(t_search_phase, 1),
                        "deep_consolidation": (
                            round(t_consolidation, 1)
                            if t_consolidation is not None else None),
                        "kernels": round(t_kernel_phase, 1),
                        "total_wall": round(time.perf_counter() - t_start, 1)},
            # the summary lines (merge/prune/profile counts) come LAST in
            # run_consolidation's report — keep the tail, not the head
            "consolidation_result": ("; ".join(
                (consolidation_msg or "").splitlines()[-3:])[:240] or None),
            "reference_default": ref_default,
            "multi_tenant": tenants,
            "llm_loop": llm_loop,
            "dim": DIM,
            "dtype": "bfloat16",
            "llm_stage": "queued-canned (deterministic, zero-egress)",
            "device": str(dev),
        },
    }
    # the run completed: retire the crash-salvage sidecar so a stale
    # partial can never be attributed to a later killed run
    try:
        os.unlink(partial_path)
    except OSError:
        pass
    print(json.dumps(out))


def fused_quant_stage_main():
    """Standalone quantized-serving A/B (BENCH_FUSED_QUANT=<rows,rows,...>
    or =1 for the ISSUE 3 pair 262144,1048576): runs ONLY the fused-quant
    stage and writes bench_artifacts/pr3_fused_quant_<size>_<dev>.json.
    Separate from main() so the multi-hour 1M ingest pipeline isn't a
    prerequisite for the serving artifact."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_FUSED_QUANT", "1")
    sizes = ([262_144, 1_048_576] if spec.strip() in ("", "1")
             else [int(s) for s in spec.split(",") if s.strip()])
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    results = {}
    for rows in sizes:
        print(f"[bench] fused-quant stage at {rows} rows", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        out = bench_fused_quant(on_tpu, rows)
        out["stage_total_s"] = round(time.perf_counter() - t0, 1)
        size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
        results[size_tag] = out
        path = os.path.join(art_dir,
                            f"pr3_fused_quant_{size_tag}_{dev_tag}.json")
        with open(path, "w") as f:
            json.dump({"metric": "fused_quant_retrieval_qps",
                       "value": out["fused_quant_retrieval_qps"],
                       "unit": "qps", "device": dev_tag, "sizes": results},
                      f, indent=1)
        print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "fused_quant_retrieval_qps",
                      "sizes": results}))


def fused_ivf_stage_main():
    """Standalone fused-IVF A/B (BENCH_FUSED_IVF=<rows,rows,...> or =1 for
    the ISSUE 4 pair 262144,1048576): runs ONLY the fused-IVF stage and
    writes bench_artifacts/pr4_fused_ivf_<size>_<dev>.json. Separate from
    main() so the multi-hour 1M ingest pipeline isn't a prerequisite for
    the serving artifact."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_FUSED_IVF", "1")
    sizes = ([262_144, 1_048_576] if spec.strip() in ("", "1")
             else [int(s) for s in spec.split(",") if s.strip()])
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    for rows in sizes:
        print(f"[bench] fused-ivf stage at {rows} rows", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        out = bench_fused_ivf(on_tpu, rows)
        out["stage_total_s"] = round(time.perf_counter() - t0, 1)
        size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
        path = os.path.join(art_dir,
                            f"pr4_fused_ivf_{size_tag}_{dev_tag}.json")
        with open(path, "w") as f:
            json.dump({"metric": "fused_ivf_retrieval_qps",
                       "value": out["fused_ivf_retrieval_qps"],
                       "unit": "qps", "device": dev_tag,
                       "sizes": {size_tag: out}}, f, indent=1)
        print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
        print(json.dumps({"metric": "fused_ivf_retrieval_qps",
                          "sizes": {size_tag: out}}))


def fused_sharded_stage_main():
    """Standalone pod-serving A/B (BENCH_FUSED_SHARDED=<rows[,rows...]> or
    =1 for the ISSUE 5 size 262144): runs ONLY the fused-sharded stage on
    an n-way host-device mesh and writes
    bench_artifacts/pr5_fused_sharded_<size>_<dev>.json. On CPU run with
    XLA_FLAGS=--xla_force_host_platform_device_count=<n> (the stage warns
    and shrinks the mesh otherwise). BENCH_SHARDED_PARTS picks the mesh
    width (default 4)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_FUSED_SHARDED", "1")
    sizes = ([262_144] if spec.strip() in ("", "1")
             else [int(s) for s in spec.split(",") if s.strip()])
    n_parts = int(os.environ.get("BENCH_SHARDED_PARTS", "4"))
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    for rows in sizes:
        print(f"[bench] fused-sharded stage at {rows} rows, {n_parts}-way",
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        out = bench_fused_sharded(on_tpu, rows, n_parts=n_parts)
        out["stage_total_s"] = round(time.perf_counter() - t0, 1)
        size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
        path = os.path.join(art_dir,
                            f"pr5_fused_sharded_{size_tag}_{dev_tag}.json")
        with open(path, "w") as f:
            json.dump({"metric": "fused_sharded_retrieval_qps",
                       "value": out["fused_sharded_retrieval_qps"],
                       "unit": "qps", "device": dev_tag,
                       "sizes": {size_tag: out}}, f, indent=1)
        print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
        print(json.dumps({"metric": "fused_sharded_retrieval_qps",
                          "sizes": {size_tag: out}}))


def sharded_ingest_stage_main():
    """Standalone pod-ingest A/B (BENCH_SHARDED_INGEST=<rows[,rows...]> or
    =1 for the ISSUE 9 size 262144): runs ONLY the sharded-ingest stage on
    an n-way host-device mesh and writes
    bench_artifacts/pr9_sharded_ingest_<size>_<dev>.json. On CPU run with
    XLA_FLAGS=--xla_force_host_platform_device_count=<n> (the stage warns
    and shrinks the mesh otherwise). BENCH_SHARDED_PARTS picks the mesh
    width (default 4); BENCH_INGEST_BATCH the mega-batch size (default
    1024)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_SHARDED_INGEST", "1")
    sizes = ([262_144] if spec.strip() in ("", "1")
             else [int(s) for s in spec.split(",") if s.strip()])
    n_parts = int(os.environ.get("BENCH_SHARDED_PARTS", "4"))
    batch = int(os.environ.get("BENCH_INGEST_BATCH", "1024"))
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    for rows in sizes:
        print(f"[bench] sharded-ingest stage at {rows} rows, {n_parts}-way,"
              f" batch {batch}", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        out = bench_sharded_ingest(on_tpu, rows, n_parts=n_parts,
                                   batch=batch)
        out["stage_total_s"] = round(time.perf_counter() - t0, 1)
        size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
        path = os.path.join(art_dir,
                            f"pr9_sharded_ingest_{size_tag}_{dev_tag}.json")
        with open(path, "w") as f:
            json.dump({"metric": "sharded_ingest_memories_per_sec",
                       "value": out["sharded_ingest_memories_per_sec"],
                       "unit": "memories/s", "device": dev_tag,
                       "sizes": {size_tag: out}}, f, indent=1)
        print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
        print(json.dumps({"metric": "sharded_ingest_memories_per_sec",
                          "sizes": {size_tag: {
                              k: v for k, v in out.items()
                              if k not in ("telemetry",
                                           "peak_hbm_gauges")}}}))


def online_ivf_stage_main():
    """Standalone online-IVF acceptance stage (BENCH_ONLINE_IVF=<rows> or
    =1 for the default 65536): sustained clustered churn with in-dispatch
    IVF maintenance vs the offline-rebuild baseline, serving latency
    sampled throughout; writes
    bench_artifacts/pr12_online_ivf_<size>_<dev>.json — gated in CI by
    scripts/check_dispatch_counts.py (dispatches_per_conversation == 1,
    recall floor, assignment staleness ≤ 0.02). BENCH_ONLINE_IVF_ROUNDS /
    BENCH_INGEST_BATCH tune the churn stream."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_ONLINE_IVF", "1")
    rows = 65_536 if spec.strip() in ("", "1") else int(spec)
    rounds = int(os.environ.get("BENCH_ONLINE_IVF_ROUNDS", "6"))
    batch = int(os.environ.get("BENCH_INGEST_BATCH", "256"))
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] online-ivf stage at {rows} rows, {rounds} rounds x "
          f"batch {batch}", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    out = bench_online_ivf(on_tpu, rows, rounds=rounds, batch=batch)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
    path = os.path.join(art_dir,
                        f"pr12_online_ivf_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "online_ingest_memories_per_sec",
                   "value": out["online_ingest_memories_per_sec"],
                   "unit": "memories/s", "device": dev_tag,
                   "sizes": {size_tag: out}}, f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "online_ingest_memories_per_sec",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",)}}}))


def fused_pq_stage_main():
    """Standalone fused-PQ A/B (BENCH_FUSED_PQ=<rows,rows,...> or =1 for
    the default 262144): the ISSUE 16 acceptance stage — fused single-
    dispatch IVF-PQ serving vs the classic multi-dispatch ``pq_serving``
    sequence it retires, plus the incremental-code ingest conversations;
    writes bench_artifacts/pr16_fused_pq_<size>_<dev>.json, gated in CI
    by scripts/check_dispatch_counts.py (``"pq_fused": true`` →
    dispatches_per_turn == 1, recall floor, bytes_per_row < int8's) and
    swept by scripts/check_hbm_budget.py via the pq="true" gauges in the
    embedded telemetry block."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_FUSED_PQ", "1")
    sizes = ([262_144] if spec.strip() in ("", "1")
             else [int(s) for s in spec.split(",") if s.strip()])
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    for rows in sizes:
        print(f"[bench] fused-pq stage at {rows} rows", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        out = bench_fused_pq(on_tpu, rows)
        out["stage_total_s"] = round(time.perf_counter() - t0, 1)
        size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
        path = os.path.join(art_dir,
                            f"pr16_fused_pq_{size_tag}_{dev_tag}.json")
        with open(path, "w") as f:
            json.dump({"metric": "fused_pq_retrieval_qps",
                       "value": out["fused_pq_retrieval_qps"],
                       "unit": "qps", "device": dev_tag,
                       "sizes": {size_tag: out}}, f, indent=1)
        print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
        print(json.dumps({"metric": "fused_pq_retrieval_qps",
                          "sizes": {size_tag: {
                              k: v for k, v in out.items()
                              if k not in ("telemetry",)}}}))


def tiered_stage_main():
    """Standalone tiered-memory acceptance stage (BENCH_TIERED=<rows> or
    =1 for the default 65536): serves a corpus 4× the hot-row budget
    through the two-tier stack (watermark-policy demotion, hot-only
    1-dispatch probe, cold ≤2-dispatch probe, recall vs exact ground
    truth, pump-overlap p95) and writes
    bench_artifacts/pr8_tiered_<size>_<dev>.json. BENCH_TIERED_BUDGET
    overrides the hot budget (default rows // 4)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_TIERED", "1")
    rows = 65_536 if spec.strip() in ("", "1") else int(spec)
    budget = int(os.environ.get("BENCH_TIERED_BUDGET", "0")) or rows // 4
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] tiered-memory stage at {rows} rows, hot budget "
          f"{budget}", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    out = bench_tiered_serving(on_tpu, rows, hot_budget=budget)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
    path = os.path.join(art_dir, f"pr8_tiered_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "tiered_hot_qps",
                   "value": out["tiered_hot_qps"], "unit": "qps",
                   "device": dev_tag, "sizes": {size_tag: out}},
                  f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "tiered_hot_qps",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",)}}}))


def paged_arena_stage_main():
    """Standalone paged-arena acceptance stage (BENCH_PAGED_ARENA=<rows>
    or =1 for the default 16384): dense-vs-paged serving QPS + dispatch
    count, watermark-demote page reclamation, copy-free growth, and the
    planner's paged resident-bytes prediction. Writes
    bench_artifacts/pr17_paged_arena_<size>_<dev>.json (gated in CI by
    scripts/check_hbm_budget.py and check_dispatch_counts.py)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_PAGED_ARENA", "1")
    rows = 16_384 if spec.strip() in ("", "1") else int(spec)
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] paged-arena stage at {rows} rows", file=sys.stderr,
          flush=True)
    t0 = time.perf_counter()
    out = bench_paged_arena(on_tpu, rows)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
    path = os.path.join(art_dir,
                        f"pr17_paged_arena_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "paged_qps_ratio",
                   "value": out["paged_qps_ratio"], "unit": "x",
                   "device": dev_tag, "sizes": {size_tag: out}},
                  f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "paged_qps_ratio",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",)}}}))


def lifecycle_stage_main():
    """Standalone lifecycle acceptance stage (BENCH_LIFECYCLE=<rows> or
    =1 for the default 8192): all-tenant decay+prune+archive as ONE fused
    sweep under a live serving thread — serve-p99 ratio vs the
    maintenance-free baseline, host-stall speedup vs the classic
    per-tenant loop, the counted one-dispatch sweep, and the bit-parity
    flag. Writes bench_artifacts/pr19_lifecycle_<size>_<dev>.json (gated
    in CI by scripts/check_dispatch_counts.py, swept by
    check_hbm_budget.py via the path="lifecycle" gauges).
    BENCH_LIFECYCLE_TENANTS picks the tenant count (default 16)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_LIFECYCLE", "1")
    rows = 8_192 if spec.strip() in ("", "1") else int(spec)
    tenants = int(os.environ.get("BENCH_LIFECYCLE_TENANTS", "16"))
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] lifecycle stage at {rows} rows, {tenants} tenants",
          file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    out = bench_lifecycle(on_tpu, rows, tenants=tenants)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
    path = os.path.join(art_dir,
                        f"pr19_lifecycle_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "lifecycle_host_stall_speedup",
                   "value": out["host_stall_speedup"], "unit": "x",
                   "device": dev_tag, "sizes": {size_tag: out}},
                  f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "lifecycle_host_stall_speedup",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",)}}}))


def semantic_cache_stage_main():
    """Standalone semantic-cache acceptance stage (BENCH_SEMANTIC_CACHE=
    <rows> or =1 for the default 65536): a Zipf(s≈1.1) multi-tenant
    repeated-intent workload with near-dup paraphrase mass, served with
    the similarity ring ON vs OFF — measured dispatches_per_turn (must
    stay 1.0), semantic hit rate, QPS speedup vs the cache-off twin,
    stale_hits under ingest/delete churn (must be 0), miss-population
    bit-parity, and recall@10 of hit-served turns. Writes
    bench_artifacts/pr20_semantic_cache_<size>_<dev>.json (gated in CI
    by scripts/check_dispatch_counts.py, swept by check_hbm_budget.py
    via the ring-geometry HBM model). BENCH_SEMANTIC_TENANTS picks the
    tenant count (default 4, the ISSUE floor)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_SEMANTIC_CACHE", "1")
    rows = 65_536 if spec.strip() in ("", "1") else int(spec)
    tenants = int(os.environ.get("BENCH_SEMANTIC_TENANTS", "4"))
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] semantic-cache stage at {rows} rows, {tenants} "
          f"tenants", file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    out = bench_semantic_cache(on_tpu, rows, tenants=tenants)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows // 1024}k"
    path = os.path.join(art_dir,
                        f"pr20_semantic_cache_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "semantic_cache_speedup",
                   "value": out["semantic_vs_off_speedup"], "unit": "x",
                   "device": dev_tag, "sizes": {size_tag: out}},
                  f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "semantic_cache_speedup",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",
                                       "baseline_telemetry")}}}))


def replica_stage_main():
    """Standalone replica-serving acceptance stage (BENCH_REPLICA=<rows>
    or =1 for the default 512): aggregate routed QPS over 1→2→4 replica
    groups of the 8-device CPU mesh, recall / staleness / crash-replay
    freshness cells, and the measured one-dispatch-per-routed-turn
    count. Writes bench_artifacts/pr18_replica_serving_<size>_<dev>.json
    (gated in CI by scripts/check_dispatch_counts.py and swept by
    check_hbm_budget.py via the replica_groups geometry label).
    BENCH_REPLICA_DIM pins the serving dim (default min(BENCH_DIM, 128)
    — the scaling claim lives in the latency-bound regime; see
    bench_replica_serving)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_REPLICA", "1")
    rows = 512 if spec.strip() in ("", "1") else int(spec)
    dim = int(os.environ.get("BENCH_REPLICA_DIM", "0")) or None
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] replica-serving stage at {rows} rows", file=sys.stderr,
          flush=True)
    t0 = time.perf_counter()
    out = bench_replica_serving(on_tpu, rows, dim=dim)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    size_tag = "1m" if rows >= 1_000_000 else f"{rows}"
    path = os.path.join(art_dir,
                        f"pr18_replica_serving_{size_tag}_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "replica_qps_scaling",
                   "value": out["qps_scaling"], "unit": "x",
                   "device": dev_tag, "sizes": {size_tag: out}},
                  f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "replica_qps_scaling",
                      "sizes": {size_tag: {
                          k: v for k, v in out.items()
                          if k not in ("telemetry",)}}}))


def bench_fault_recovery(on_tpu: bool, rows: int = 8192, faults_n: int = 20,
                         flood: int = 512):
    """Fault-recovery acceptance stage (ISSUE 10): measures what failure
    costs, proves recovery end-to-end, and records the counters the
    ``scripts/check_fault_matrix.py`` CI gate requires.

    Three measurements on one arena:

    1. **Recovery latency** — serve p50 on the clean path, then inject a
       dispatch fault (``index.dispatch``, transient) before ``faults_n``
       separate serves: each one recovers through the non-donating twin
       in the SAME call, and the faulted-turn wall time p50/p95 vs clean
       p50 is the measured price of a retry.
    2. **Shed rate under injected overload** — a thread flood submits
       ``flood`` single-query requests against a deliberately small
       admission budget; every future resolves (result or typed
       ``LoadShed``) — the artifact records the shed rate and that ZERO
       futures hung.
    3. **The recovery matrix** — every injection point exercised on a
       small fixture with post-recovery arena parity asserted, mirroring
       tests/test_fault_injection.py so CI artifacts carry the same
       evidence the suite pins.
    """
    import tempfile
    import threading

    from lazzaro_tpu.core import checkpoint as CK
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.reliability.errors import (ArenaPoisoned,
                                                CheckpointCorrupt,
                                                ColdReadError,
                                                DispatchTimeout, LoadShed,
                                                WorkerCrashed)
    from lazzaro_tpu.reliability.faults import (INJECTOR, InjectedFault,
                                                poison_states_hook,
                                                torn_write_hook)
    from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest
    from lazzaro_tpu.serve.scheduler import RetrievalResult
    from lazzaro_tpu.utils.telemetry import Telemetry

    EPOCH = 1000.0
    kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02, now=1234.5)

    def vecs(n, seed):
        r = np.random.default_rng(seed)
        v = r.standard_normal((n, DIM)).astype(np.float32)
        return v / np.linalg.norm(v, axis=1, keepdims=True)

    def build(n=256, int8=False, tiered=False):
        idx = MemoryIndex(dim=DIM, capacity=max(n + 64, 255),
                          int8_serving=int8 or tiered, epoch=EPOCH,
                          coarse_slack=(n + 64 if (int8 or tiered) else 8),
                          telemetry=Telemetry())
        emb = vecs(n, 3)
        idx.add([f"n{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
                ["semantic"] * n, ["default"] * n, "u0")
        idx.add_edges([(f"n{i}", f"n{i + 1}", 0.7) for i in range(n - 1)],
                      "u0", now=EPOCH)
        if tiered:
            tm = idx.enable_tiering(hot_budget_rows=n // 4,
                                    hysteresis_s=0.0)
            tm.demote_rows([idx.id_to_row[f"n{i}"]
                            for i in range(n // 2, n)])
        return idx, emb

    def reqs(emb, nq=16, boost=True, seed=9):
        r = np.random.default_rng(seed)
        q = emb[:nq] + 0.01 * r.standard_normal(
            (nq, DIM)).astype(np.float32)
        return [RetrievalRequest(query=q[i], tenant="u0", k=10,
                                 gate_enabled=False, boost=boost)
                for i in range(nq)]

    def parity(ia, ib):
        for col in ("emb", "salience", "last_accessed", "access_count",
                    "alive"):
            if not np.array_equal(np.asarray(getattr(ia.state, col)),
                                  np.asarray(getattr(ib.state, col))):
                return False
        return True

    matrix = {}

    def cell(name, fn):
        INJECTOR.clear()
        try:
            recovered, par = fn()
        except Exception as e:      # noqa: BLE001 — record, don't void
            print(f"[bench] fault cell {name} FAILED: {e!r}",
                  file=sys.stderr, flush=True)
            recovered, par = False, False
        finally:
            INJECTOR.clear()
        matrix[name] = {"recovered": bool(recovered), "parity": bool(par)}

    # ---- 1. recovery latency on the main arena -------------------------
    idx, emb = build(rows, int8=False)
    tel = idx.telemetry
    for _ in range(3):
        idx.search_fused_requests(reqs(emb), **kw)        # warm
    clean = []
    for _ in range(20):
        t0 = time.perf_counter()
        idx.search_fused_requests(reqs(emb), **kw)
        clean.append((time.perf_counter() - t0) * 1e3)
    faulted = []
    for _ in range(faults_n):
        INJECTOR.arm("index.dispatch", times=1)
        t0 = time.perf_counter()
        idx.search_fused_requests(reqs(emb), **kw)        # recovers inline
        faulted.append((time.perf_counter() - t0) * 1e3)
    INJECTOR.clear()
    clean_p50 = float(np.percentile(clean, 50))
    rec_p50 = float(np.percentile(faulted, 50))
    rec_p95 = float(np.percentile(faulted, 95))
    retries = tel.counter_total("serve.dispatch_retries")

    # ---- 2. shed rate under injected overload --------------------------
    shed_tel = Telemetry()
    sched = QueryScheduler(
        lambda rs: idx.search_fused_requests(rs, **kw),
        telemetry=shed_tel, shed_depth=32)
    futures = []
    fut_lock = threading.Lock()

    def client(seed):
        r = np.random.default_rng(seed)
        for _ in range(flood // 8):
            q = emb[int(r.integers(0, len(emb)))]
            f = sched.submit(RetrievalRequest(query=q, tenant="u0", k=10))
            with fut_lock:
                futures.append(f)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    served = shed_n = hung = 0
    max_wait = 0.0
    from concurrent.futures import TimeoutError as _FutTimeout
    for f in futures:
        tw = time.perf_counter()
        try:
            f.result(timeout=60)
            served += 1
        except LoadShed:
            shed_n += 1
        except _FutTimeout:
            hung += 1           # the one outcome the layer must forbid
        except Exception:       # noqa: BLE001 — typed failure, not a hang
            shed_n += 1
        max_wait = max(max_wait, (time.perf_counter() - tw) * 1e3)
    flood_s = time.perf_counter() - t0
    sched.close()
    shed_rate = shed_n / max(1, len(futures))

    # ---- 3. the recovery matrix ----------------------------------------
    def _dispatch_cell(int8, tiered):
        a, e = build(int8=int8, tiered=tiered)
        b, _ = build(int8=int8, tiered=tiered)
        INJECTOR.arm("index.dispatch", times=1)
        ra = a.search_fused_requests(reqs(e, nq=8), **kw)
        rb = b.search_fused_requests(reqs(e, nq=8), **kw)
        ok = all(x.ids == y.ids for x, y in zip(ra, rb))
        return ok, parity(a, b)

    cell("dispatch_raise:exact", lambda: _dispatch_cell(False, False))
    cell("dispatch_raise:quant", lambda: _dispatch_cell(True, False))
    cell("dispatch_raise:tiered", lambda: _dispatch_cell(False, True))

    def _poison_cell():
        a, e = build(int8=True)
        ctrl, _ = build(int8=True)
        with tempfile.TemporaryDirectory() as tmp:
            CK.save_index(a, tmp + "/ck")
            INJECTOR.arm("index.dispatch", times=1,
                         hook=poison_states_hook)
            try:
                a.update_access(["n0"], now=2000.0)
                return False, False          # must have raised
            except ArenaPoisoned:
                pass
            restored = CK.load_index(tmp + "/ck", int8_serving=True,
                                     coarse_slack=a.coarse_slack)
            return True, parity(restored, ctrl)

    cell("dispatch_poison:exact", _poison_cell)

    def _worker_cell():
        a, e = build()
        wd_tel = Telemetry()
        s = QueryScheduler(lambda rs: a.search_fused_requests(rs, **kw),
                           telemetry=wd_tel)
        INJECTOR.arm("scheduler.worker", times=1)
        fs = s.submit_many(reqs(e, nq=4))
        typed = 0
        for f in fs:
            try:
                f.result(timeout=30)
            except WorkerCrashed:
                typed += 1
        ok2 = all(r.ids for r in
                  [f.result(timeout=30)
                   for f in s.submit_many(reqs(e, nq=4))])
        s.close()
        return typed == 4 and ok2, True

    cell("worker_death:exact", _worker_cell)

    def _watchdog_cell():
        calls = {"n": 0}

        def ex(rs):
            calls["n"] += 1
            if calls["n"] == 1:
                time.sleep(0.2)
            return [RetrievalResult() for _ in rs]

        wd_tel = Telemetry()
        s = QueryScheduler(ex, telemetry=wd_tel, dispatch_timeout_s=0.05)
        f = s.submit(RetrievalRequest(
            query=np.zeros(DIM, np.float32), tenant="t"))
        try:
            f.result(timeout=30)
            return False, False
        except DispatchTimeout:
            pass
        f2 = s.submit(RetrievalRequest(
            query=np.zeros(DIM, np.float32), tenant="t"))
        ok = isinstance(f2.result(timeout=30), RetrievalResult)
        s.close()
        nonlocal_timeouts["n"] += wd_tel.counter_total(
            "reliability.watchdog_timeouts")
        return ok, True

    nonlocal_timeouts = {"n": 0}
    cell("watchdog_timeout:exact", _watchdog_cell)

    def _pump_cell():
        a, _ = build(int8=True)
        b, _ = build(int8=True)
        tm = a.enable_tiering(hot_budget_rows=64, hysteresis_s=0.0)
        rows_ = [a.id_to_row[f"n{i}"] for i in range(128, 192)]
        INJECTOR.arm("pump.mid_chunk", times=1)
        try:
            tm.demote_rows(rows_)
            return False, False
        except InjectedFault:
            pass
        ok = tm.cold_count == 0 and parity(a, b)
        moved = tm.demote_rows(rows_)
        return ok and moved == len(rows_), ok

    cell("pump_mid_chunk:tiered", _pump_cell)

    def _torn_cell():
        a, e = build(tiered=True)
        with tempfile.TemporaryDirectory() as tmp:
            ck = tmp + "/ck"
            INJECTOR.arm("checkpoint.torn", times=1, exc=None,
                         hook=torn_write_hook())
            CK.save_index(a, ck)
            try:
                CK.load_index(ck, int8_serving=True,
                              coarse_slack=a.coarse_slack)
                return False, False
            except CheckpointCorrupt:
                pass
            CK.save_index(a, ck)
            restored = CK.load_index(ck, int8_serving=True,
                                     coarse_slack=a.coarse_slack)
            return True, parity(restored, a)

    cell("checkpoint_torn:tiered", _torn_cell)

    def _cold_cell():
        a, e = build(tiered=True)
        b, _ = build(tiered=True)
        INJECTOR.arm("coldstore.read", times=1, exc=ColdReadError)
        try:
            a.search_fused_requests(reqs(e, nq=8, boost=False), **kw)
            return False, False
        except ColdReadError:
            pass
        ra = a.search_fused_requests(reqs(e, nq=8, boost=False), **kw)
        rb = b.search_fused_requests(reqs(e, nq=8, boost=False), **kw)
        ok = all(x.ids == y.ids for x, y in zip(ra, rb))
        return ok, parity(a, b)

    cell("coldstore_read:tiered", _cold_cell)

    def _journal_cell():
        from lazzaro_tpu.reliability import IngestJournal
        with tempfile.TemporaryDirectory() as tmp:
            j = IngestJournal(tmp + "/ing.wal")
            j.append([{"content": "a"}, {"content": "b"}])
            j2 = IngestJournal(tmp + "/ing.wal")   # crash + reopen
            pend = j2.pending()
            n = sum(len(f) for _, f in pend)
            journal_counts["replayed"] += n
            j2.commit(j2.last_seq)
            return n == 2 and IngestJournal(
                tmp + "/ing.wal").pending_count == 0, True

    journal_counts = {"replayed": 0}
    cell("ingest_journal:replay", _journal_cell)

    all_recovered = all(c["recovered"] and c["parity"]
                        for c in matrix.values())
    return {
        "reliability": True,
        "rows": rows,
        "dim": DIM,
        "fault_matrix": matrix,
        "all_recovered": all_recovered,
        "clean_p50_ms": round(clean_p50, 3),
        "recovery_latency_ms_p50": round(rec_p50, 3),
        "recovery_latency_ms_p95": round(rec_p95, 3),
        "recovery_overhead_x": round(rec_p50 / max(clean_p50, 1e-9), 2),
        "shed": {"submitted": len(futures), "served": served,
                 "shed": shed_n, "hung_futures": hung,
                 "flood_s": round(flood_s, 2),
                 "max_future_wait_ms": round(max_wait, 1)},
        "shed_rate": round(shed_rate, 4),
        "counters": {
            "dispatch_retries": retries,
            "load_shed": shed_tel.counter_total("reliability.load_shed"),
            "watchdog_timeouts": nonlocal_timeouts["n"],
            "worker_restarts": shed_tel.counter_total(
                "reliability.worker_restarts"),
            "journal_replayed": journal_counts["replayed"],
        },
        "telemetry": _telemetry_block(tel),
    }


def fault_recovery_stage_main():
    """Standalone fault-recovery stage (BENCH_FAULT_RECOVERY=<rows> or =1
    for the default 8192): runs ONLY the reliability stage and writes
    bench_artifacts/pr10_fault_recovery_<dev>.json — the artifact
    ``scripts/check_fault_matrix.py`` gates in CI."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_FAULT_RECOVERY", "1")
    rows = 8192 if spec.strip() in ("", "1") else int(spec)
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] fault-recovery stage at {rows} rows", file=sys.stderr,
          flush=True)
    t0 = time.perf_counter()
    out = bench_fault_recovery(on_tpu, rows)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    path = os.path.join(art_dir, f"pr10_fault_recovery_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "fault_recovery_latency_p95_ms",
                   "value": out["recovery_latency_ms_p95"], "unit": "ms",
                   "device": dev_tag, "reliability": True,
                   "sizes": {"default": out}}, f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "fault_recovery_latency_p95_ms",
                      "value": out["recovery_latency_ms_p95"],
                      "fault_matrix": out["fault_matrix"],
                      "shed_rate": out["shed_rate"]}))


def bench_hbm_plan(on_tpu: bool, rows: int = 8192):
    """Memory-safe serving acceptance stage (ISSUE 11): serve a query-
    batch geometry LADDER across a throttled HBM budget and prove the
    planner turns would-be OOMs into planned degradations.

    Measurements:

    1. **The ladder** — batches 8→128 against a budget sized so the small
       geometries admit FUSED and the large ones need planned splits /
       chunked scans: per point, the decision, the MEASURED
       dispatches-per-turn next to the PLANNED count (the dispatch-count
       gate accepts exactly that pairing), and p95 latency of the planned
       turn vs an unthrottled single-dispatch control — the measured
       price of staying inside the budget.
    2. **Replan recovery** — injected ``RESOURCE_EXHAUSTED`` at the
       dispatch (the ``plan.oom`` point) across exact/quant/tiered
       fixtures: every cell must recover via ONE replan through the copy
       twins to bit-parity, and the replan-turn latency p50/p95 vs clean
       p50 is recorded (the fault-matrix gate checks the cells + the
       ``oom_replans`` counter).
    3. **Typed shed** — a flood against an infeasible-budget index: every
       future resolves with the typed ``PlanInfeasible`` (shed like
       LoadShed), ZERO hang, ZERO ``RESOURCE_EXHAUSTED`` crashes anywhere
       in the stage.

    The stage also records every geometry it EXERCISED (not just ones
    that compiled) for ``scripts/check_hbm_budget.py``'s planner sweep,
    and persists the cost-model calibration beside the artifacts."""
    from lazzaro_tpu.core.index import MemoryIndex
    from lazzaro_tpu.reliability.errors import PlanInfeasible
    from lazzaro_tpu.reliability.faults import INJECTOR, oom_error
    from lazzaro_tpu.reliability.guard import is_resource_exhausted
    from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest
    from lazzaro_tpu.utils.telemetry import Telemetry

    EPOCH = 1000.0
    kw = dict(cap_take=5, max_nbr=8, super_gate=0.4, acc_boost=0.05,
              nbr_boost=0.02, now=1234.5)
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    calib_path = os.path.join(art_dir, "plan_calibration.json")
    oom_crashes = 0

    def vecs(n, seed):
        r = np.random.default_rng(seed)
        v = r.standard_normal((n, DIM)).astype(np.float32)
        return v / np.linalg.norm(v, axis=1, keepdims=True)

    def build(n=rows, budget=0, int8=False, tiered=False, calib=False,
              tel_hbm=False):
        idx = MemoryIndex(
            dim=DIM, capacity=max(n + 64, 255), epoch=EPOCH,
            int8_serving=int8 or tiered,
            coarse_slack=(n + 64 if (int8 or tiered) else 8),
            telemetry=Telemetry(), telemetry_hbm=tel_hbm,
            hbm_budget_bytes=budget,
            plan_calibration_path=(calib_path if calib else None))
        emb = vecs(n, 3)
        idx.add([f"n{i}" for i in range(n)], emb, [0.5] * n, [0.0] * n,
                ["semantic"] * n, ["default"] * n, "u0")
        idx.add_edges([(f"n{i}", f"n{i + 1}", 0.7)
                       for i in range(min(n, 512) - 1)], "u0", now=EPOCH)
        if tiered:
            tm = idx.enable_tiering(hot_budget_rows=n // 4,
                                    hysteresis_s=0.0)
            tm.demote_rows([idx.id_to_row[f"n{i}"]
                            for i in range(n // 2, n)])
        return idx, emb

    def reqs(emb, nq, boost=False, seed=9):
        r = np.random.default_rng(seed)
        q = emb[:nq] + 0.01 * r.standard_normal(
            (nq, DIM)).astype(np.float32)
        return [RetrievalRequest(query=q[i], tenant="u0", k=10,
                                 gate_enabled=False, boost=boost)
                for i in range(nq)]

    def parity(ia, ib):
        for col in ("emb", "salience", "last_accessed", "access_count",
                    "alive"):
            if not np.array_equal(np.asarray(getattr(ia.state, col)),
                                  np.asarray(getattr(ib.state, col))):
                return False
        return True

    # ---- budget sizing: the ladder must CROSS it --------------------
    # Size from the SAME calibration the throttled index will load, or a
    # previously-persisted (grown) multiplier would shift the whole
    # ladder past the budget.
    from lazzaro_tpu.plan import CostModel
    ctrl, emb = build()                        # planner off = the control
    model = CostModel.load_or_default(
        calib_path if os.path.exists(calib_path) else None)
    # Just above the ONE-bucket geometry (batch 8, maximally chunked
    # scan): the smallest ladder point admits fused, everything larger
    # must take planned sub-dispatches — the ladder crosses the budget.
    probe_g = ctrl._serve_geometry(8, "exact", ctrl.serve_k_max)
    budget = int(model.predict(probe_g.with_(scan_chunk=8)) / 0.9) \
        + (48 << 10)
    planned, _ = build(budget=budget, calib=True, tel_hbm=True)
    tel = planned.telemetry
    geoms_exercised = []
    ladder = []
    ladder_batches = (8, 32, 64, 128)
    turns = 6
    for b in ladder_batches:
        g = planned._serve_geometry(b, "exact", planned.serve_k_max)
        d = planned.planner.plan(g)
        geoms_exercised.append({
            "kind": "serve", "mode": g.mode, "batch": g.batch,
            "rows": g.rows, "dim": g.dim, "k": g.k,
            "dtype_bytes": g.dtype_bytes, "mesh_parts": g.mesh_parts,
            "edge_cap": g.edge_cap})
        rs = reqs(emb, b)
        for idx in (planned, ctrl):            # warm both kernels
            idx.search_fused_requests(rs, **kw)
        t_planned, t_ctrl = [], []
        before = tel.counter_total("serve.dispatches")
        for _ in range(turns):
            t0 = time.perf_counter()
            try:
                res_p = planned.search_fused_requests(rs, **kw)
            except Exception as e:  # noqa: BLE001 — the crash we forbid
                if is_resource_exhausted(e):
                    oom_crashes += 1
                raise
            t_planned.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            res_c = ctrl.search_fused_requests(rs, **kw)
            t_ctrl.append((time.perf_counter() - t0) * 1e3)
        measured = (tel.counter_total("serve.dispatches")
                    - before) / turns
        assert all(x.ids == y.ids for x, y in zip(res_p, res_c))
        ladder.append({
            "batch": b,
            "decision": d.reason,
            "planned_splits": d.splits,
            "scan_chunk": d.scan_chunk,
            "predicted_bytes": d.predicted_bytes,
            # "measured_" prefix: the top-level dict carries the GATED
            # dispatches_per_turn/planned pair next to its telemetry
            # block; per-point dicts record without re-triggering the
            # ISSUE 6 per-dict telemetry requirement
            "measured_dispatches_per_turn": round(measured, 2),
            "p95_ms_planned": round(float(np.percentile(t_planned, 95)),
                                    3),
            "p95_ms_unsplit": round(float(np.percentile(t_ctrl, 95)), 3),
            "split_overhead_x": round(
                float(np.percentile(t_planned, 95))
                / max(float(np.percentile(t_ctrl, 95)), 1e-9), 2),
        })
    split_points = [p for p in ladder if p["planned_splits"] > 1]
    fused_points = [p for p in ladder if p["planned_splits"] == 1]
    # ingest geometry exercised through the same admission surface (on
    # the deliberately throttled budget a typed rejection is a VALID
    # planner outcome — the point is it is never a runtime OOM)
    try:
        d_ing = planned.plan_ingest(1024)
        ing_decision = {"splits": d_ing.splits, "reason": d_ing.reason}
    except PlanInfeasible:
        ing_decision = {"splits": 0, "reason": "infeasible (typed)"}
    gi = planned._ingest_geometry(1024)
    geoms_exercised.append({
        "kind": "ingest", "mode": "ingest", "batch": gi.batch,
        "rows": gi.rows, "dim": gi.dim, "k": gi.k,
        "dtype_bytes": gi.dtype_bytes, "mesh_parts": gi.mesh_parts})

    # ---- replan recovery: injected RESOURCE_EXHAUSTED ----------------
    # A dedicated generous-budget index: every injected OOM legitimately
    # inflates the model (each one is evidence it under-predicted), so a
    # deliberately-throttled budget could not absorb 8 of them — the
    # throttled index's single replan is covered by the matrix cells.
    replanner, _ = build(budget=1 << 34)
    clean = []
    rs16 = reqs(emb, 16)
    replanner.search_fused_requests(rs16, **kw)     # warm
    for _ in range(8):
        t0 = time.perf_counter()
        replanner.search_fused_requests(rs16, **kw)
        clean.append((time.perf_counter() - t0) * 1e3)
    replan_ms = []
    for _ in range(8):
        INJECTOR.arm("plan.oom", times=1, exc=oom_error)
        t0 = time.perf_counter()
        replanner.search_fused_requests(rs16, **kw)  # recovers inline
        replan_ms.append((time.perf_counter() - t0) * 1e3)
    INJECTOR.clear()

    matrix = {}

    def cell(name, int8, tiered):
        INJECTOR.clear()
        try:
            a, e = build(n=256, budget=1 << 34, int8=int8, tiered=tiered)
            c, _ = build(n=256, int8=int8, tiered=tiered)
            INJECTOR.arm("plan.oom", times=1, exc=oom_error)
            ra = a.search_fused_requests(reqs(e, 8), **kw)
            rc = c.search_fused_requests(reqs(e, 8), **kw)
            ok = all(x.ids == y.ids for x, y in zip(ra, rc))
            ok = ok and a.telemetry.counter_total("plan.oom_replans") >= 1
            matrix[name] = {"recovered": bool(ok),
                            "parity": bool(parity(a, c))}
        except Exception as exc:  # noqa: BLE001 — record, don't void
            print(f"[bench] replan cell {name} FAILED: {exc!r}",
                  file=sys.stderr, flush=True)
            matrix[name] = {"recovered": False, "parity": False}
        finally:
            INJECTOR.clear()

    cell("plan.oom:exact", False, False)
    cell("plan.oom:quant", True, False)
    cell("plan.oom:tiered", False, True)

    # ---- typed shed: infeasible geometry never hangs a future --------
    infeasible_idx, _ = build(n=256, budget=4096)
    shed_tel = Telemetry()

    def admission(requests):
        infeasible_idx.planner.check_feasible(
            infeasible_idx._serve_geometry(
                1, "exact", infeasible_idx.serve_k_max))

    sched = QueryScheduler(
        lambda r_: infeasible_idx.search_fused_requests(r_, **kw),
        telemetry=shed_tel, admission_check=admission)
    futs = sched.submit_many(reqs(emb, 64))
    hung = served = shed_n = 0
    from concurrent.futures import TimeoutError as _FutTimeout
    for f in futs:
        try:
            f.result(timeout=30)
            served += 1
        except PlanInfeasible:
            shed_n += 1
        except _FutTimeout:
            hung += 1
        except Exception:  # noqa: BLE001 — typed failure, not a hang
            shed_n += 1
    sched.close()

    all_recovered = all(c["recovered"] and c["parity"]
                        for c in matrix.values())
    worst = max(split_points, key=lambda p: p["planned_splits"],
                default=ladder[-1])
    return {
        "hbm_plan": True,
        "reliability": True,
        "rows": rows,
        "dim": DIM,
        "budget_bytes": budget,
        "headroom_fraction": planned.planner.headroom_fraction,
        "ladder": ladder,
        "ladder_split_points": len(split_points),
        "ladder_fused_points": len(fused_points),
        "dispatches_per_turn": worst["measured_dispatches_per_turn"],
        "planned_dispatches_per_turn": worst["planned_splits"],
        "fused_probe": {"batch": fused_points[0]["batch"],
                        "measured_dispatches_per_turn":
                            fused_points[0]
                            ["measured_dispatches_per_turn"]}
        if fused_points else None,
        "geometries_exercised": geoms_exercised,
        "plan": {
            "split_dispatches":
                tel.counter_total("plan.split_dispatches"),
            "planned_turns": tel.counter_total("plan.planned_turns"),
            "scan_chunked": tel.counter_total("plan.scan_chunked"),
            "oom_replans":
                replanner.telemetry.counter_total("plan.oom_replans"),
            "infeasible_shed":
                shed_tel.counter_total("plan.infeasible_shed"),
            "ingest_decision": ing_decision,
            "resource_exhausted_crashes": oom_crashes,
            "calibration_path": os.path.relpath(
                calib_path, os.path.dirname(art_dir)),
            "multipliers": dict(planned.planner.model.multipliers),
        },
        "fault_matrix": matrix,
        "all_recovered": all_recovered,
        "clean_p50_ms": round(float(np.percentile(clean, 50)), 3),
        "recovery_latency_ms_p50":
            round(float(np.percentile(replan_ms, 50)), 3),
        "recovery_latency_ms_p95":
            round(float(np.percentile(replan_ms, 95)), 3),
        "shed": {"submitted": len(futs), "served": served,
                 "shed": shed_n, "hung_futures": hung},
        "shed_rate": round(shed_n / max(1, len(futs)), 4),
        "counters": {
            "dispatch_retries":
                tel.counter_total("serve.dispatch_retries"),
            "load_shed": shed_tel.counter_total("reliability.load_shed"),
            "watchdog_timeouts":
                tel.counter_total("reliability.watchdog_timeouts"),
            "worker_restarts":
                tel.counter_total("reliability.worker_restarts"),
            "journal_replayed":
                tel.counter_total("reliability.journal_replayed"),
            "oom_replans":
                replanner.telemetry.counter_total("plan.oom_replans"),
        },
        "telemetry": _telemetry_block(tel),
    }


def hbm_plan_stage_main():
    """Standalone memory-safe-serving stage (BENCH_HBM_PLAN=<rows> or =1
    for the default 8192): runs ONLY the planner ladder and writes
    bench_artifacts/pr11_hbm_plan_<dev>.json — gated in CI by
    ``check_hbm_budget.py`` (plan block, geometry sweep, model soundness),
    ``check_dispatch_counts.py`` (planned counts), and
    ``check_fault_matrix.py`` (replan cells + oom_replans counter)."""
    on_tpu = backend.on_tpu()
    spec = os.environ.get("BENCH_HBM_PLAN", "1")
    rows = 8192 if spec.strip() in ("", "1") else int(spec)
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    dev_tag = "tpu" if on_tpu else "cpu"
    print(f"[bench] hbm-plan stage at {rows} rows", file=sys.stderr,
          flush=True)
    t0 = time.perf_counter()
    out = bench_hbm_plan(on_tpu, rows)
    out["stage_total_s"] = round(time.perf_counter() - t0, 1)
    path = os.path.join(art_dir, f"pr11_hbm_plan_{dev_tag}.json")
    with open(path, "w") as f:
        json.dump({"metric": "hbm_plan_split_overhead_x",
                   "value": max(p["split_overhead_x"]
                                for p in out["ladder"]),
                   "unit": "x", "device": dev_tag,
                   "sizes": {"default": out}}, f, indent=1)
    print(f"[bench] wrote {path}", file=sys.stderr, flush=True)
    print(json.dumps({"metric": "hbm_plan_split_overhead_x",
                      "value": max(p["split_overhead_x"]
                                   for p in out["ladder"]),
                      "split_points": out["ladder_split_points"],
                      "resource_exhausted_crashes":
                          out["plan"]["resource_exhausted_crashes"],
                      "shed_rate": out["shed_rate"]}))


if __name__ == "__main__":
    place_compile_cache()
    if os.environ.get("BENCH_HBM_PLAN"):
        hbm_plan_stage_main()
    elif os.environ.get("BENCH_FAULT_RECOVERY"):
        fault_recovery_stage_main()
    elif os.environ.get("BENCH_TIERED"):
        tiered_stage_main()
    elif os.environ.get("BENCH_PAGED_ARENA"):
        paged_arena_stage_main()
    elif os.environ.get("BENCH_REPLICA"):
        replica_stage_main()
    elif os.environ.get("BENCH_SEMANTIC_CACHE"):
        semantic_cache_stage_main()
    elif os.environ.get("BENCH_LIFECYCLE"):
        lifecycle_stage_main()
    elif os.environ.get("BENCH_FUSED_QUANT"):
        fused_quant_stage_main()
    elif os.environ.get("BENCH_FUSED_IVF"):
        fused_ivf_stage_main()
    elif os.environ.get("BENCH_FUSED_SHARDED"):
        fused_sharded_stage_main()
    elif os.environ.get("BENCH_SHARDED_INGEST"):
        sharded_ingest_stage_main()
    elif os.environ.get("BENCH_ONLINE_IVF"):
        online_ivf_stage_main()
    elif os.environ.get("BENCH_FUSED_PQ"):
        fused_pq_stage_main()
    else:
        main()
