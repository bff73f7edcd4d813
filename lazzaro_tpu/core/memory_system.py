"""MemorySystem: the orchestrator (TPU-native rebuild).

Parity target: reference ``core/memory_system.py`` (1550 LoC) — same public
method names and semantics (SURVEY §2.2), rebuilt on:
- an HBM-resident SoA index (``core.index.MemoryIndex``) instead of LanceDB +
  per-node Python similarity loops;
- on-device providers by default (hashing embedder / heuristic LLM; swap in
  the flax encoder + decoder LM or remote providers via the same protocols);
- a single-writer consolidation worker guarded by one mutation lock — the
  reference runs a ThreadPoolExecutor that mutates shards/counters unlocked
  (a real data race, SURVEY §5 "design away").

Semantic thresholds replicate the reference exactly (dedup 0.95, super-node
gate 0.4, link gate 0.5, salience floor 0.2, importance 0.5/0.3/0.2, decay
0.01, cap-5 retrieval); the known reference bugs are NOT replicated
(`_merge_similar_nodes` indentation bug, dead `_get_relevant_shards`, broken
CLI /save path — SURVEY §2.2 quirks).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import (Any, Dict, Iterator, List, Optional, Sequence, Set,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core.buffer_graph import BufferGraph
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.memory_shard import MemoryShard
from lazzaro_tpu.core.profile import Profile
from lazzaro_tpu.core.providers import (HashingEmbedder, HeuristicLLM,
                                        _extract_json_object, infer_topic)
from lazzaro_tpu.core.query_cache import QueryCache
from lazzaro_tpu.core.store import ArrowStore
from lazzaro_tpu.models.graph import Edge, Node
from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest
from lazzaro_tpu.utils.batching import IngestCoalescer
from lazzaro_tpu.utils.telemetry import Telemetry

_logger = logging.getLogger("lazzaro_tpu.memory_system")


def _ensure_log_handler() -> None:
    """Attach one bare-message stderr handler to the ``lazzaro_tpu`` logger
    when neither it nor the root logger is configured — ``verbose=True``
    stays visible out of the box, while applications that configure
    logging get full control (and silence) the standard way."""
    pkg = logging.getLogger("lazzaro_tpu")
    if pkg.handlers or logging.root.handlers:
        return
    handler = logging.StreamHandler()
    handler.setFormatter(logging.Formatter("%(message)s"))
    pkg.addHandler(handler)
    if pkg.level == logging.NOTSET:
        pkg.setLevel(logging.INFO)


class _LifecyclePump:
    """Background maintenance thread (ISSUE 19): calls
    ``system.lifecycle_tick()`` every ``interval_s``. The tick itself is
    scheduler-aware (it defers while serving load is queued), so the pump
    stays a dumb metronome — mirror of ``tier.TierPump``."""

    def __init__(self, system: "MemorySystem", interval_s: float):
        self._system = system
        self.interval_s = float(interval_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="lifecycle-pump", daemon=True)

    def start(self) -> "_LifecyclePump":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self._system.lifecycle_tick()
            except Exception:                            # pragma: no cover
                logging.getLogger("lazzaro_tpu").exception(
                    "lifecycle tick failed")


class MemorySystem:
    # Above this many arena rows, per-conversation host syncs become
    # selective (dirty rows only) and the full sweep is reserved for
    # explicit display/export/snapshot surfaces.
    _SYNC_FULL_MAX = 20_000

    def __init__(
        self,
        enable_sharding: Optional[bool] = None,
        enable_hierarchy: Optional[bool] = None,
        enable_caching: Optional[bool] = None,
        enable_async: Optional[bool] = None,
        max_shard_size: Optional[int] = None,
        super_node_threshold: Optional[int] = None,
        auto_consolidate: Optional[bool] = None,
        consolidate_every: Optional[int] = None,
        auto_prune: Optional[bool] = None,
        prune_threshold: Optional[float] = None,
        max_buffer_size: Optional[int] = None,
        load_from_disk: Optional[bool] = None,
        db_dir: Optional[str] = None,
        user_id: Optional[str] = None,
        llm_provider=None,
        embedding_provider=None,
        store=None,
        config: Optional[MemoryConfig] = None,
        verbose: bool = True,
        mesh=None,
    ):
        """``mesh``: optional ``jax.sharding.Mesh`` with a 'data' axis — the
        arena index row-shards across it and every kernel runs SPMD (full
        pod-scale orchestrator; see MemoryIndex sharding notes)."""
        # Explicit kwargs win; otherwise values come from the (possibly
        # caller-supplied) MemoryConfig, whose defaults match the reference
        # constructor (memory_system.py:63-84).
        self.config = config or MemoryConfig()
        cfg = self.config

        def pick(kwarg, field):
            if kwarg is not None:
                setattr(cfg, field, kwarg)
            return getattr(cfg, field)

        self.enable_sharding = pick(enable_sharding, "enable_sharding")
        self.enable_hierarchy = pick(enable_hierarchy, "enable_hierarchy")
        self.enable_caching = pick(enable_caching, "enable_caching")
        self.enable_async = pick(enable_async, "enable_async")
        self.max_shard_size = pick(max_shard_size, "max_shard_size")
        self.super_node_threshold = pick(super_node_threshold, "super_node_threshold")
        self.auto_consolidate = pick(auto_consolidate, "auto_consolidate")
        self.consolidate_every = pick(consolidate_every, "consolidate_every")
        self.auto_prune = pick(auto_prune, "auto_prune")
        self.prune_threshold = pick(prune_threshold, "prune_threshold")
        self.max_buffer_size = pick(max_buffer_size, "max_buffer_size")
        db_dir = pick(db_dir, "db_dir")
        self.user_id = pick(user_id, "user_id")
        load_from_disk = pick(load_from_disk, "load_from_disk")
        self.verbose = verbose

        self.llm = llm_provider if llm_provider is not None else HeuristicLLM()
        self.embedder = (embedding_provider if embedding_provider is not None
                         else HashingEmbedder(dim=cfg.embed_dim))
        dim = getattr(self.embedder, "dim", None)
        if not isinstance(dim, int) or dim <= 0:
            dim = len(self.embedder.embed("dimension probe"))
        self.embed_dim = dim

        # Serving telemetry (ISSUE 6): one registry per system — the index,
        # the query scheduler, the store's and journals' file operations and
        # the chat/consolidation paths all record into it;
        # ``metrics_summary()`` / the dashboard ``/metrics`` endpoint read
        # it out.
        self.telemetry = Telemetry(cfg.serve_telemetry_window,
                                   enabled=cfg.serve_telemetry)
        self.store = (store if store is not None
                      else ArrowStore(db_dir, telemetry=self.telemetry))
        self.vector_store = self.store  # back-compat alias (reference :110)

        self.shards: Dict[str, MemoryShard] = {}
        self.super_nodes: Dict[str, Node] = {}
        # O(1) placement caches: edge_key → shard_key and node_id → shard_key.
        # Self-healing — entries are validated on read and rebuilt on miss, so
        # a mutation path that forgets to update them costs one repair scan,
        # never correctness. Kills the per-edge×per-shard scans that crept
        # toward the reference's O(E·S) habits (_find_edge, _add_edges_batch,
        # _save_incremental) as shard count grows monthly.
        self._edge_shard: Dict[Tuple[str, str], str] = {}
        self._node_shard_cache: Dict[str, str] = {}
        self.buffer = BufferGraph(self.shards, self.super_nodes)
        self.profile = Profile()
        self.mesh = mesh
        self.index = MemoryIndex(dim, capacity=cfg.initial_capacity,
                                 edge_capacity=cfg.max_edges,
                                 dtype=jnp.dtype(cfg.dtype), mesh=mesh,
                                 int8_serving=cfg.int8_serving,
                                 ivf_nprobe=cfg.ivf_serving,
                                 ivf_online=cfg.ivf_online,
                                 ivf_member_cap_factor=(
                                     cfg.ivf_member_cap_factor),
                                 ivf_online_eta=cfg.ivf_online_eta,
                                 pq_serving=cfg.pq_serving,
                                 coarse_slack=cfg.coarse_fetch_slack,
                                 telemetry=self.telemetry,
                                 telemetry_hbm=cfg.serve_telemetry_hbm,
                                 serve_k_max=cfg.serve_k_max,
                                 serve_pad_granularity=cfg.serve_pad_granularity,
                                 serve_kernel_cache_max=cfg.serve_kernel_cache_max,
                                 ingest_sharded=cfg.ingest_sharded,
                                 dispatch_retry_max=cfg.dispatch_retry_max,
                                 dispatch_retry_backoff_s=(
                                     cfg.dispatch_retry_backoff_s),
                                 hbm_budget_bytes=cfg.hbm_budget_bytes,
                                 hbm_headroom_fraction=(
                                     cfg.hbm_headroom_fraction),
                                 plan_max_splits=cfg.plan_max_splits,
                                 plan_calibration_path=(
                                     cfg.plan_calibration_path),
                                 paged=cfg.paged_arena,
                                 page_rows=cfg.arena_page_rows,
                                 semantic_cache=cfg.semantic_cache,
                                 semantic_cache_slots=(
                                     cfg.semantic_cache_slots),
                                 semantic_cache_threshold=(
                                     cfg.semantic_cache_threshold),
                                 semantic_cache_block=(
                                     cfg.semantic_cache_block))

        # Tiered memory (ISSUE 8): a hot-row budget attaches the residency
        # manager and (with async on) the background demotion/promotion
        # pump, so tier traffic overlaps serving dispatches.
        self.tier_pump = None
        if cfg.tier_hot_budget_rows > 0:
            tmgr = self.index.enable_tiering(
                cfg.tier_hot_budget_rows,
                high_watermark=cfg.tier_high_watermark,
                low_watermark=cfg.tier_low_watermark,
                chunk_rows=cfg.tier_chunk_rows,
                min_idle_s=cfg.tier_min_idle_s,
                promote_hits=cfg.tier_promote_hits,
                hysteresis_s=cfg.tier_hysteresis_s,
                cold_dir=cfg.tier_cold_dir)
            if cfg.tier_pump_interval_s > 0 and self.enable_async:
                from lazzaro_tpu.tier import TierPump
                self.tier_pump = TierPump(
                    tmgr, cfg.tier_pump_interval_s).start()

        self.query_cache = QueryCache(cfg.cache_size) if self.enable_caching else None

        # Device-side lifecycle (ISSUE 19): periodic all-tenant maintenance
        # tick (decay + prune + archive verdicts in ONE fused dispatch).
        # 0 interval = manual ticks only (tests/bench call lifecycle_tick).
        self.lifecycle_pump = None
        if cfg.lifecycle_interval_s > 0 and self.enable_async:
            self.lifecycle_pump = _LifecyclePump(
                self, cfg.lifecycle_interval_s).start()

        self.short_term_memory: List[Dict] = []
        self.conversation_history: List[Dict] = []
        self.conversation_active = False
        self.conversation_count = 0
        self.node_counter = 0
        self.consolidation_queue: List[Dict] = []
        self._inflight_batches: List[Dict] = []   # popped but not yet durable
        # Cross-conversation fact batcher: extracted facts from every
        # buffered conversation coalesce into bounded mega-batches, each
        # ingested by ONE fused device dispatch (cfg.ingest_fused). With
        # ingest_flush_wait_s > 0 the coalescer's time/size policy DEFERS
        # small young batches so trickle load coalesces too.
        self._ingest_coalescer = IngestCoalescer(cfg.ingest_coalesce_max,
                                                 cfg.ingest_flush_wait_s)
        # Serving path: the cross-request query scheduler (lazy — the
        # worker thread spawns on first fused retrieval) and the deferred
        # boost accumulator for cache-hit turns (node_id -> [access_count,
        # neighbor_count, latest_now]; flushed as ONE scatter).
        self.query_scheduler: Optional[QueryScheduler] = None
        self._pending_boosts: Dict[str, List] = {}
        # Conversations whose facts the ingest flush policy deferred into
        # the coalescer: their source turns stay journaled (WAL) until the
        # facts actually land in the arena.
        self._deferred_batches: List[Dict] = []

        # Incremental persistence state. Mutation paths record which node
        # ids / edge keys changed since the last save; saves then upsert only
        # those rows as delta segments instead of rewriting the user's whole
        # table (the reference rewrites everything per conversation,
        # memory_system.py:1275-1302). Uniform decay is never written
        # per-row: ``_decay_pass`` counts sweeps, rows are stamped with the
        # pass they were written at, and loads replay the difference in
        # closed form (s' = floor + (s-floor)(1-rate)^k).
        self._supports_incremental = (
            hasattr(self.store, "save_sys_meta")
            and hasattr(self.store, "get_nodes_columns"))
        self._store_synced = False     # False ⇒ next save does a full rewrite
        self._decay_pass = 0
        self._dirty_nodes: Set[str] = set()
        self._dirty_edges: Set[Tuple[str, str]] = set()
        self._deleted_edge_ids: Set[str] = set()

        # Single-writer ingest: one worker thread + one mutation lock.
        self._mutex = threading.RLock()
        self.background_executor = (ThreadPoolExecutor(max_workers=1)
                                    if self.enable_async else None)

        # Monotonic call counters (reference parity). Latency tracking that
        # used to live here as unbounded ``retrieval_times[]`` /
        # ``consolidation_times[]`` lists is now ring-buffered Telemetry
        # spans ("chat.retrieval_ms", "consolidation.run_ms") with
        # percentile summaries — see ``metrics_summary()``.
        self.metrics = {
            "embedding_calls": 0,
            "llm_calls": 0,
            "edges_linked": 0,
        }
        self._last_version = -1

        if load_from_disk:
            self._load_from_persistence()
        self._journal = None
        self._recovered_turns = False
        self._setup_journal(replay=bool(load_from_disk))
        # Durable ingest journal (ISSUE 10): extracted facts appended
        # before they enter the coalescer, committed after their fused
        # dispatch lands, replayed idempotently here on startup.
        self._ingest_journal = None
        self._setup_ingest_journal(replay=bool(load_from_disk))

    # --------------------------------------------------------------- journal
    #
    # Invariant: the WAL always holds exactly the turns that are NOT yet
    # durable in the store — queued-but-unconsolidated batches plus the
    # current short-term buffer. It is rewritten (not blindly truncated) at
    # every lifecycle transition, so a background consolidation finishing
    # after a new conversation has started can never wipe fresh turns.

    def _setup_journal(self, replay: bool = True) -> None:
        """Open this user's turn journal; optionally recover crashed turns.

        Journaling activates only when the store exposes a ``db_dir`` (the
        injected fake stores in tests don't, matching their in-memory
        semantics). Recovered turns land back in short-term memory with the
        conversation re-opened, so the next ``end_conversation`` — or a
        ``start_conversation``, which consolidates recovered turns before
        opening a fresh buffer — persists them. The reference simply loses
        them (persists only at conversation end, memory_system.py:648).
        ``replay=False`` (a ``load_from_disk=False`` construction) requests a
        clean session: the journal is opened for writing but prior-process
        state is not injected.
        """
        self._journal = None
        self._recovered_turns = False
        journal_dir = getattr(self.store, "db_dir", None)
        if not self.config.journal or not journal_dir:
            return
        from urllib.parse import quote

        from lazzaro_tpu.native import WriteAheadLog

        path = f"{journal_dir}/journal__{quote(self.user_id, safe='')}.wal"
        self._journal = WriteAheadLog(path, fsync=self.config.journal_fsync,
                                      telemetry=self.telemetry)
        if not replay:
            return
        recovered = []
        with self.telemetry.span("journal.setup"):
            for payload in self._journal.replay():
                try:
                    turn = json.loads(payload.decode("utf-8"))
                except (UnicodeDecodeError, json.JSONDecodeError):
                    continue
                if isinstance(turn, dict) and turn.get("content"):
                    recovered.append(turn)
        if recovered:
            self.short_term_memory = recovered
            self.conversation_active = True
            self._recovered_turns = True
            self._log(f"🛟 Recovered {len(recovered)} unconsolidated turn(s) "
                      "from the journal")

    def _journal_turn(self, turn: Dict) -> None:
        if self._journal is not None:
            with self.telemetry.span("journal.turn"):
                try:
                    self._journal.append(json.dumps(turn).encode("utf-8"))
                except OSError as e:
                    self._log(f"⚠ Journal append failed: {e}")

    def _journal_sync(self) -> None:
        """Rewrite the WAL to the current not-yet-durable turn set. Callers
        hold ``self._mutex`` so the snapshot is consistent."""
        if self._journal is None:
            return
        turns: List[Dict] = []
        for batch in (self._deferred_batches + self._inflight_batches
                      + self.consolidation_queue):
            turns.extend(batch.get("memories", []))
        if self.conversation_active:
            turns.extend(self.short_term_memory)
        with self.telemetry.span("journal.sync"):
            try:
                self._journal.reset()
                for t in turns:
                    self._journal.append(json.dumps(t).encode("utf-8"))
            except OSError:
                pass

    # -------------------------------------------------------- ingest journal
    #
    # Append → dispatch → commit (ISSUE 10): the turn WAL above covers raw
    # conversation turns, but extracted FACTS used to exist only in process
    # memory between the LLM extraction and the fused ingest dispatch — a
    # crash in that window re-paid the extraction at best. The ingest
    # journal makes the facts themselves durable the moment extraction
    # returns; replay feeds them through the normal ingest, where the
    # in-dispatch dedup probe collapses anything that DID land before the
    # crash into merges. Zero lost facts, zero double-ingest.

    def _setup_ingest_journal(self, replay: bool = True) -> None:
        self._ingest_journal = None
        journal_dir = getattr(self.store, "db_dir", None)
        if not self.config.ingest_journal or not journal_dir:
            return
        from urllib.parse import quote

        from lazzaro_tpu.reliability import IngestJournal

        path = f"{journal_dir}/ingest__{quote(self.user_id, safe='')}.wal"
        # the span ends before the replay below: that one runs the ingest
        # and the store under their own names
        with self.telemetry.span("journal.setup"):
            try:
                self._ingest_journal = IngestJournal(
                    path, fsync=self.config.ingest_journal_fsync,
                    telemetry=self.telemetry)
            except OSError as e:
                self._log(f"⚠ Ingest journal unavailable: {e}")
                return
            if not replay:
                return
            pending = self._ingest_journal.pending()
        if not pending:
            return
        n_facts = sum(len(f) for _, f in pending)
        self._log(f"🛟 Replaying {n_facts} journaled fact(s) from "
                  f"{len(pending)} uncommitted ingest batch(es)")
        for _seq, facts in pending:
            self._ingest_facts(facts)
        self.telemetry.bump("reliability.journal_replayed", n_facts)
        self._commit_ingest_journal(self._ingest_journal.last_seq)
        self._save_to_persistence()

    def _commit_ingest_journal(self, seq: int) -> None:
        with self.telemetry.span("journal.commit"):
            self._ingest_journal.commit(seq)

    # ------------------------------------------------------------------ util
    def _log(self, msg: str) -> None:
        """Verbose-mode progress lines route through ``logging`` (ISSUE 6
        satellite: library users silence or redirect them with standard
        logging config; the old bare ``print`` could not be turned off
        without ``verbose=False``). A plain stderr handler is attached
        lazily when nothing else is configured, so interactive
        ``verbose=True`` sessions still see output by default."""
        if self.verbose:
            _ensure_log_handler()
            _logger.info(msg)

    def _status(self, results: List[str], msg: str) -> str:
        """Consolidation/lifecycle status strings (``"✓ Applied temporal
        decay"`` and friends) route through ``logging`` AS they are
        produced — not only through the joined return string — so library
        users see them under standard logging config and
        ``scripts/lint_no_print.py`` keeps ``core/`` print-free with no
        exemptions (ISSUE 8 satellite). Appends to ``results`` and
        returns the message for call sites that also return it."""
        self._log(msg)
        results.append(msg)
        return msg

    def _q(self, node_id: str) -> str:
        """Tenant-qualified index key (node ids like 'node_1' repeat per user)."""
        return f"{self.user_id}:{node_id}"

    def _generate_node_id(self) -> str:
        self.node_counter += 1
        return f"node_{self.node_counter}"

    def _infer_shard_key(self, content: str) -> str:
        """Keyword topic routing, fallback = current month (parity :152-169)."""
        if not self.enable_sharding:
            return "default"
        topic = infer_topic(content)
        if topic != "other":
            return topic
        return time.strftime("%Y-%m")

    def _get_or_create_shard(self, shard_key: str) -> MemoryShard:
        if shard_key not in self.shards:
            self.shards[shard_key] = MemoryShard(shard_key)
        return self.shards[shard_key]

    def _get_embedding(self, text: str) -> List[float]:
        self.metrics["embedding_calls"] += 1
        if self.query_cache:
            cached = self.query_cache.get_embedding(text)
            if cached:
                return cached
        embedding = self.embedder.embed(text)
        if self.query_cache:
            self.query_cache.set_embedding(text, embedding)
        return embedding

    def _batch_embed(self, texts: List[str]) -> List[List[float]]:
        if not texts:
            return []
        self.metrics["embedding_calls"] += 1
        return self.embedder.batch_embed(texts)

    def _cosine_similarity(self, v1, v2) -> float:
        if v1 is None or v2 is None or len(v1) == 0 or len(v2) == 0:
            return 0.0
        a, b = np.asarray(v1, np.float32), np.asarray(v2, np.float32)
        norm = np.linalg.norm(a) * np.linalg.norm(b)
        return float(np.dot(a, b) / norm) if norm > 0 else 0.0

    def _call_llm(self, messages: List[Dict], response_format: Optional[Dict] = None) -> str:
        self.metrics["llm_calls"] += 1
        return self.llm.completion(messages, response_format)

    # -------------------------------------------------------- device ↔ host
    def _index_add_node(self, node: Node) -> None:
        self.index.add(
            [self._q(node.id)],
            np.asarray(node.embedding, np.float32).reshape(1, -1),
            [node.salience], [node.timestamp], [node.type],
            [node.shard_key or "default"], self.user_id,
            [node.is_super_node])

    def _sync_from_arena(self, node_ids: Optional[Set[str]] = None,
                         edge_keys: Optional[Set[Tuple[str, str]]] = None) -> None:
        """Refresh mutable numerics on host nodes/edges from the arena.

        With no arguments this is the full bulk pull (display/export/JSON
        snapshot surfaces want every host copy fresh). With ``node_ids`` /
        ``edge_keys`` it gathers just those rows — the incremental save path
        at 1M-node scale, where a full host sweep per conversation would
        dominate the save."""
        if node_ids is not None:
            pairs = []
            for nid in node_ids:
                row = self.index.id_to_row.get(self._q(nid))
                if row is not None:
                    pairs.append((nid, row))
            if pairs:
                cols = self.index.pull_numeric_rows([r for _, r in pairs])
                for i, (nid, _row) in enumerate(pairs):
                    node = self.buffer.get_node(nid)
                    if node is None:
                        continue
                    node.salience = float(cols["salience"][i])
                    node.last_accessed = float(cols["last_accessed"][i])
                    node.access_count = int(cols["access_count"][i])
            keys = {(self._q(s), self._q(t)) for s, t in (edge_keys or set())}
            for (qsrc, qtgt), (w, co) in self.index.edge_weights_for(sorted(keys)).items():
                edge = self._find_edge((qsrc.partition(":")[2],
                                        qtgt.partition(":")[2]))
                if edge is not None:
                    edge.weight = w
                    edge.co_occurrence = co
            return
        cols = self.index.pull_numeric()
        for qid, row in self.index.id_to_row.items():
            user, _, nid = qid.partition(":")
            if user != self.user_id:
                continue
            node = self.buffer.get_node(nid)
            if node is None:
                continue
            node.salience = float(cols["salience"][row])
            node.last_accessed = float(cols["last_accessed"][row])
            node.access_count = int(cols["access_count"][row])
        for (qsrc, qtgt), (w, co) in self.index.edge_weights().items():
            user, _, src = qsrc.partition(":")
            if user != self.user_id:
                continue
            tgt = qtgt.partition(":")[2]
            edge = self._find_edge((src, tgt))
            if edge is not None:
                edge.weight = w
                edge.co_occurrence = co

    # ------------------------------------------------------- dirty tracking
    def _mark_dirty(self, *node_ids: str) -> None:
        self._dirty_nodes.update(node_ids)

    def _mark_edge_dirty(self, key: Tuple[str, str]) -> None:
        # Delete-then-recreate within one interval needs no tombstone
        # cancellation: the save flushes tombstones BEFORE upserts, so the
        # re-created row wins, while a pruned edge of a *different*
        # edge_type on the same key stays deleted.
        self._dirty_edges.add(key)

    def _shard_of_node(self, node_id: str) -> Optional[MemoryShard]:
        """O(1) owner-shard lookup through the placement cache; falls back to
        one repair scan on a stale/missing entry."""
        sk = self._node_shard_cache.get(node_id)
        if sk is not None:
            shard = self.shards.get(sk)
            if shard is not None and node_id in shard.nodes:
                return shard
            del self._node_shard_cache[node_id]
        for sk, shard in self.shards.items():
            if node_id in shard.nodes:
                self._node_shard_cache[node_id] = sk
                return shard
        return None

    def _find_edge(self, key: Tuple[str, str]) -> Optional[Edge]:
        sk = self._edge_shard.get(key)
        if sk is not None:
            shard = self.shards.get(sk)
            edge = shard.edges.get(key) if shard is not None else None
            if edge is not None:
                return edge
            del self._edge_shard[key]
        for sk, shard in self.shards.items():
            edge = shard.edges.get(key)
            if edge is not None:
                self._edge_shard[key] = sk
                return edge
        return None

    @staticmethod
    def _store_edge_id(edge: Edge) -> str:
        """Matches ArrowStore's derived edge id (src|tgt|type)."""
        return f"{edge.source}|{edge.target}|{edge.edge_type}"

    def _mark_edge_deleted(self, edge: Edge) -> None:
        self._deleted_edge_ids.add(self._store_edge_id(edge))
        self._dirty_edges.discard((edge.source, edge.target))

    # --------------------------------------------------------------- session
    def start_conversation(self) -> str:
        with self.telemetry.span("api.start_conversation"):
            if self._recovered_turns and self.conversation_active and self.short_term_memory:
                # Crash-recovered turns must not be discarded by the normal
                # "/start clears the buffer" flow — consolidate them first.
                self._log("🛟 Consolidating recovered turns before new conversation...")
                self.end_conversation()
            self._recovered_turns = False
            self.conversation_active = True
            self.short_term_memory = []
            self.conversation_history = []
            with self._mutex:
                self._journal_sync()   # drops abandoned-conversation turns
            return "✓ Conversation started"

    def add_to_short_term(self, content: str, memory_type: str = "semantic",
                          salience: float = 0.5) -> None:
        if not self.conversation_active:
            raise RuntimeError("No active conversation")
        turn = {
            "content": content,
            "type": memory_type,
            "salience": salience,
            "timestamp": time.time(),
        }
        with self._mutex:
            # Mutex covers both the buffer append and the WAL append so a
            # concurrent _journal_sync rewrite can't interleave and duplicate
            # this turn in the journal.
            self.short_term_memory.append(turn)
            self._journal_turn(turn)
        self._auto_save_if_needed()

    def _auto_save_if_needed(self) -> None:
        # Saving happens at end/consolidation (parity: no-op stub :238-240).
        pass

    def end_conversation(self) -> str:
        if not self.conversation_active:
            return "⚠ No active conversation to end."
        if not self.short_term_memory:
            self.conversation_active = False
            self._recovered_turns = False
            return "✓ Conversation ended. No memories to consolidate."
        # one span per conversation that consolidates: the per-conversation
        # metrics divide by their number
        with self.telemetry.span("api.end_conversation"):
            return self._end_conversation()

    def _end_conversation(self) -> str:
        results = []
        n_turns = len(self.short_term_memory)
        with self._mutex:
            # One atomic transition: buffer → queue and conversation closed.
            # A background _journal_sync observing intermediate state would
            # otherwise see the turns in neither place and wipe them from
            # the WAL.
            self.consolidation_queue.append({
                "memories": self.short_term_memory.copy(),
                "timestamp": time.time(),
            })
            self.conversation_active = False
            self._recovered_turns = False
            self.short_term_memory = []
        if self.enable_async and self.background_executor:
            self._log(f"🔄 Queueing consolidation for {n_turns} exchanges...")
            self.background_executor.submit(self._async_consolidate)
            self._status(results, "✓ Conversation ended (consolidation queued)")
        else:
            self._log(f"🔄 Consolidating {n_turns} exchanges...")
            self._async_consolidate()
            nodes, edges = self.buffer.size()
            self._status(results, f"✓ Consolidation complete. Memory: {nodes} nodes, {edges} edges")

        with self.telemetry.span("write.decay"), self._mutex:
            # Deferred cache-hit boosts land BEFORE the decay sweep, so the
            # batched flush reproduces the classic boost-then-decay order.
            self._flush_pending_boosts_locked()
            self.index.decay(self.user_id, self.config.decay_rate,
                             self.config.salience_floor)
            self._decay_pass += 1
            if self.auto_prune:
                pruned = self._prune_weak_edges(self.prune_threshold)
                if pruned > 0:
                    self._status(results, f"✓ Auto-pruned {pruned} weak edges")
            # Small graphs keep every host copy exactly fresh (parity
            # surfaces read node.salience directly); at scale the dirty rows
            # are synced inside the save itself and clean rows are
            # reconstructed on load by the closed-form decay replay.
            if len(self.index) <= self._SYNC_FULL_MAX:
                self._sync_from_arena()
        self._status(results, "✓ Applied temporal decay")

        self._enforce_buffer_limit()
        self.conversation_count += 1

        if self.auto_consolidate and self.conversation_count % self.consolidate_every == 0:
            self._log(f"🔄 Auto-consolidation triggered (every {self.consolidate_every} conversations)...")
            results.append(self.run_consolidation(persist=False))

        self.short_term_memory = []
        self.conversation_history = []
        self._save_to_persistence()
        return "\n".join(results)

    def _prune_weak_edges(self, threshold: float) -> int:
        """Device prune + host structural cleanup; returns count removed."""
        removed = self.index.prune_edges(self.user_id, threshold)
        count = 0
        for qsrc, qtgt in removed:
            key = (qsrc.partition(":")[2], qtgt.partition(":")[2])
            edge = self._find_edge(key)
            if edge is not None:
                self._mark_edge_deleted(edge)
                del self.shards[self._edge_shard.pop(key)].edges[key]
                count += 1
        if self.query_cache:
            # scoped flush: only this tenant's graph changed (ISSUE 19
            # satellite — the old all-tenant flush threw away every other
            # tenant's warm results on each prune)
            self.query_cache.invalidate_results(self.user_id)
        return count

    # ---------------------------------------------------- lifecycle (ISSUE 19)
    def lifecycle_tick(self, now: Optional[float] = None,
                       force: bool = False) -> Dict[str, object]:
        """ONE all-tenant maintenance sweep: salience decay, edge decay +
        weak-edge prune, and importance-ranked archive verdicts (bottom-k
        per tenant, fed to the TierPump demote queue — "archived" means
        demoted-to-cold, never deleted), all in one donated dispatch + one
        packed readback (``MemoryIndex.lifecycle_sweep``).

        Scheduler-aware: while the serving scheduler reports queued work
        the tick defers (``lifecycle.deferred_busy``) instead of queueing
        maintenance behind live traffic — correctness never depends on
        this (the donation gate already serializes state handoff), only
        tail latency does. ``config.lifecycle_fused = False`` runs the
        classic host loop instead — the A/B + bit-parity oracle."""
        sched = self.query_scheduler
        if (not force and sched is not None and not sched.closed
                and sched.load() > self.config.lifecycle_busy_load):
            self.telemetry.bump("lifecycle.deferred_busy")
            return {"deferred": True}
        cfg = self.config
        t0 = time.perf_counter()
        with self._mutex:
            passes = {t: 1 for t in self.index._tenants}
            if cfg.lifecycle_fused:
                out = self.index.lifecycle_sweep(
                    passes, rate=cfg.decay_rate,
                    salience_floor=cfg.salience_floor,
                    prune_threshold=cfg.prune_threshold,
                    weights=(cfg.importance_w_salience,
                             cfg.importance_w_access,
                             cfg.importance_w_recency),
                    archive_k=cfg.lifecycle_archive_k, now=now)
            else:
                out = self._lifecycle_classic(passes, now=now)
            self._decay_pass += 1
            out["pruned_hosts"] = self._lifecycle_cleanup(out)
            if len(self.index) <= self._SYNC_FULL_MAX:
                self._sync_from_arena()
        tiering = self.index.tiering
        out["archived"] = 0
        if tiering is not None and cfg.lifecycle_archive_k:
            rows = [row for pairs in out["verdicts"].values()
                    for (_nid, _imp, row) in pairs]
            out["archived"] = tiering.queue_demotions(rows)
        out["deferred"] = False
        wall_ms = (time.perf_counter() - t0) * 1e3
        self.telemetry.record("lifecycle.sweep_ms", wall_ms)
        self.telemetry.bump("lifecycle.ticks")
        self.telemetry.bump(
            "lifecycle.archive_verdicts",
            sum(len(v) for v in out["verdicts"].values()))
        return out

    def _lifecycle_classic(self, passes: Dict[str, int],
                           now: Optional[float] = None) -> Dict[str, object]:
        """The host-driven per-tenant loop the fused sweep replaces — kept
        as the A/B + bit-parity oracle: same decay/prune/verdict
        arithmetic, but 3 device round trips per tenant per pass and a
        host stall between each."""
        cfg = self.config
        removed: List[Tuple[str, str]] = []
        verdicts: Dict[str, List[Tuple[str, float, int]]] = {}
        dispatches = 0
        for tenant, owed in passes.items():
            for _ in range(max(0, int(owed))):
                self.index.decay(tenant, cfg.decay_rate, cfg.salience_floor)
                removed.extend(self.index.prune_edges(tenant,
                                                      cfg.prune_threshold))
                dispatches += 2
            if cfg.lifecycle_archive_k:
                cand = self.index.evict_candidates(
                    tenant, cfg.lifecycle_archive_k, now=now,
                    weights=(cfg.importance_w_salience,
                             cfg.importance_w_access,
                             cfg.importance_w_recency))
                verdicts[tenant] = [
                    (nid, imp, self.index.id_to_row.get(nid, -1))
                    for nid, imp in cand]
                dispatches += 1
        return {"verdicts": verdicts, "removed_edges": removed,
                "pruned_edges": len(removed), "dispatches": dispatches}

    def _lifecycle_cleanup(self, out: Dict[str, object]) -> int:
        """Host structural cleanup after a sweep: mirror deletion for the
        CURRENT user's pruned edges (foreign tenants have no host mirror
        loaded — their device/edge-slot state is already consistent) and
        per-tenant query-cache flushes scoped to whoever actually pruned."""
        touched: Set[str] = set()
        count = 0
        for qsrc, qtgt in out.get("removed_edges", ()):
            tenant = qsrc.partition(":")[0]
            touched.add(tenant)
            key = (qsrc.partition(":")[2], qtgt.partition(":")[2])
            if key not in self._edge_shard:
                continue
            edge = self._find_edge(key)
            if edge is not None:
                self._mark_edge_deleted(edge)
                del self.shards[self._edge_shard.pop(key)].edges[key]
                count += 1
        if self.query_cache:
            for tenant in touched:
                self.query_cache.invalidate_results(tenant)
        return count

    # ------------------------------------------------------------------ chat
    def chat(self, user_message: str) -> str:
        if not self.conversation_active:
            self._log(self.start_conversation())

        start_time = time.time()
        self.add_to_short_term(user_message, "episodic", salience=0.7)
        self.conversation_history.append({"role": "user", "content": user_message})

        query_emb = self._get_embedding(user_message)
        retrieved_ids, boost_mode = self._retrieve_for_chat(query_emb,
                                                            user_message)
        self._boost_neighbors(retrieved_ids, mode=boost_mode)

        retrieval_time = (time.time() - start_time) * 1000
        self.telemetry.record("chat.retrieval_ms", retrieval_time,
                              labels={"tenant": self.user_id})

        messages = self._assemble_messages(retrieved_ids, mode=boost_mode)
        response = self._call_llm(messages)
        self.add_to_short_term(response, "semantic", salience=0.5)
        self.conversation_history.append({"role": "assistant", "content": response})

        self._log(f"[{Telemetry.tier(retrieval_time)} Retrieval: "
                  f"{retrieval_time:.0f}ms, Retrieved: {len(retrieved_ids)} nodes]")
        if retrieved_ids and self.verbose:
            self._log("   Retrieved Nodes:")
            for nid in retrieved_ids:
                node = self.buffer.get_node(nid)
                if node:
                    snippet = node.content[:60] + "..." if len(node.content) > 60 else node.content
                    self._log(f"   • [{nid}] ({node.shard_key}) {snippet}")
        return response

    def chat_stream(self, user_message: str) -> Iterator[Dict[str, str]]:
        """Yields {"type": "info"|"token", "content": ...} dicts (parity :353-451)."""
        if not self.conversation_active:
            self.start_conversation()
            yield {"type": "info", "content": "✓ Conversation started"}

        start_time = time.time()
        self.add_to_short_term(user_message, "episodic", salience=0.7)
        self.conversation_history.append({"role": "user", "content": user_message})

        query_emb = self._get_embedding(user_message)
        retrieved_ids, boost_mode = self._retrieve_for_chat(query_emb,
                                                            user_message)
        self._boost_neighbors(retrieved_ids, mode=boost_mode)

        retrieval_time = (time.time() - start_time) * 1000
        self.telemetry.record("chat.retrieval_ms", retrieval_time,
                              labels={"tenant": self.user_id})
        yield {"type": "info",
               "content": f"[{Telemetry.tier(retrieval_time)} Retrieval: "
                          f"{retrieval_time:.0f}ms, Retrieved: {len(retrieved_ids)} nodes]"}

        messages = self._assemble_messages(retrieved_ids, mode=boost_mode)
        self.metrics["llm_calls"] += 1
        chunks: List[str] = []
        if hasattr(self.llm, "completion_stream"):
            for chunk in self.llm.completion_stream(messages):
                chunks.append(chunk)
                yield {"type": "token", "content": chunk}
            response = "".join(chunks)
        else:
            response = self.llm.completion(messages)
            yield {"type": "token", "content": response}

        self.add_to_short_term(response, "semantic", salience=0.5)
        self.conversation_history.append({"role": "assistant", "content": response})

    def _assemble_messages(self, retrieved_ids: List[str],
                           mode: str = "classic") -> List[Dict[str, str]]:
        """``mode`` says who pays the access-boost device scatter:
        "classic" dispatches it here (the pre-fused behavior), "device"
        means the fused retrieval kernel already applied it in the same
        dispatch that found the ids, and "deferred" (query-cache hits)
        accumulates counts for one batched flush — a cached turn costs
        ZERO device round trips. Host copies update in every mode."""
        context_parts = []
        profile_context = self.profile.get_context()
        if profile_context and profile_context != "No profile data yet.":
            context_parts.append(f"User Profile:\n{profile_context}\n")

        if retrieved_ids:
            memory_texts = []
            access_ids = []
            for nid in retrieved_ids:
                node = self.buffer.get_node(nid)
                if node:
                    memory_texts.append(f"- {node.content}")
                    access_ids.append(nid)
            if access_ids:
                with self._mutex:
                    if mode == "classic":
                        self.index.update_access(
                            [self._q(n) for n in access_ids],
                            boost=self.config.access_salience_boost)
                    elif mode == "deferred":
                        now = time.time()
                        for nid in access_ids:
                            self._queue_boost(nid, acc=1, now=now)
                    self._mark_dirty(*access_ids)
                for nid in access_ids:
                    self.buffer.update_access(nid, self.config.access_salience_boost)
            if memory_texts:
                context_parts.append(
                    "Relevant Information from Past Conversations (Use if relevant to the query):\n"
                    + "\n".join(memory_texts) + "\n")

        system_prompt = ("You are a helpful assistant with access to the user's profile "
                         "and past memories. Use the provided context ONLY if it is relevant "
                         "to the user's current query. Do not force the information if it "
                         "doesn't fit naturally.")
        messages = [{"role": "system", "content": system_prompt}]
        if context_parts:
            messages.append({"role": "system", "content": "\n".join(context_parts)})
        messages.extend(self.conversation_history[-self.config.history_window:])
        return messages

    # ------------------------------------------------------------- retrieval
    def _optimized_retrieval(self, query_emb: List[float], query_text: str) -> List[str]:
        if self.query_cache:
            # keyed by (tenant, text): two tenants asking the same
            # question must never see each other's node ids
            cached = self.query_cache.get_results(query_text,
                                                  tenant=self.user_id)
            if cached:
                return cached

        q = np.asarray(query_emb, np.float32)
        retrieved: List[str] = []

        # 1. Hierarchy fast path: one masked top-k over super-node rows
        #    (replaces the O(#super × d) Python scan, memory_system.py:464-482).
        if self.enable_hierarchy and self.super_nodes:
            # threshold-gated decision (0.4 super-node gate): always the
            # exact master — approximate serving modes could flip it
            sids, sscores = self.index.search(q, self.user_id, k=1,
                                              super_filter=1, exact=True)
            if sids and sscores[0] > self.config.super_node_gate:
                best = self.super_nodes.get(sids[0].partition(":")[2])
                if best is not None:
                    for child_id in best.child_ids[:self.config.hierarchy_children]:
                        child = self.buffer.get_node(child_id)
                        if child and not child.is_super_node:
                            retrieved.append(child_id)
                    if len(retrieved) >= self.config.retrieval_cap:
                        result = retrieved[:self.config.retrieval_cap]
                        if self.query_cache:
                            self.query_cache.set_results(
                                query_text, result, tenant=self.user_id)
                        return result

        # 2. Arena ANN (replaces LanceDB search_nodes)
        limit = self.config.ann_limit if not retrieved else self.config.retrieval_cap
        vec_ids, _ = self.index.search(q, self.user_id, k=limit, super_filter=-1)
        vector_ids = [v.partition(":")[2] for v in vec_ids]

        seen_ids: Set[str] = set(retrieved)
        seen_content: Set[str] = set()
        final: List[str] = []
        for rid in retrieved:
            node = self.buffer.get_node(rid)
            if node:
                seen_content.add(node.content)
                final.append(rid)
        for rid in vector_ids:
            if rid in seen_ids:
                continue
            node = self.buffer.get_node(rid)
            if node and node.content not in seen_content:
                seen_content.add(node.content)
                final.append(rid)
                seen_ids.add(rid)

        final = final[:self.config.retrieval_cap]
        if self.query_cache:
            self.query_cache.set_results(query_text, final,
                                         tenant=self.user_id)
        return final

    def _boost_neighbors(self, retrieved_ids: List[str],
                         mode: str = "classic") -> None:
        """Associative neighbor boost. ``mode`` as in
        ``_assemble_messages``: "device" skips the dispatch (the fused
        kernel's CSR gather already scattered it), "deferred" queues
        counts for the batched flush; host-side Node copies and dirty
        marks update in every mode."""
        neighbors: Set[str] = set()
        for nid in retrieved_ids:
            neighbors.update(self.buffer.get_neighbors(nid))
        to_boost = [n for n in neighbors if n not in set(retrieved_ids)]
        if not to_boost:
            return
        now = time.time()
        with self._mutex:
            if mode == "classic":
                self.index.boost([self._q(n) for n in to_boost],
                                 self.config.neighbor_salience_boost, now)
            elif mode == "deferred":
                for n in to_boost:
                    self._queue_boost(n, nbr=1, now=now)
            self._mark_dirty(*to_boost)
        count = 0
        for nid in to_boost:
            node = self.buffer.get_node(nid)
            if node:
                node.last_accessed = now
                node.salience = min(1.0, node.salience + self.config.neighbor_salience_boost)
                count += 1
        if count:
            self._log(f"   (Graph: Boosted {count} neighbor nodes via association)")

    # ----------------------------------------------------------- fused serving
    def _use_fused_serving(self) -> bool:
        """Fused retrieval serves every arena mode — exact by default,
        through the quantized two-stage kernel (int8 coarse scan + exact
        rescore, ``state.search_fused_quant_ragged``) when the int8 serving
        shadow is on, and through the IVF coarse stage (centroid prefilter +
        member gather INSIDE the dispatch, ``state.search_fused_ivf_ragged``)
        once a build is published — so quantized AND IVF modes keep the
        one-dispatch turn, cross-request mega-batching, and zero-RTT cache
        hits (``MemoryIndex.search_fused_requests`` owns the routing; an
        IVF config with no build yet serves the dense fused path). Under a
        MESH the same request flow routes to the distributed shard_map
        program (``state.make_fused_sharded`` via the index's pod
        dispatch, ISSUE 5) — shard-local scan, one all_gather merge,
        shard-local boost scatters — so the pod path keeps the gate /
        neighbor / boost semantics and the one-distributed-dispatch turn
        too. PQ member storage joined the fused path last (ISSUE 16,
        ``state.search_fused_pq_ragged``: in-kernel ADC table build + m-byte
        member scan + exact shortlist rescore), so every serving mode now
        keeps the one-dispatch contract — ``serve_fused`` alone decides."""
        return self.config.serve_fused

    def _ensure_scheduler(self) -> QueryScheduler:
        """Lazily spawn the cross-request query scheduler (one per system).
        It keeps donated state mutation single-writer on the serving
        side: one dispatch at a time, except a full batch of pure reads
        admitted over a batch of pure reads in flight — which batches
        those are is the index's word (``reads_may_overlap``)."""
        sched = self.query_scheduler
        if sched is not None and not sched.closed:
            return sched
        with self._mutex:
            sched = self.query_scheduler
            if sched is None or sched.closed:
                sched = QueryScheduler(
                    self._serve_requests,
                    max_batch=self.config.serve_batch_max,
                    telemetry=self.telemetry,
                    tenant_max_inflight=self.config.serve_tenant_max_inflight,
                    dispatch_timeout_s=self.config.serve_dispatch_timeout_s,
                    breaker_threshold=self.config.serve_breaker_threshold,
                    breaker_cooldown_s=self.config.serve_breaker_cooldown_s,
                    shed_depth=self.config.serve_shed_depth,
                    shed_bytes=self.config.serve_shed_bytes,
                    degrade_cap_take=self.config.serve_degrade_cap_take,
                    degrade_nprobe=self.config.serve_degrade_nprobe,
                    admission_check=self._plan_admission,
                    overlap_check=self._reads_may_overlap)
                self.query_scheduler = sched
        return sched

    def _plan_admission(self, reqs) -> None:
        """Scheduler admission probe (ISSUE 11): a submission whose
        MINIMUM geometry — one pad bucket, maximal chunking — no split
        can fit raises the typed ``PlanInfeasible`` before it queues
        (shed like LoadShed; larger coalesced batches split fine, so
        only the truly impossible are rejected here)."""
        planner = self.index.planner
        if planner is None or not planner.active \
                or not self.index.id_to_row:
            return
        route = self.index._serve_route(self.config.retrieval_cap)
        planner.check_feasible(
            self.index._serve_geometry(1, route.mode, route.k_bucket),
            chunkable=self.index.mesh is None)

    def _reads_may_overlap(self, reqs) -> bool:
        """Scheduler overlap predicate: the index that executes the batch
        vouches for it (looked up per call: a restore swaps the index)."""
        return self.index.reads_may_overlap(reqs)

    def _serve_requests(self, reqs: List[RetrievalRequest]):
        """Scheduler executor: ONE fused device dispatch + ONE packed
        readback for the whole coalesced batch."""
        return self.index.search_fused_requests(
            reqs, cap_take=self.config.retrieval_cap,
            max_nbr=self.config.serve_max_nbr,
            super_gate=self.config.super_node_gate,
            acc_boost=self.config.access_salience_boost,
            nbr_boost=self.config.neighbor_salience_boost)

    def warmup_serving(self, geometries=(8, 64)):
        """Pre-compile the fused serving kernels for the given query-batch
        geometries with THIS system's serving parameters (ISSUE 7
        satellite: the first live request must not eat a cold multi-second
        XLA compile). Call after the corpus/edge graph are in place —
        bench.py does, right before its timed sections. Warmup wall time
        lands in ``kernel.warmup_ms{mode,batch}``."""
        return self.index.warmup_serving(
            geometries, cap_take=self.config.retrieval_cap,
            max_nbr=self.config.serve_max_nbr,
            super_gate=self.config.super_node_gate,
            acc_boost=self.config.access_salience_boost,
            nbr_boost=self.config.neighbor_salience_boost,
            k=self.config.serve_k_max)

    def _retrieve_for_chat(self, query_emb: List[float],
                           query_text: str) -> Tuple[List[str], str]:
        """Chat-turn retrieval front door. Returns ``(ids, boost_mode)``:

        - query-cache hit → "deferred": ZERO device round trips this turn;
          the access/neighbor boosts accumulate host-side and flush later
          as one batched scatter (cached hits used to pay the full device
          boost sequence anyway).
        - fused serving → "device" when the kernel applied both boosts in
          the same dispatch that found the ids, or "classic" when the
          super-gate fired (the host owns the hierarchy fast path and pays
          the classic boosts for exact parity).
        - otherwise → the classic multi-dispatch ``_optimized_retrieval``.
        """
        if self.query_cache:
            cached = self.query_cache.get_results(query_text,
                                                  tenant=self.user_id)
            if cached:
                return cached, "deferred"
        if not self._use_fused_serving():
            return self._optimized_retrieval(query_emb, query_text), "classic"
        req = RetrievalRequest(
            query=np.asarray(query_emb, np.float32),
            tenant=self.user_id, k=self.config.ann_limit,
            gate_enabled=bool(self.enable_hierarchy and self.super_nodes),
            boost=True)
        res = self._ensure_scheduler().submit(req).result()
        final = self._merge_fused_retrieval(res, query_text)
        return final, ("device" if res.boosted else "classic")

    def _merge_fused_retrieval(self, res, query_text: str) -> List[str]:
        """Host half of the fused chat retrieval: the same hierarchy-children
        expansion and content-dedup merge as ``_optimized_retrieval``, fed
        from the kernel's packed (gate, ANN) result instead of two separate
        device searches."""
        retrieved: List[str] = []
        if res.fast and res.gate_id is not None:
            best = self.super_nodes.get(res.gate_id.partition(":")[2])
            if best is not None:
                for child_id in best.child_ids[:self.config.hierarchy_children]:
                    child = self.buffer.get_node(child_id)
                    if child and not child.is_super_node:
                        retrieved.append(child_id)
                if len(retrieved) >= self.config.retrieval_cap:
                    result = retrieved[:self.config.retrieval_cap]
                    if self.query_cache:
                        self.query_cache.set_results(
                            query_text, result, tenant=self.user_id)
                    return result
        vector_ids = [v.partition(":")[2] for v in res.ids]
        seen_ids: Set[str] = set(retrieved)
        seen_content: Set[str] = set()
        final: List[str] = []
        for rid in retrieved:
            node = self.buffer.get_node(rid)
            if node:
                seen_content.add(node.content)
                final.append(rid)
        for rid in vector_ids:
            if rid in seen_ids:
                continue
            node = self.buffer.get_node(rid)
            if node and node.content not in seen_content:
                seen_content.add(node.content)
                final.append(rid)
                seen_ids.add(rid)
        final = final[:self.config.retrieval_cap]
        if self.query_cache:
            self.query_cache.set_results(query_text, final,
                                         tenant=self.user_id)
        return final

    def _queue_boost(self, node_id: str, acc: int = 0, nbr: int = 0,
                     now: Optional[float] = None) -> None:
        """Accumulate a deferred boost for ``node_id`` (callers hold
        ``self._mutex``). Cache-hit chat turns queue counts here instead of
        paying a device dispatch; ``_flush_pending_boosts`` applies many
        turns' worth in ONE donated scatter."""
        ent = self._pending_boosts.get(node_id)
        if ent is None:
            ent = self._pending_boosts[node_id] = [0, 0, 0.0]
        ent[0] += acc
        ent[1] += nbr
        ent[2] = max(ent[2], now if now is not None else time.time())
        if len(self._pending_boosts) >= self.config.serve_boost_flush_max:
            self._flush_pending_boosts_locked()

    def _flush_pending_boosts(self) -> None:
        with self._mutex:
            self._flush_pending_boosts_locked()

    def _flush_pending_boosts_locked(self) -> None:
        """Apply every queued (access, neighbor) boost count as one donated
        scatter. Runs before anything that READS arena salience — decay,
        eviction scoring, consolidation, and saves (``_sync_from_arena``
        would otherwise overwrite boosted host copies with stale arena
        values)."""
        if not self._pending_boosts:
            return
        entries = {self._q(nid): (acc, nbr, ts)
                   for nid, (acc, nbr, ts) in self._pending_boosts.items()}
        self._pending_boosts.clear()
        self.index.apply_boosts(entries, self.config.access_salience_boost,
                                self.config.neighbor_salience_boost)

    # ---------------------------------------------------------- consolidation
    _EXTRACTION_PROMPT = """Extract distinct, atomic facts from this conversation.
Categorization Guidelines:
1. semantic: Stable facts, preferences, or knowledge (e.g., "User likes Python", "User lives in London").
2. episodic: Specific events, occurrences, or recent activities (e.g., "User started a new job today", "User fixed a bug in the API").
3. procedural: Processes, workflows, or instructions (e.g., "User follows the git-flow model", "User prefers TDD for testing").

Format Rules:
- Formulate facts in the THIRD PERSON.
- Abstract from conversational filler.
- If no new facts, return empty list.

Return JSON: {"memories": [{"content": "...", "type": "semantic|episodic|procedural", "salience": 0.0-1.0, "topic": "work|personal|learning|health|other"}]}
"""

    def _async_consolidate(self) -> None:
        """Crash-surviving wrapper (ISSUE 10 satellite): the consolidation
        worker runs on a ThreadPoolExecutor whose futures nobody reads, so
        an uncaught exception used to strand the in-flight batches forever
        — silently. Any failure now requeues the turns for the next
        consolidation pass (they stay WAL-journaled meanwhile); if their
        facts were already extracted + journaled, the in-dispatch dedup
        probe collapses the re-extraction into merges."""
        try:
            self._consolidate_once()
        except Exception as e:      # noqa: BLE001 — worker must survive
            self._log(f"⚠ Consolidation worker error: {e!r} "
                      f"(turns requeued for retry)")
            self.telemetry.bump("reliability.ingest_failures")
            self._requeue_inflight()

    def _consolidate_once(self) -> None:
        with self._mutex:
            if not self.consolidation_queue:
                return
            all_memories: List[Dict] = []
            for batch in self.consolidation_queue:
                all_memories.extend(batch["memories"])
            # Move (don't drop) the batches to the in-flight list: they stay
            # visible to _journal_sync until durable, so a concurrent
            # start_conversation can't compute an empty turn set and wipe
            # the WAL while the LLM call below is still running.
            self._inflight_batches.extend(self.consolidation_queue)
            self.consolidation_queue.clear()

        start_time = time.time()
        self._log(f"🔄 Processing {len(all_memories)} memories in background...")

        with self.telemetry.span("write.extract"):
            conv_text = json.dumps(all_memories)
            response = self._call_llm(
                [{"role": "system", "content": self._EXTRACTION_PROMPT},
                 {"role": "user", "content": conv_text}],
                response_format={"type": "json_object"})

            try:
                data = json.loads(_extract_json_object(response))
                if isinstance(data, dict):
                    memories = data.get("memories", [])
                elif isinstance(data, list):
                    memories = data
                else:
                    self._log(f"⚠ Unexpected data type: {type(data)}")
                    self._requeue_inflight()
                    return
            except json.JSONDecodeError as e:
                self._log(f"⚠ Parse error: {e}")
                self._requeue_inflight()
                return

            memories = [m for m in memories if isinstance(m, dict)]
        self._log(f"✓ Extracted {len(memories)} memory candidates")
        # Durable ingest journal (ISSUE 10): the facts become durable the
        # moment extraction returns — BEFORE the coalescer buffers them —
        # so a crash anywhere between here and the fused dispatch loses
        # nothing (startup replay + dedup probe make recovery idempotent).
        if self._ingest_journal is not None and memories:
            with self.telemetry.span("journal.append"):
                try:
                    self._ingest_journal.append(memories)
                except OSError as e:
                    self._log(f"⚠ Ingest journal append failed: {e}")
        # Fault point "ingest.worker" (ISSUE 10): a raise here models the
        # consolidation worker dying between extraction and ingest.
        from lazzaro_tpu.reliability import faults as _faults
        _faults.fire("ingest.worker", facts=len(memories))
        # Cross-conversation coalescing: this extraction already covers
        # every queued conversation (one LLM call over the drained queue);
        # the coalescer merges it with anything still buffered and hands
        # back bounded mega-batches — each ingested by ONE fused dispatch.
        # A split (huge extraction) is logged, never silent.
        self._ingest_coalescer.add_conversation(memories)
        if not self._ingest_coalescer.should_flush():
            # Time/size policy says wait (trickle load, ingest_flush_wait_s
            # > 0): the facts stay buffered for a denser fused dispatch and
            # their source turns stay journaled via _deferred_batches until
            # they actually land in the arena.
            with self._mutex:
                self._deferred_batches.extend(self._inflight_batches)
                self._inflight_batches.clear()
                self._journal_sync()
            self._log(f"⏳ Ingest deferred: {len(self._ingest_coalescer)} "
                      "facts buffered by the flush policy")
            return
        # Per-batch coalesce-wait span (ISSUE 9 satellite): how long the
        # oldest buffered conversation waited for its mega-batch — the
        # write-path twin of the serving queue-wait span, so the
        # ingest_flush_wait_s trade (denser dispatches vs added latency)
        # is measured, not guessed.
        coalesce_wait_ms = self._ingest_coalescer.oldest_age_s() * 1e3
        # Everything the drain pops is covered by journal sequences up to
        # here; captured BEFORE the drain so facts appended concurrently
        # are never committed by this pass.
        commit_to = (self._ingest_journal.last_seq
                     if self._ingest_journal is not None else 0)
        mega_batches = self._ingest_coalescer.drain()
        if len(mega_batches) > 1:
            self._log(f"   (ingest split into {len(mega_batches)} mega-"
                      f"batches of ≤ {self._ingest_coalescer.max_facts} facts)")
        new_nodes: List[Tuple[str, str]] = []
        done = 0
        try:
            for facts, _n_convs in mega_batches:
                self.telemetry.record("ingest.coalesce_wait_ms",
                                      coalesce_wait_ms)
                new_nodes.extend(self._ingest_facts(facts))
                done += 1
        except Exception as e:      # noqa: BLE001 — ingest must not strand
            # An ingest dispatch failed (ISSUE 10): the un-ingested
            # mega-batches go BACK to the front of the coalescer (they
            # retry on the next flush) and their source turns move to the
            # deferred set so the WAL keeps covering them; the ingest
            # journal still holds every fact uncommitted.
            self._ingest_coalescer.requeue(mega_batches[done:])
            self.telemetry.bump("reliability.ingest_failures")
            with self._mutex:
                self._deferred_batches.extend(self._inflight_batches)
                self._inflight_batches.clear()
                self._journal_sync()
            self._log(f"⚠ Ingest failed after {done}/{len(mega_batches)} "
                      f"mega-batches ({e!r}); facts requeued, journal "
                      f"retains them")
            return

        self._finish_consolidation(new_nodes, start_time)
        if self._ingest_journal is not None:
            # append → dispatch → COMMIT: every drained fact is durable in
            # the arena + store now, so the journal can retire them.
            self._commit_ingest_journal(commit_to)

    def _ingest_facts(self, memories: List[Dict]) -> List[Tuple[str, str]]:
        """Stage, dedup, and ingest one mega-batch of extracted facts;
        returns the (node_id, shard_key) pairs created."""
        with self.telemetry.span("write.embed"):
            contents = [m.get("content", "") for m in memories
                        if m.get("content")]
            embeddings = self._batch_embed(contents)
            try:
                # one bulk list→array conversion for the whole batch (per-fact
                # np.asarray over float lists was ~30% of ingest host time)
                emb_rows = np.asarray(embeddings, np.float32)
                if emb_rows.ndim != 2:
                    raise ValueError
            except (ValueError, TypeError):    # ragged/failed rows: per-item
                emb_rows = None

        with self._mutex:
            # Stage valid facts, then resolve near-duplicates with two
            # batched similarity ops instead of one device probe per fact:
            # (a) ONE arena top-1 search for the whole batch (pre-batch
            #     graph — the same visibility the reference's LanceDB probe
            #     has, since its batch insert also lands after the loop);
            # (b) one host gram matrix for duplicates WITHIN the batch.
            staged: List[Tuple[Dict, str, np.ndarray]] = []
            ei = 0
            empty = np.empty((0,), np.float32)
            for mem in memories:
                content = mem.get("content", "")
                if not content:
                    continue
                if ei < len(embeddings):
                    new_emb = (emb_rows[ei] if emb_rows is not None
                               else np.asarray(embeddings[ei], np.float32))
                else:
                    new_emb = empty
                ei += 1
                if len(content) < 5:
                    continue
                staged.append((mem, content, new_emb))

            if (self.config.ingest_fused and self.config.ingest_dedup_fused
                    and staged
                    and all(e.size == self.embed_dim for _, _, e in staged)):
                # Truly single-round-trip ingest: the dedup probe below
                # (pre-add top-1 + intra-batch gram) rides INSIDE the fused
                # device program instead of paying its own dispatch.
                return self._ingest_facts_dedup_fused(staged)

            with self.telemetry.span("write.prepare"):
                probe: List[Tuple[Optional[str], float]] = [(None, 0.0)] * len(staged)
                probeable = [i for i, (_, _, e) in enumerate(staged)
                             if e.size == self.embed_dim]
                if probeable:
                    qs = np.stack([staged[i][2] for i in probeable])
                    res = self.index.search_batch(qs, self.user_id, k=1,
                                                  super_filter=-1, exact=True)
                    for i, (ids, scores) in zip(probeable, res):
                        if ids:
                            probe[i] = (ids[0].partition(":")[2], scores[0])
                intra_best_col = intra_best_sim = None
                if len(probeable) >= 2:
                    M = np.stack([staged[i][2] for i in probeable])
                    norms = np.linalg.norm(M, axis=1, keepdims=True)
                    norms[norms == 0] = 1.0
                    M = M / norms
                    intra = M @ M.T
                    # Per row, the best match among EARLIER batch rows — one
                    # vectorized masked argmax instead of an O(B²) Python scan.
                    n_p = len(probeable)
                    tril = np.where(np.tri(n_p, k=-1, dtype=bool), intra, -np.inf)
                    intra_best_col = np.argmax(tril, axis=1)
                    intra_best_sim = tril[np.arange(n_p), intra_best_col]
                pos_in_probeable = {i: j for j, i in enumerate(probeable)}

                new_nodes: List[Tuple[str, str]] = []
                new_nodes_data: List[Dict] = []
                created: List[Node] = []
                created_embs: List[np.ndarray] = []
                merge_ids: List[str] = []
                merge_sals: List[float] = []
                fact_target: List[Optional[str]] = []  # node id each fact resolved to
                for fi, (mem, content, new_emb) in enumerate(staged):
                    shard_key = mem.get("topic") or self._infer_shard_key(content)
                    if shard_key == "other":
                        shard_key = self._infer_shard_key(content)
                    shard = self._get_or_create_shard(shard_key)

                    # Best match: pre-batch arena probe vs earlier-in-batch fact.
                    target_id, best = probe[fi]
                    if intra_best_sim is not None and fi in pos_in_probeable:
                        row = pos_in_probeable[fi]
                        sim = float(intra_best_sim[row])
                        if sim > best:
                            t = fact_target[probeable[int(intra_best_col[row])]]
                            if t is not None:
                                target_id, best = t, sim
                    existing_node = (self.buffer.get_node(target_id)
                                     if target_id is not None
                                     and best > self.config.dedup_similarity
                                     else None)

                    if existing_node is not None:
                        cand_sal = float(mem.get("salience", 0.5))
                        existing_node.salience = max(existing_node.salience, cand_sal)
                        existing_node.last_accessed = time.time()
                        existing_node.access_count += 1
                        merge_ids.append(existing_node.id)
                        merge_sals.append(cand_sal)
                        self._mark_dirty(existing_node.id)
                        fact_target.append(existing_node.id)
                        self._log(f"   (Merged semantic duplicate into {existing_node.id})")
                        continue

                    node_id = self._generate_node_id()
                    # The arena owns the vector (embedding=None on the host);
                    # keeping a Python float-list per node is what made 1M-node
                    # host graphs impossible. Persistence gathers on demand.
                    node = Node(
                        id=node_id,
                        content=content,
                        embedding=None,
                        type=mem.get("type", "semantic"),
                        salience=float(mem.get("salience", 0.5)),
                        shard_key=shard_key,
                    )
                    shard.add_node(node)
                    created.append(node)
                    created_embs.append(new_emb)
                    fact_target.append(node_id)
                    new_nodes.append((node_id, shard_key))
                    if new_emb.size != self.embed_dim:
                        # wrong-dim/missing vector: the rare irregular row goes
                        # through the dict path (vector omitted = NULL)
                        new_nodes_data.append({
                            "id": node_id,
                            "content": content,
                            "type": node.type,
                            "salience": node.salience,
                            "shard_key": node.shard_key,
                            "timestamp": node.timestamp,
                            "decay_pass": self._decay_pass,
                        })

                # ONE arena scatter for every new node, ONE touch for all merges
                # — and with ingest_fused, the link scan and edge insert ride in
                # the SAME donated device program.
                arena_new = [(n, e) for n, e in zip(created, created_embs)
                             if e.size == self.embed_dim]
                # stacked once, shared by the arena scatter AND the store write
                emb_matrix = (np.stack([e for _, e in arena_new])
                              if arena_new else None)
                chain_edges = self._chain_edges(new_nodes)
                use_fused = bool(self.config.ingest_fused and arena_new)
                fused_created = None
                if use_fused:
                    arena_ids = {n.id for n, _ in arena_new}
                    chain_pairs = [(self._q(e.source), self._q(e.target))
                                   for e in chain_edges
                                   if e.source in arena_ids and e.target in arena_ids]
                    _rows, _cands, fused_created = self.index.ingest_batch(
                        ids=[self._q(n.id) for n, _ in arena_new],
                        embeddings=emb_matrix,
                        saliences=[n.salience for n, _ in arena_new],
                        timestamps=[n.timestamp for n, _ in arena_new],
                        types=[n.type for n, _ in arena_new],
                        shard_keys=[n.shard_key or "default" for n, _ in arena_new],
                        tenant=self.user_id,
                        is_super=[n.is_super_node for n, _ in arena_new],
                        merge_ids=[self._q(i) for i in merge_ids],
                        merge_saliences=merge_sals,
                        chain_pairs=chain_pairs,
                        chain_weight=self.config.chain_link_weight,
                        link_k=self.config.cross_link_top_k,
                        link_gate=self.config.link_gate,
                        link_scale=self.config.link_weight_scale,
                        shard_modes=(1, 0),
                        link_accept_hint=self.config.link_accept_hint)
                else:
                    if arena_new:
                        self.index.add(
                            [self._q(n.id) for n, _ in arena_new],
                            emb_matrix,
                            [n.salience for n, _ in arena_new],
                            [n.timestamp for n, _ in arena_new],
                            [n.type for n, _ in arena_new],
                            [n.shard_key or "default" for n, _ in arena_new],
                            self.user_id,
                            [n.is_super_node for n, _ in arena_new])
                    if merge_ids:
                        self.index.merge_touch([self._q(i) for i in merge_ids],
                                               merge_sals)

            with self.telemetry.span("write.apply"):
                # Persist fresh nodes. arena_new is exactly the full-dim
                # subset; the rare irregular rows ride along as dicts.
                if arena_new or new_nodes_data:
                    self._store_fresh_nodes(arena_new, emb_matrix,
                                            new_nodes_data)

                if use_fused:
                    # The device already inserted every chain + gate-passing
                    # link edge inside the fused dispatch; only the host
                    # bookkeeping (shard placement, Edge objects, dirty marks)
                    # runs here — no second device round trip.
                    def _unq(qid: str) -> str:
                        return qid.partition(":")[2]

                    sim_edges = [Edge(source=_unq(s), target=_unq(t), weight=w)
                                 for sm in (1, 0)
                                 for s, t, w in fused_created.get(sm, [])]
                    self._register_edges_host(chain_edges + sim_edges)
                    n_cross = len(fused_created.get(0, []))
                    if n_cross:
                        self._log(f"✓ Created {n_cross} cross-conversation links")
                else:
                    # Both link scans (same-shard + any-shard) in one round trip.
                    link_cands = self.index.link_candidates_multi(
                        [self._q(n) for n, _ in new_nodes], self.user_id,
                        k=self.config.cross_link_top_k,
                        shard_modes=(1, 0)) if new_nodes else {1: {}, 0: {}}
                    self._link_within_shards(new_nodes, link_cands[1],
                                             chain=chain_edges)
                    self._link_to_existing_memories(new_nodes, link_cands[0])
        return new_nodes

    def _ingest_facts_dedup_fused(
            self, staged: List[Tuple[Dict, str, np.ndarray]]
    ) -> List[Tuple[str, str]]:
        """Memory-safe entry of the device-dedup mega-batch ingest
        (ISSUE 11): with a planner budget configured, the fact mega-batch
        is admitted BEFORE building the dispatch — split into planned
        sub-batches when its geometry would blow the HBM budget
        (``plan.split_dispatches{path="ingest"}`` counts them; the
        in-dispatch dedup probe keeps every sub-batch idempotent and
        dedup-exact against already-landed facts — the one semantic
        seam is that a chain edge cannot span a sub-batch boundary), or
        rejected typed (``PlanInfeasible``) when no split fits. Planner
        disabled = straight passthrough."""
        n = len(staged)
        planner = self.index.planner
        if planner is not None and planner.active and n > 1:
            d = self.index.plan_ingest(
                n, link_k=self.config.cross_link_top_k)
            if d.splits > 1:
                per = -(-n // d.splits)
                groups = [staged[i:i + per] for i in range(0, n, per)]
                self.telemetry.bump("plan.planned_turns",
                                    labels={"path": "ingest"})
                self.telemetry.bump("plan.split_dispatches", len(groups),
                                    labels={"path": "ingest"})
                out: List[Tuple[str, str]] = []
                for g in groups:
                    out.extend(self._ingest_facts_dedup_fused_one(g))
                return out
        return self._ingest_facts_dedup_fused_one(staged)

    def _ingest_facts_dedup_fused_one(
            self, staged: List[Tuple[Dict, str, np.ndarray]]
    ) -> List[Tuple[str, str]]:
        """Device-dedup mega-batch ingest (caller holds ``self._mutex``):
        the dedup probe, node scatter, merge touch, chain edges, link scan,
        and gated edge insert all run in ONE donated device dispatch
        (``state.ingest_dedup_fused``) with ONE packed readback; the host
        only finishes id bookkeeping afterwards. Node ids are assigned from
        the readback's dup verdicts, so the counter advances exactly like
        the classic path (which only names surviving facts)."""
        # prepare: per-fact host work up to and with the ONE fused dispatch
        # (index.ingest_batch_dedup: its lz.ingest.dedup_fused span lies
        # inside); apply: the host maps, nodes, shards and the store after
        # its readback
        with self.telemetry.span("write.prepare"):
            cfg = self.config
            now = time.time()
            shard_keys: List[str] = []
            for mem, content, _ in staged:
                sk = mem.get("topic") or self._infer_shard_key(content)
                if sk == "other":
                    sk = self._infer_shard_key(content)
                shard_keys.append(sk)
            emb_matrix = np.stack([e for _, _, e in staged]).astype(np.float32)
            saliences = [float(m.get("salience", 0.5)) for m, _, _ in staged]
            types = [m.get("type", "semantic") for m, _, _ in staged]
            pending = self.index.ingest_batch_dedup(
                emb_matrix, saliences, [now] * len(staged), types, shard_keys,
                tenant=self.user_id, dedup_gate=cfg.dedup_similarity,
                chain_weight=cfg.chain_link_weight,
                link_k=cfg.cross_link_top_k, link_gate=cfg.link_gate,
                link_scale=cfg.link_weight_scale, shard_modes=(1, 0), now=now,
                link_accept_hint=cfg.link_accept_hint)
        if pending is None:
            return []
        with self.telemetry.span("write.apply"):
            dup = pending["dup"]
            ids = [None if dup[i] else self._q(self._generate_node_id())
                   for i in range(len(staged))]
            _cands, created, merges, chains = \
                self.index.commit_ingest_dedup(pending, ids)

            def _unq(qid: str) -> str:
                return qid.partition(":")[2]

            new_nodes: List[Tuple[str, str]] = []
            survivors: List[Tuple[Node, np.ndarray]] = []
            for i, (mem, content, e) in enumerate(staged):
                if dup[i]:
                    continue
                node = Node(
                    id=_unq(ids[i]),
                    content=content,
                    embedding=None,          # the arena owns the vector
                    type=types[i],
                    salience=saliences[i],
                    timestamp=now,
                    shard_key=shard_keys[i],
                )
                self._get_or_create_shard(shard_keys[i]).add_node(node)
                survivors.append((node, e))
                new_nodes.append((node.id, shard_keys[i]))
            # Device-merged duplicates: mirror the arena's merge touch on the
            # host copy (max salience, access+1, fresh last_accessed).
            for i, target_qid in merges:
                tgt = (self.buffer.get_node(_unq(target_qid))
                       if target_qid else None)
                if tgt is None:
                    continue
                tgt.salience = max(tgt.salience, saliences[i])
                tgt.last_accessed = now
                tgt.access_count += 1
                self._mark_dirty(tgt.id)
                self._log(f"   (Merged semantic duplicate into {tgt.id})")
            if survivors:
                self._store_fresh_nodes(survivors)
            # Edges the device already inserted — host bookkeeping only.
            chain_edges = [Edge(source=_unq(s), target=_unq(t),
                                weight=cfg.chain_link_weight)
                           for s, t in chains]
            sim_edges = [Edge(source=_unq(s), target=_unq(t), weight=w)
                         for sm in (1, 0) for s, t, w in created.get(sm, [])]
            self._register_edges_host(chain_edges + sim_edges)
            n_cross = len(created.get(0, []))
            if n_cross:
                self._log(f"✓ Created {n_cross} cross-conversation links")
        return new_nodes

    def _store_fresh_nodes(self, fresh: List[Tuple[Node, np.ndarray]],
                           emb_matrix: Optional[np.ndarray] = None,
                           irregular: Sequence[Dict] = ()) -> None:
        """One mega-batch's new nodes to the store. ``fresh`` are the
        full-width ones (exactly those the arena holds, so arena and store
        never disagree about which nodes carry vectors): the columnar bulk
        path when the store has it (one flat embedding buffer, no per-row
        dicts — ingest hot path), dict rows for protocol-parity stores.
        ``irregular`` are ready dict rows (wrong-width or missing vector)."""
        with self.telemetry.span("store.add"):
            rows = list(irregular)
            if fresh and hasattr(self.store, "add_nodes_columns"):
                self.store.add_nodes_columns(
                    ids=[n.id for n, _ in fresh],
                    contents=[n.content for n, _ in fresh],
                    embeddings=(emb_matrix if emb_matrix is not None
                                else np.stack([e for _, e in fresh])),
                    types=[n.type for n, _ in fresh],
                    saliences=[n.salience for n, _ in fresh],
                    timestamps=[n.timestamp for n, _ in fresh],
                    shard_keys=[n.shard_key or "" for n, _ in fresh],
                    decay_pass=self._decay_pass,
                    user_id=self.user_id)
            else:
                rows.extend({
                    "id": n.id, "content": n.content,
                    "embedding": e.tolist(), "type": n.type,
                    "salience": n.salience, "shard_key": n.shard_key,
                    "timestamp": n.timestamp,
                    "decay_pass": self._decay_pass,
                } for n, e in fresh)
            if rows:
                self.store.add_nodes(rows, user_id=self.user_id)

    def _finish_consolidation(self, new_nodes: List[Tuple[str, str]],
                              start_time: float) -> None:
        self._enforce_buffer_limit()

        if self.enable_hierarchy:
            with self._mutex:
                for shard_key in {sk for _, sk in new_nodes}:
                    shard = self.shards.get(shard_key)
                    if shard and len(shard.nodes) > self.super_node_threshold:
                        self._create_super_nodes_for_shard(shard_key)

        if self.query_cache:
            self.query_cache.invalidate_results(self.user_id)

        # IVF coarse-index upkeep belongs to background maintenance (this
        # runs on the single consolidation worker), never a serving query —
        # a 1M-row k-means is multi-second.
        if self.index.ivf_nprobe:
            with self._mutex:
                if self.index.ivf_maintenance():
                    self._log("🧭 IVF coarse index rebuilt")

        elapsed = time.time() - start_time
        self.telemetry.record("consolidation.run_ms", elapsed * 1e3)
        self._log(f"✓ Background consolidation complete ({elapsed:.2f}s)")
        self._save_to_persistence()
        with self._mutex:
            # The consolidated batches are durable; the WAL shrinks to
            # whatever is still pending (e.g. a conversation started while
            # the LLM call ran). A drain ingests every deferred fact too,
            # so the flush-policy backlog retires with it.
            self._inflight_batches.clear()
            self._deferred_batches.clear()
            self._journal_sync()

    def _requeue_inflight(self) -> None:
        """A consolidation attempt failed (LLM parse error): put its batches
        back on the queue so the next consolidation retries them, keeping
        them journaled meanwhile. The reference silently drops the turns
        (memory_system.py:697-699)."""
        with self._mutex:
            self.consolidation_queue = self._inflight_batches + self.consolidation_queue
            self._inflight_batches = []

    def _add_edge(self, edge: Edge) -> None:
        """Insert into both the host shard record and the edge arena."""
        self._add_edges_batch([edge])

    def _register_edges_host(self, edges: List[Edge]) -> None:
        """Host half of edge insertion: shard placement (O(1) via the
        placement caches), Edge-object bookkeeping, dirty marks, metrics.
        The DEVICE half happens elsewhere — ``_add_edges_batch`` follows
        this with ``index.add_edges``; the fused ingest path has already
        scattered the rows inside its one dispatch."""
        for edge in edges:
            key = (edge.source, edge.target)
            # Existing edge: reinforce it where it lives. New edge: dispatch
            # to the source node's shard (O(1) via the placement caches).
            sk = self._edge_shard.get(key)
            shard = self.shards.get(sk) if sk is not None else None
            if shard is None or key not in shard.edges:
                shard = self._shard_of_node(edge.source)
                if shard is None:
                    shard = self._get_or_create_shard("default")
            shard.add_edge(edge, reinforce=self.config.edge_reinforce)
            self._edge_shard[key] = shard.shard_key
            self._mark_edge_dirty(key)
        self.metrics["edges_linked"] += len(edges)

    def _add_edges_batch(self, edges: List[Edge]) -> None:
        """Host bookkeeping per edge + ONE device scatter for the whole batch
        (a consolidation creates O(new_facts) links; per-edge dispatches are
        what made the reference's ingest loop host-bound)."""
        if not edges:
            return
        self._register_edges_host(edges)
        self.index.add_edges(
            [(self._q(e.source), self._q(e.target), e.weight) for e in edges],
            self.user_id, reinforce=self.config.edge_reinforce)

    def _chain_edges(self, new_nodes: List[Tuple[str, str]]) -> List[Edge]:
        """Consecutive same-shard new nodes chain with w=0.5 (shared by the
        fused and classic link passes)."""
        by_shard: Dict[str, List[str]] = {}
        for node_id, shard_key in new_nodes:
            by_shard.setdefault(shard_key, []).append(node_id)
        batch: List[Edge] = []
        for _shard_key, node_ids in by_shard.items():
            if len(node_ids) >= 2:
                for a, b in zip(node_ids, node_ids[1:]):
                    batch.append(Edge(source=a, target=b,
                                      weight=self.config.chain_link_weight))
        return batch

    def _link_within_shards(self, new_nodes: List[Tuple[str, str]],
                            cands: Optional[Dict] = None,
                            chain: Optional[List[Edge]] = None) -> None:
        """Chain consecutive new nodes (w=0.5) + top-3 same-shard cosine>0.5
        links (w=sim·0.8). The similarity scan is one batched matmul on the
        arena (replaces hot loop #2, memory_system.py:797-836); the
        consolidation path precomputes ``cands`` via
        ``link_candidates_multi`` so both link passes share one readback."""
        batch: List[Edge] = list(chain) if chain is not None \
            else self._chain_edges(new_nodes)

        all_new = [nid for nid, _ in new_nodes]
        if not all_new:
            self._add_edges_batch(batch)
            return
        if cands is None:
            cands = self.index.link_candidates(
                [self._q(n) for n in all_new], self.user_id,
                k=self.config.cross_link_top_k, shard_mode=1)
        for qid, pairs in cands.items():
            nid = qid.partition(":")[2]
            for qcand, sim in pairs:
                if sim > self.config.link_gate:
                    batch.append(Edge(source=nid,
                                      target=qcand.partition(":")[2],
                                      weight=sim * self.config.link_weight_scale))
        self._add_edges_batch(batch)

    def _link_to_existing_memories(self, new_nodes: List[Tuple[str, str]],
                                   cands: Optional[Dict] = None) -> None:
        """Top-3 cross-links across ALL existing memories (any shard), gate
        0.5, weight sim·0.8, dedup both directions (replaces hot loop #3,
        memory_system.py:838-891)."""
        if not new_nodes:
            return
        if cands is None:
            cands = self.index.link_candidates(
                [self._q(n) for n, _ in new_nodes], self.user_id,
                k=self.config.cross_link_top_k, shard_mode=0)
        batch: List[Edge] = []
        staged: Set[Tuple[str, str]] = set()
        for qid, pairs in cands.items():
            nid = qid.partition(":")[2]
            for qcand, sim in pairs:
                if sim <= self.config.link_gate:
                    continue
                cand = qcand.partition(":")[2]
                exists = ((nid, cand) in staged or (cand, nid) in staged
                          or any((nid, cand) in s.edges or (cand, nid) in s.edges
                                 for s in self.shards.values()))
                if not exists:
                    batch.append(Edge(source=nid, target=cand,
                                      weight=sim * self.config.link_weight_scale))
                    staged.add((nid, cand))
        self._add_edges_batch(batch)
        links_created = len(batch)
        if links_created:
            self._log(f"✓ Created {links_created} cross-conversation links")

    def _create_super_nodes_for_shard(self, shard_key: str) -> None:
        shard = self.shards[shard_key]
        if len(shard.nodes) < self.super_node_threshold:
            return
        if any(n.shard_key == shard_key for n in self.super_nodes.values()):
            return

        self._log(f"  Creating super-node for shard '{shard_key}' ({len(shard.nodes)} nodes)")
        nodes = list(shard.nodes.values())
        super_id = f"super_{shard_key}_{int(time.time())}"
        samples = [n.content for n in nodes[:3]]
        aggregated = f"Topic: {shard_key}. Contains memories about: " + "; ".join(samples)

        # Centroid on device: mean of child embeddings (memory_system.py:916-917)
        avg = self.index.mean_embedding([self._q(n.id) for n in nodes])

        super_node = Node(
            id=super_id,
            content=aggregated,
            embedding=avg.tolist(),
            type="semantic",
            is_super_node=True,
            child_ids=[n.id for n in nodes],
            shard_key=shard_key,
        )
        for node in nodes:
            node.parent_id = super_id
        self.super_nodes[super_id] = super_node
        self._index_add_node(super_node)
        self._mark_dirty(super_id, *(n.id for n in nodes))
        self._log(f"  ✓ Created super-node {super_id} with {len(nodes)} children")

    # -------------------------------------------------------------- forgetting
    def _enforce_buffer_limit(self) -> None:
        with self._mutex:
            nodes, _ = self.buffer.size()
            if nodes <= self.max_buffer_size:
                return
            # eviction scores read arena salience — land queued boosts first
            self._flush_pending_boosts_locked()
            excess = nodes - self.max_buffer_size
            cands = self.index.evict_candidates(self.user_id, excess)
            removed_ids = []
            for qid, _imp in cands[:excess]:
                nid = qid.partition(":")[2]
                node = self.buffer.get_node(nid)
                if node is None or node.is_super_node:
                    continue
                shard = self.shards.get(node.shard_key)
                if shard and nid in shard.nodes:
                    del shard.nodes[nid]
                    self._node_shard_cache.pop(nid, None)
                    # cross-links live in the SOURCE node's shard, so scan all
                    # shards — not just the evictee's own (the reference only
                    # cleans the home shard, leaving dangling edges).
                    for s in self.shards.values():
                        for key in [k for k in s.edges
                                    if k[0] == nid or k[1] == nid]:
                            self._mark_edge_deleted(s.edges[key])
                            del s.edges[key]
                            self._edge_shard.pop(key, None)
                    removed_ids.append(nid)
                    self._dirty_nodes.discard(nid)
            if removed_ids:
                self.index.delete([self._q(n) for n in removed_ids])
                self.store.delete_nodes(removed_ids, user_id=self.user_id)
                if self.query_cache:
                    self.query_cache.invalidate_results(self.user_id)
                self._log(f"⚠ Buffer limit reached! Archived {len(removed_ids)} old nodes "
                          f"(limit: {self.max_buffer_size})")

    # ------------------------------------------------------ deep consolidation
    def run_consolidation(self, weight_threshold: float = 0.6,
                          merge_similar: bool = True,
                          persist: bool = True) -> str:
        results = []
        self._log("🔄 Running consolidation...")
        self._flush_pending_boosts()   # consolidation reads arena salience

        if merge_similar:
            merged = self._merge_similar_nodes(self.config.merge_similarity)
            if merged > 0:
                self._status(results, f"✓ Merged {merged} similar nodes")

        components = self.buffer.get_connected_components()
        # ONE pass over all edges, bucketing intra-component weights by
        # component id — the per-component edge scan was O(components ×
        # edges), which at 1M nodes with a few hundred thousand live edges
        # is billions of host operations inside the measured deep-
        # consolidation path.
        comp_of: Dict[str, int] = {}
        for ci, component in enumerate(components):
            for nid in component:
                comp_of[nid] = ci
        w_sum = [0.0] * len(components)
        w_cnt = [0] * len(components)
        for s in self.shards.values():
            for (src, tgt), e in s.edges.items():
                ci = comp_of.get(src)
                if ci is not None and comp_of.get(tgt) == ci:
                    w_sum[ci] += e.weight
                    w_cnt[ci] += 1
        profile_updates = 0
        for ci, component in enumerate(components):
            if len(component) < self.config.component_min_size or not w_cnt[ci]:
                continue
            if w_sum[ci] / w_cnt[ci] > self.config.component_min_avg_weight:
                update = self._extract_profile_from_component(component)
                if "Updated" in update:
                    profile_updates += 1
                    results.append(update)

        pruned = self._prune_weak_edges(self.prune_threshold)
        if pruned > 0:
            self._status(results, f"✓ Pruned {pruned} weak edges")

        if profile_updates > 0:
            self._status(results, f"✓ Updated {profile_updates} profile domains")
        else:
            all_contents = [n.content for n in self.buffer.nodes.values()
                            if not n.is_super_node]
            if len(all_contents) >= self.config.component_min_size:
                update = self._extract_profile_from_contents(all_contents)
                if "Updated" in update:
                    results.append(update)

        if not results:
            self._status(results, "✓ No consolidation actions needed")
        elif persist:
            # Standalone callers (CLI /consolidate, dashboard POST) get the
            # merged rows and profile updates made durable immediately; the
            # end_conversation path saves right after and passes persist=False.
            self._save_to_persistence()
        return "\n".join(results)

    def _extract_profile_from_component(self, component: Set[str]) -> str:
        contents = []
        for nid in component:
            node = self.buffer.get_node(nid)
            if node and not node.is_super_node:
                contents.append(node.content)
        if not contents:
            return "No content to extract"
        return self._extract_profile_from_contents(contents)

    _PROFILE_PROMPT = """Analyze these related memories and generate brief, factual personality insights (1-2 sentences each).
Identify all applicable domains: preferences, personality_traits, knowledge_domains, interaction_style, or key_experiences.
Return a JSON object where keys are the domain names and values are the specific insights.
Example: {"preferences": "User prefers Python for data science.", "knowledge_domains": "Exhibits deep expertise in memory systems."}"""

    def _extract_profile_from_contents(self, contents: List[str]) -> str:
        if not contents:
            return "No content to extract"
        prompt = "Related memories:\n" + "\n".join(f"- {c}" for c in contents[:10])
        response = self._call_llm(
            [{"role": "system", "content": self._PROFILE_PROMPT},
             {"role": "user", "content": prompt}],
            response_format={"type": "json_object"})
        try:
            data = json.loads(_extract_json_object(response))
            if not isinstance(data, dict):
                # a top-level array/scalar parses but has no domains
                return "Failed to extract profile"
            updated_any = False
            for domain, insight in data.items():
                if domain in self.profile.data and insight:
                    current = self.profile.data.get(domain, "")
                    if current and insight not in current:
                        updated = f"{current}. {insight}".strip()
                    else:
                        updated = insight
                    self.profile.update_domain(domain, updated)
                    self._log(f"  ✓ Profile updated: {domain} = {insight[:50]}...")
                    updated_any = True
            if updated_any:
                return "✓ Updated profile domains"
        except json.JSONDecodeError as e:
            self._log(f"  ⚠ JSON parse error: {e}")
        return "Failed to extract profile"

    def _merge_similar_nodes(self, similarity_threshold: float = 0.95) -> int:
        """All-pairs near-duplicate merge — the *intended* semantics of the
        reference (its :1073-1077 indentation bug only merges duplicates of
        the last node; SURVEY §2.2 says build the intended version). Pair
        discovery is one arena matmul; merging is host bookkeeping."""
        with self._mutex:
            if len(self.buffer.nodes) < 2:
                return 0
            pairs = self.index.merge_candidates(self.user_id, similarity_threshold)
            merged_count = 0
            absorbed: Set[str] = set()
            for qkeep, qmerge, _sim in pairs:
                user, _, keep_id = qkeep.partition(":")
                if user != self.user_id:
                    continue
                merge_id = qmerge.partition(":")[2]
                if keep_id in absorbed or merge_id in absorbed:
                    continue
                node1 = self.buffer.get_node(keep_id)
                node2 = self.buffer.get_node(merge_id)
                if node1 is None or node2 is None or node1.is_super_node or node2.is_super_node:
                    continue

                node1.content = f"{node1.content} | {node2.content}"
                node1.salience = max(node1.salience, node2.salience)
                node1.access_count += node2.access_count

                # Rewire edges in EVERY shard (cross-links live in the source
                # node's shard, not necessarily the merged node's).
                for shard in self.shards.values():
                    rewires = []
                    for (src, tgt) in list(shard.edges.keys()):
                        if src == merge_id:
                            rewires.append(((src, tgt), (keep_id, tgt)))
                        elif tgt == merge_id:
                            rewires.append(((src, tgt), (src, keep_id)))
                    for old_key, new_key in rewires:
                        edge = shard.edges.pop(old_key)
                        self._edge_shard.pop(old_key, None)
                        self._mark_edge_deleted(edge)
                        edge.source, edge.target = new_key
                        if new_key[0] != new_key[1]:
                            shard.edges[new_key] = edge
                            self._edge_shard[new_key] = shard.shard_key
                            self.index.add_edges(
                                [(self._q(new_key[0]), self._q(new_key[1]), edge.weight)],
                                self.user_id)
                            self._mark_edge_dirty(new_key)
                    if merge_id in shard.nodes:
                        del shard.nodes[merge_id]
                        self._node_shard_cache.pop(merge_id, None)

                self.index.merge_touch([qkeep], [node1.salience])
                self.index.delete([qmerge])
                absorbed.add(merge_id)
                self._dirty_nodes.discard(merge_id)
                merged_count += 1
                # keep_id goes dirty: the merged content plus the arena's
                # merge_touch result (max salience, access+1) reach the
                # store at the save that follows this consolidation.
                self._mark_dirty(keep_id)
            if absorbed:
                self.store.delete_nodes(sorted(absorbed), user_id=self.user_id)
            if merged_count and self.query_cache:
                self.query_cache.invalidate_results(self.user_id)
            return merged_count

    # ------------------------------------------------------------ multi-tenant
    def _drain_background(self) -> None:
        """Barrier on the single-worker executor: any queued consolidation for
        the current user completes before we proceed (prevents the queued
        batch from being ingested under a different user_id)."""
        if self.background_executor:
            self.background_executor.submit(lambda: None).result()

    def switch_user(self, new_user_id: str) -> None:
        with self.telemetry.span("api.switch_user"):
            if self.conversation_active:
                self.end_conversation()       # saves after consolidation
                self._drain_background()
            else:
                self._drain_background()
                self._save_to_persistence()
            self.user_id = new_user_id
            self._load_from_persistence()
            self._setup_journal()        # per-user journal; replays crashed turns
            self._setup_ingest_journal()   # per-user fact journal + replay
        self._log(f"👤 Switched context to user: {new_user_id}")

    def get_all_users(self) -> List[str]:
        if hasattr(self.store, "get_all_users"):
            users = self.store.get_all_users()
            return users if users else [self.user_id]
        return [self.user_id]

    # ----------------------------------------------------------------- search
    def search_memories(self, query: str, limit: int = 5) -> List[Node]:
        query_emb = self._get_embedding(query)
        if self._use_fused_serving():
            # Route through the scheduler: a lone call pays at most the
            # flush wait; concurrent callers coalesce into one dispatch.
            res = self._ensure_scheduler().submit(RetrievalRequest(
                query=np.asarray(query_emb, np.float32),
                tenant=self.user_id, k=limit)).result()
            ids = res.ids
        else:
            ids, _ = self.index.search(np.asarray(query_emb, np.float32),
                                       self.user_id, k=limit, super_filter=-1)
        results = []
        for qid in ids:
            node = self.buffer.get_node(qid.partition(":")[2])
            if node:
                results.append(node)
        return results

    def search_memories_batch(self, queries: List[str], limit: int = 5
                              ) -> List[List[Node]]:
        """Fleet-serving variant of ``search_memories``: ONE batched encoder
        forward + ONE batched top-k kernel for all queries (per-query
        dispatch amortized — the reason the index lives in HBM). With fused
        serving the fleet rides the QueryScheduler, so it shares device
        batches with any concurrent chat retrievals (submit_many keeps the
        group contiguous and demuxes results in order)."""
        if not queries:
            return []
        embs = np.asarray(self._batch_embed(list(queries)), np.float32)
        if self._use_fused_serving():
            reqs = [RetrievalRequest(query=embs[i], tenant=self.user_id,
                                     k=limit) for i in range(len(queries))]
            futures = self._ensure_scheduler().submit_many(reqs)
            per_query = [(f.result().ids, f.result().scores) for f in futures]
        else:
            per_query = self.index.search_batch(embs, self.user_id, k=limit,
                                                super_filter=-1)
        results: List[List[Node]] = []
        for ids, _scores in per_query:
            nodes = []
            for qid in ids:
                node = self.buffer.get_node(qid.partition(":")[2])
                if node:
                    nodes.append(node)
            results.append(nodes)
        return results

    def get_connected_memories(self, node_id: str) -> List[Node]:
        connected: Set[str] = set()
        for shard in self.shards.values():
            for (src, tgt) in shard.edges:
                if src == node_id:
                    connected.add(tgt)
                elif tgt == node_id:
                    connected.add(src)
        return [n for n in (self.buffer.get_node(c) for c in connected) if n]

    # ------------------------------------------------------------ persistence
    def _bulk_fill_embeddings(self, dicts: List[Dict[str, Any]],
                              node_ids: List[str]) -> None:
        """Fill missing/empty 'embedding' entries from the arena in ONE
        device gather (snapshot-loaded nodes don't materialize host copies)."""
        missing = [(i, self._q(nid))
                   for i, (d, nid) in enumerate(zip(dicts, node_ids))
                   if not d.get("embedding")]
        if not missing:
            return
        valid = []
        for i, q in missing:
            r = self.index.id_to_row.get(q)
            if r is not None:
                valid.append((i, r))
        if not valid:
            return
        rows_arr = np.asarray([r for _, r in valid])
        gathered = np.asarray(self.index.state.emb[rows_arr], np.float32)
        # Tiered memory (ISSUE 8): a demoted row's master embedding is
        # ZEROED — persisting that would corrupt the durable row store.
        # Its exact bytes live in the host cold store.
        tm = self.index.tiering
        if tm is not None and tm.cold_count:
            cold_mask = tm.is_cold_rows(rows_arr)
            if cold_mask.any():
                gathered[cold_mask] = np.asarray(
                    tm.gather_cold(rows_arr[cold_mask].tolist()),
                    np.float32)
        for (i, _), e in zip(valid, gathered):
            dicts[i]["embedding"] = [float(x) for x in e]

    def _save_to_persistence(self) -> None:
        """Persist the user's durable rows.

        Incremental path (segmented stores): upsert only rows dirtied since
        the last save, flush edge tombstones, and record the decay-pass
        counter — a conversation's save cost is proportional to what the
        conversation touched, not graph size. Fallback path (injected/
        protocol-parity stores, or before the first sync): the reference's
        full delete-all + re-insert (memory_system.py:1275-1302)."""
        with self.telemetry.span("store.save"), self._mutex:
            # queued boosts must land before _sync_from_arena pulls rows,
            # or boosted host copies get overwritten with stale values
            self._flush_pending_boosts_locked()
            if not self._supports_incremental:
                self._save_full()
                self._last_version = self.store.get_latest_version()
                return
            # a segmented store commits the save's writes as one: VERSION
            # rises once, after the last of them — or not at all when
            # nothing was dirty, and then _last_version stands
            with self.store.commit() as commit:
                if self._store_synced:
                    self._save_incremental()
                else:
                    self._save_full()
            if commit.version is not None:
                self._last_version = commit.version

    def _save_incremental(self) -> None:
        self._sync_from_arena(node_ids=set(self._dirty_nodes),
                              edge_keys=set(self._dirty_edges))
        nodes = []
        for nid in sorted(self._dirty_nodes):
            node = self.buffer.get_node(nid)
            if node is not None:
                nodes.append(node)
        # Dirty rows carry embedding=None unless the host holds a real copy:
        # the store preserves each row's stored vector, so no arena gather
        # (and no f32→arena-dtype degradation) happens here.
        rows = [self._node_row(n) for n in nodes]
        if rows:
            self.store.add_nodes(rows, user_id=self.user_id)
        # Tombstones flush BEFORE upserts: segments merge last-wins, so an
        # edge deleted and re-created within one save interval must end with
        # its upsert as the final word.
        if self._deleted_edge_ids:
            self.store.delete_edges(sorted(self._deleted_edge_ids),
                                    user_id=self.user_id)
        edge_rows = []
        for key in sorted(self._dirty_edges):
            edge = self._find_edge(key)
            if edge is not None:
                edge_rows.append(self._edge_row(edge))
        if edge_rows:
            self.store.add_edges(edge_rows, user_id=self.user_id)
        self.store.save_profile(self.profile.to_dict(), user_id=self.user_id)
        self.store.save_sys_meta({"decay_pass": self._decay_pass,
                                  "node_counter": self.node_counter},
                                 user_id=self.user_id)
        self._dirty_nodes.clear()
        self._dirty_edges.clear()
        self._deleted_edge_ids.clear()
        self._log(f"💾 Saved {len(rows)} nodes, {len(edge_rows)} edges (delta)")

    def _save_full(self) -> None:
        """Delete-all + re-insert (parity with memory_system.py:1275-1302).
        Nodes whose host embedding is unmaterialized get theirs from the
        arena in one bulk gather. ``buffer.nodes`` merges super-nodes in."""
        self._sync_from_arena()
        all_nodes = list(self.buffer.nodes.values())
        nodes_data = [self._node_row(n) for n in all_nodes]
        # The delete-all below destroys the stored rows, so vectors must be
        # materialized first: prefer the store's pristine float32 copy, fall
        # back to an arena gather for rows the store never held.
        self._preserve_stored_embeddings(nodes_data)
        self._bulk_fill_embeddings(nodes_data, [n.id for n in all_nodes])
        edges_data = [self._edge_row(edge)
                      for shard in self.shards.values()
                      for edge in shard.edges.values()]
        self.store.delete_nodes([], user_id=self.user_id)
        if nodes_data:
            self.store.add_nodes(nodes_data, user_id=self.user_id)
        self.store.delete_edges([], user_id=self.user_id)
        if edges_data:
            self.store.add_edges(edges_data, user_id=self.user_id)
        self.store.save_profile(self.profile.to_dict(), user_id=self.user_id)
        if self._supports_incremental:
            self.store.save_sys_meta({"decay_pass": self._decay_pass,
                                      "node_counter": self.node_counter},
                                     user_id=self.user_id)
            self._store_synced = True
        self._dirty_nodes.clear()
        self._dirty_edges.clear()
        self._deleted_edge_ids.clear()
        self._log(f"💾 Saved {len(nodes_data)} nodes, {len(edges_data)} edges")

    def _preserve_stored_embeddings(self, rows: List[Dict[str, Any]]) -> None:
        """Backfill empty 'embedding' entries from the store's current rows
        (vectors that live neither on the host nor in the arena)."""
        missing = {r["id"] for r in rows if not r.get("embedding")}
        if not missing or not hasattr(self.store, "get_nodes_columns"):
            return
        try:
            cols = self.store.get_nodes_columns(self.user_id)
        except Exception:
            return
        if cols is None:
            return
        ragged = cols.get("ragged_embeddings", {})
        byid: Dict[str, List[float]] = {}
        for i, rid in enumerate(cols["id"]):
            if rid not in missing:
                continue
            if cols["has_embedding"][i]:
                byid[rid] = cols["embedding"][i].tolist()
            elif i in ragged:
                byid[rid] = ragged[i].tolist()
        for r in rows:
            if not r.get("embedding") and r["id"] in byid:
                r["embedding"] = byid[r["id"]]

    def _edge_row(self, edge: Edge) -> Dict[str, Any]:
        return {
            "source_id": edge.source,
            "target_id": edge.target,
            "weight": edge.weight,
            "edge_type": edge.edge_type,
            "co_occurrence": edge.co_occurrence,
            "last_updated": edge.last_updated,
            "decay_pass": self._decay_pass,
        }

    def _node_row(self, node: Node) -> Dict[str, Any]:
        # embedding None = "no new vector": the segmented store keeps the
        # pristine stored one (never the arena's normalized/quantized copy).
        emb = node.embedding
        return {
            "id": node.id,
            "content": node.content,
            "embedding": None if emb is None else [float(x) for x in emb],
            "type": node.type,
            "timestamp": node.timestamp,
            "access_count": node.access_count,
            "last_accessed": node.last_accessed,
            "salience": node.salience,
            "is_super_node": node.is_super_node,
            "child_ids": list(node.child_ids),
            "parent_id": node.parent_id,
            "shard_key": node.shard_key,
            # Stamp: which decay sweep these numerics are current as of —
            # loads replay (current_pass - stamp) sweeps in closed form.
            "decay_pass": self._decay_pass,
        }

    def _load_from_persistence(self) -> None:
        with self.telemetry.span("store.load"), self._mutex:
            # Drop stale arena rows for this tenant, then rebuild host + arena.
            stale = list(self.index.tenant_nodes.get(self.user_id, set()))
            if stale:
                self.index.delete(stale)
            self.shards.clear()
            self.super_nodes.clear()
            self._edge_shard.clear()
            self._node_shard_cache.clear()
            self._dirty_nodes.clear()
            self._dirty_edges.clear()
            self._deleted_edge_ids.clear()
            meta = (self.store.load_sys_meta(self.user_id)
                    if self._supports_incremental else {})
            self._decay_pass = int(meta.get("decay_pass", 0))

            if self._supports_incremental:
                self._load_columnar()
            else:
                self._load_rows()

            prof = self.store.load_profile(user_id=self.user_id)
            self.profile = Profile.from_dict(prof) if prof else Profile()

            self.node_counter = max(self.node_counter,
                                    int(meta.get("node_counter", 0)))
            self._last_version = self.store.get_latest_version()
            self._store_synced = True
            if self.query_cache:
                self.query_cache.invalidate_results()

    def _restore_counter(self, node_id: str) -> None:
        if node_id.startswith("node_"):
            try:
                self.node_counter = max(self.node_counter, int(node_id[5:]))
            except ValueError:
                pass

    @staticmethod
    def _replay_node_decay(stored: np.ndarray, missed: np.ndarray,
                           rate: float, floor: float) -> np.ndarray:
        """Replay the decay sweeps a stored row missed since its stamp,
        bit-for-bit against the arena kernel: each pass is the f32 sub the
        device does, then the multiply-add in f64 — exact, so the single
        rounding back to f32 reproduces the kernel's fused multiply-add.
        A closed-form ``(1-rate)**missed`` in f64 lands within an ulp but
        NOT on the same bits, and restart parity is a CI gate."""
        sal = np.asarray(stored, np.float32).copy()
        left = np.asarray(missed, np.int64).copy()
        fl32 = np.float32(floor)
        fl64, dec64 = np.float64(fl32), np.float64(np.float32(1.0)
                                                   - np.float32(rate))
        while True:
            m = left > 0
            if not m.any():
                break
            base = (sal[m] - fl32).astype(np.float64)
            sal[m] = (fl64 + base * dec64).astype(np.float32)
            left[m] -= 1
        return sal

    @staticmethod
    def _replay_edge_decay(stored: np.ndarray, missed: np.ndarray,
                           rate: float) -> np.ndarray:
        """Edge-weight twin of :meth:`_replay_node_decay`: ``w *= (1-rate)``
        per missed pass, one f32 rounding per step like the kernel."""
        w = np.asarray(stored, np.float32).copy()
        left = np.asarray(missed, np.int64).copy()
        dec32 = np.float32(1.0) - np.float32(rate)
        while True:
            m = left > 0
            if not m.any():
                break
            w[m] = w[m] * dec32
            left[m] -= 1
        return w

    def _load_columnar(self) -> None:
        """Bulk columnar restore: embeddings go host→arena as ONE matrix,
        host nodes materialize WITHOUT per-node vectors, and clean rows'
        salience / edge weights are reconstructed by replaying the uniform
        decay sweeps they missed since their stamp (closed form — the store
        never rewrites rows just because a sweep ran)."""
        cols = self.store.get_nodes_columns(self.user_id)
        if cols is None:
            return
        rate = self.config.decay_rate
        floor = self.config.salience_floor
        missed = np.maximum(self._decay_pass - cols["decay_pass"], 0)
        sal = self._replay_node_decay(cols["salience"], missed, rate, floor)
        ids = cols["id"]
        contents = cols["content"]
        types = cols["type"]
        shard_keys = cols["shard_key"]
        parents = cols["parent_id"]
        child_json = cols["child_ids"]
        ts = cols["timestamp"]
        la = cols["last_accessed"]
        ac = cols["access_count"]
        is_super = cols["is_super_node"]
        ragged = cols.get("ragged_embeddings", {})
        for i in range(len(ids)):
            node = Node(
                id=ids[i],
                content=contents[i] or "",
                # Arena-authoritative (None) for modal-dimension rows; rows
                # stored at another dimension keep their host copy so a
                # later upsert can't destroy the vector.
                embedding=(ragged[i].tolist() if i in ragged else None),
                type=types[i] or "semantic",
                timestamp=float(ts[i]),
                access_count=int(ac[i]),
                last_accessed=float(la[i]),
                salience=float(sal[i]),
                is_super_node=bool(is_super[i]),
                child_ids=(json.loads(child_json[i])
                           if child_json[i] and child_json[i] != "[]" else []),
                parent_id=parents[i] or None,
                shard_key=shard_keys[i] or "default",
            )
            if node.is_super_node:
                self.super_nodes[node.id] = node
            else:
                self._get_or_create_shard(node.shard_key).add_node(node)
            self._restore_counter(node.id)

        matrix = cols["embedding"]
        ok = cols["has_embedding"]
        if matrix.shape[1] != self.embed_dim:
            # Store's modal dimension differs from the current embedder:
            # only rows that happen to match the embedder dimension are
            # servable from the arena (the rest stay host-resident).
            idx = np.asarray(sorted(i for i, v in ragged.items()
                                    if v.size == self.embed_dim), np.int64)
            emb_rows = (np.stack([ragged[int(i)] for i in idx])
                        if idx.size else np.zeros((0, self.embed_dim), np.float32))
        else:
            idx = np.nonzero(ok)[0]
            emb_rows = matrix[idx]
        if idx.size:
            qids = [self._q(ids[i]) for i in idx]
            self.index.add(
                qids,
                emb_rows,
                sal[idx],
                ts[idx],
                [types[i] or "semantic" for i in idx],
                [shard_keys[i] or "default" for i in idx],
                self.user_id,
                is_super[idx])
            self.index.restore_access(qids, ac[idx], la[idx])

        ecols = self.store.get_edges_columns(self.user_id)
        if ecols is None:
            return
        missed_e = np.maximum(self._decay_pass - ecols["decay_pass"], 0)
        weights = self._replay_edge_decay(ecols["weight"], missed_e, rate)
        node_shard = {}
        for i in range(len(ids)):
            if not is_super[i]:
                node_shard[ids[i]] = shard_keys[i] or "default"
        srcs = ecols["source_id"]
        tgts = ecols["target_id"]
        ets = ecols["edge_type"]
        cos = ecols["co_occurrence"]
        lus = ecols["last_updated"]
        triples = []
        for i in range(len(srcs)):
            edge = Edge(source=srcs[i], target=tgts[i], weight=float(weights[i]),
                        edge_type=ets[i] or "relates_to",
                        co_occurrence=int(cos[i]), last_updated=float(lus[i]))
            owner = self.shards.get(node_shard.get(edge.source, "default"))
            if owner is None:
                owner = self._get_or_create_shard("default")
            owner.edges[edge.key] = edge
            self._edge_shard[edge.key] = owner.shard_key
            triples.append((self._q(edge.source), self._q(edge.target), edge.weight))
        if triples:
            self.index.add_edges(triples, self.user_id)

    def _load_rows(self) -> None:
        """Row-dict restore for protocol-parity stores without the columnar
        API (mirrors reference _load_from_persistence :1304-1410)."""
        rows = self.store.get_nodes(user_id=self.user_id)
        batch: List[Node] = []
        for r in rows:
            node = Node(
                id=r["id"],
                content=r.get("content", ""),
                embedding=r.get("embedding") or None,
                type=r.get("type", "semantic"),
                timestamp=r.get("timestamp", time.time()),
                access_count=int(r.get("access_count", 0)),
                last_accessed=r.get("last_accessed", time.time()),
                salience=float(r.get("salience", 0.5)),
                is_super_node=bool(r.get("is_super_node", False)),
                child_ids=list(r.get("child_ids") or []),
                parent_id=r.get("parent_id"),
                shard_key=r.get("shard_key") or "default",
            )
            if node.is_super_node:
                self.super_nodes[node.id] = node
            else:
                self._get_or_create_shard(node.shard_key).add_node(node)
            if node.embedding is not None and len(node.embedding) == self.embed_dim:
                batch.append(node)
            self._restore_counter(node.id)

        if batch:
            qids = [self._q(n.id) for n in batch]
            self.index.add(
                qids,
                np.asarray([n.embedding for n in batch], np.float32),
                [n.salience for n in batch],
                [n.timestamp for n in batch],
                [n.type for n in batch],
                [n.shard_key or "default" for n in batch],
                self.user_id,
                [n.is_super_node for n in batch])
            self.index.restore_access(qids,
                                      [n.access_count for n in batch],
                                      [n.last_accessed for n in batch])

        edge_rows = self.store.get_edges(user_id=self.user_id)
        triples = []
        for r in edge_rows:
            edge = Edge(
                source=r.get("source_id") or r.get("source"),
                target=r.get("target_id") or r.get("target"),
                weight=float(r.get("weight", 0.5)),
                edge_type=r.get("edge_type", "relates_to"),
                co_occurrence=int(r.get("co_occurrence", 1)),
                last_updated=r.get("last_updated", time.time()),
            )
            owner = self._shard_of_node(edge.source)
            if owner is None:
                owner = self._get_or_create_shard("default")
            owner.edges[edge.key] = edge
            self._edge_shard[edge.key] = owner.shard_key
            triples.append((self._q(edge.source), self._q(edge.target), edge.weight))
        if triples:
            self.index.add_edges(triples, self.user_id)

    def check_for_updates(self) -> bool:
        try:
            current = self.store.get_latest_version()
            if current > self._last_version:
                self._log(f"🔄 Store updated (v{current}), reloading...")
                self._load_from_persistence()
                return True
        except Exception:
            pass
        return False

    # ----------------------------------------------------------- JSON snapshot
    def save_snapshot(self, snapshot_dir: str) -> str:
        """Fast binary system snapshot: the arena checkpoint (ALL tenants'
        embeddings + numerics, ``core/checkpoint.py``) plus a host-side JSON
        of the current user's structural graph WITHOUT embeddings — the
        1M-scale complement to ``save_state``'s human-readable JSON
        (reference memory_system.py:1216-1273)."""
        from lazzaro_tpu.core import checkpoint as ckpt
        from lazzaro_tpu.core.store import _atomic_write

        # Drain BEFORE taking the mutex: the background worker acquires the
        # same mutex to consolidate, so draining inside it would deadlock —
        # and snapshotting without draining would miss the just-ended
        # conversation's memories.
        self._drain_background()
        with self._mutex:
            self._sync_from_arena()

            def slim(node: Node) -> Dict[str, Any]:
                d = node.to_dict()
                d.pop("embedding", None)
                return d

            # One id stamped into BOTH halves: host.json and the index
            # checkpoint are written separately (never atomic as a pair), so
            # a crash between the writes leaves a fresh half paired with a
            # stale one — load_snapshot verifies the ids match and warns
            # when they don't (r3 advisor finding).
            import uuid
            snapshot_id = uuid.uuid4().hex
            host = {
                "snapshot_id": snapshot_id,
                "user_id": self.user_id,
                "shards": {
                    k: {
                        "nodes": [slim(n) for n in v.nodes.values()],
                        "edges": [e.to_dict() for e in v.edges.values()],
                    }
                    for k, v in self.shards.items()
                },
                "super_nodes": [slim(n) for n in self.super_nodes.values()],
                "profile": self.profile.to_dict(),
                "node_counter": self.node_counter,
                "conversation_count": self.conversation_count,
                "settings": {
                    "auto_consolidate": self.auto_consolidate,
                    "consolidate_every": self.consolidate_every,
                    "auto_prune": self.auto_prune,
                    "prune_threshold": self.prune_threshold,
                    "max_buffer_size": self.max_buffer_size,
                },
            }
            # Multi-host: only rank 0 writes host.json (N ranks would race
            # last-writer-wins on a shared filesystem and could pair rank-k
            # host state with rank-0's index). host.json goes FIRST so that
            # save_index's internal all-rank barrier is the last sync point
            # — once any rank returns, both files are durably in place.
            if jax.process_count() == 1 or jax.process_index() == 0:
                os.makedirs(snapshot_dir, exist_ok=True)
                _atomic_write(os.path.join(snapshot_dir, "host.json"),
                              json.dumps(host).encode())
            ckpt.save_index(self.index, os.path.join(snapshot_dir, "index"),
                            extra_meta={"snapshot_id": snapshot_id})
        return f"✓ Snapshot saved to {snapshot_dir}"

    def load_snapshot(self, snapshot_dir: str) -> str:
        """Restore from ``save_snapshot`` output. Host nodes come back with
        ``embedding=None`` — the arena owns the vectors; persistence and
        merge paths fetch them on demand (``_bulk_fill_embeddings``). Any
        in-flight conversation is discarded (the snapshot is the new truth)
        and the per-user WAL is reopened for the snapshot's user."""
        from lazzaro_tpu.core import checkpoint as ckpt

        try:
            with open(os.path.join(snapshot_dir, "host.json")) as f:
                host = json.load(f)
        except FileNotFoundError:
            return f"⚠ No snapshot at {snapshot_dir}"
        except json.JSONDecodeError as e:
            return f"⚠ Corrupt snapshot at {snapshot_dir}: {e}"
        if not isinstance(host, dict):
            return f"⚠ Corrupt snapshot at {snapshot_dir}: host.json is not an object"

        # Stage EVERYTHING fallibly before touching live state, so a corrupt
        # snapshot can never leave the system half-restored.
        pair_warning = ""
        try:
            new_index = ckpt.load_index(os.path.join(snapshot_dir, "index"),
                                        mesh=self.mesh,
                                        int8_serving=self.config.int8_serving,
                                        ivf_nprobe=self.config.ivf_serving,
                                        pq_serving=self.config.pq_serving,
                                        coarse_slack=self.config.coarse_fetch_slack,
                                        telemetry=self.telemetry,
                                        serve_k_max=self.config.serve_k_max,
                                        serve_pad_granularity=self.config.serve_pad_granularity,
                                        serve_kernel_cache_max=self.config.serve_kernel_cache_max)
            # Pairing check: both halves carry the save's snapshot_id; a
            # mismatch means a crash landed between the two writes and one
            # half is stale. Restore proceeds (both halves are individually
            # consistent) but the caller is warned.
            sid_host = host.get("snapshot_id")
            sid_index = ckpt.read_meta(
                os.path.join(snapshot_dir, "index")).get("snapshot_id")
            if sid_host and sid_index and sid_host != sid_index:
                pair_warning = (" ⚠ host.json and index checkpoint carry "
                                "different snapshot ids — one half is stale "
                                "(crash between the two writes?)")
                self._log(f"⚠ snapshot pair mismatch in {snapshot_dir}: "
                          f"host={sid_host[:8]} index={sid_index[:8]}")
            staged_shards: Dict[str, Tuple[List[Node], List[Edge]]] = {}
            for shard_key, sd in host.get("shards", {}).items():
                staged_shards[shard_key] = (
                    [Node.from_dict(nd) for nd in sd.get("nodes", [])],
                    [Edge.from_dict(ed) for ed in sd.get("edges", [])])
            staged_supers = [Node.from_dict(nd)
                             for nd in host.get("super_nodes", [])]
        except (OSError, ValueError, KeyError, TypeError) as e:
            return f"⚠ Corrupt snapshot at {snapshot_dir}: {e}"

        self._drain_background()   # outside the mutex: the worker needs it
        # The tier pump (if any) drives the OLD index's manager — stop it
        # before the swap and restart it against the restored one.
        if self.tier_pump is not None:
            self.tier_pump.stop()
            self.tier_pump = None
        with self._mutex:
            self.index = new_index
            if (new_index.tiering is not None
                    and self.config.tier_pump_interval_s > 0
                    and self.enable_async):
                from lazzaro_tpu.tier import TierPump
                self.tier_pump = TierPump(
                    new_index.tiering,
                    self.config.tier_pump_interval_s).start()
            self.user_id = host.get("user_id", self.user_id)
            self.shards.clear()
            self.super_nodes.clear()
            self._edge_shard.clear()
            self._node_shard_cache.clear()
            # Pre-restore session state is meaningless against the new graph.
            self.conversation_active = False
            self.short_term_memory.clear()
            self.conversation_history.clear()
            self.consolidation_queue.clear()
            self._inflight_batches.clear()
            # Truncate the pre-restore WAL (still the old user's handle):
            # the discarded turns must not be replayed as "crashed".
            self._journal_sync()
            for shard_key, (nodes, edges) in staged_shards.items():
                shard = self._get_or_create_shard(shard_key)
                for node in nodes:
                    shard.add_node(node)
                for edge in edges:
                    shard.edges[edge.key] = edge
                    self._edge_shard[edge.key] = shard_key
            for node in staged_supers:
                self.super_nodes[node.id] = node
            profile_data = host.get("profile", {})
            self.profile.data = profile_data.get("data", self.profile.data)
            self.profile.last_updated = profile_data.get(
                "last_updated", time.time())
            self.node_counter = host.get("node_counter", 0)
            self.conversation_count = host.get("conversation_count", 0)
            for key, val in host.get("settings", {}).items():
                if hasattr(self, key):
                    setattr(self, key, val)
            # The restored graph no longer matches the store's rows; the
            # next save must be a full rewrite, not a delta.
            self._store_synced = False
            self._dirty_nodes.clear()
            self._dirty_edges.clear()
            self._deleted_edge_ids.clear()
            if self.query_cache:
                self.query_cache.invalidate_results()
        # Reopen the WAL for the (possibly different) restored user —
        # mirrors switch_user; replays that user's crashed turns if any.
        self._setup_journal()
        self._setup_ingest_journal()
        return f"✓ Snapshot loaded from {snapshot_dir}{pair_warning}"

    def save_state(self, filename: str = "memory_state.json") -> str:
        with self._mutex:
            self._sync_from_arena()

            def dicts_for(nodes: List[Node]) -> List[Dict[str, Any]]:
                # Snapshot-loaded nodes carry embedding=None; fill from the
                # arena so a save_state → load_state round trip keeps them
                # searchable (load_state skips embedding-less nodes).
                out = [n.to_dict() for n in nodes]
                self._bulk_fill_embeddings(out, [n.id for n in nodes])
                return out

            state = {
                "shards": {
                    k: {
                        "nodes": dicts_for(list(v.nodes.values())),
                        "edges": [e.to_dict() for e in v.edges.values()],
                    }
                    for k, v in self.shards.items()
                },
                "super_nodes": dicts_for(list(self.super_nodes.values())),
                "profile": self.profile.to_dict(),
                "node_counter": self.node_counter,
                "conversation_count": self.conversation_count,
                "settings": {
                    "auto_consolidate": self.auto_consolidate,
                    "consolidate_every": self.consolidate_every,
                    "auto_prune": self.auto_prune,
                    "prune_threshold": self.prune_threshold,
                    "max_buffer_size": self.max_buffer_size,
                },
            }
        with open(filename, "w") as f:
            json.dump(state, f, indent=2)
        return f"✓ State saved to {filename}"

    def load_state(self, filename: str = "memory_state.json") -> str:
        try:
            with open(filename) as f:
                state = json.load(f)
        except FileNotFoundError:
            return f"⚠ File {filename} not found"

        with self._mutex:
            stale = list(self.index.tenant_nodes.get(self.user_id, set()))
            if stale:
                self.index.delete(stale)
            self.shards.clear()
            self.super_nodes.clear()
            self._edge_shard.clear()
            self._node_shard_cache.clear()

            batch: List[Node] = []
            for shard_key, shard_data in state.get("shards", {}).items():
                shard = self._get_or_create_shard(shard_key)
                for nd in shard_data.get("nodes", []):
                    node = Node.from_dict(nd)
                    shard.add_node(node)
                    if node.embedding is not None and len(node.embedding) == self.embed_dim:
                        batch.append(node)
                for ed in shard_data.get("edges", []):
                    edge = Edge.from_dict(ed)
                    shard.edges[edge.key] = edge
                    self._edge_shard[edge.key] = shard_key
            for nd in state.get("super_nodes", []):
                node = Node.from_dict(nd)
                self.super_nodes[node.id] = node
                if node.embedding is not None and len(node.embedding) == self.embed_dim:
                    batch.append(node)

            if batch:
                self.index.add(
                    [self._q(n.id) for n in batch],
                    np.asarray([n.embedding for n in batch], np.float32),
                    [n.salience for n in batch],
                    [n.timestamp for n in batch],
                    [n.type for n in batch],
                    [n.shard_key or "default" for n in batch],
                    self.user_id,
                    [n.is_super_node for n in batch])
            triples = [(self._q(e.source), self._q(e.target), e.weight)
                       for s in self.shards.values() for e in s.edges.values()]
            if triples:
                self.index.add_edges(triples, self.user_id)

            profile_data = state.get("profile", {})
            self.profile.data = profile_data.get("data", self.profile.data)
            self.profile.last_updated = profile_data.get("last_updated", time.time())
            self.node_counter = state.get("node_counter", 0)
            self.conversation_count = state.get("conversation_count", 0)
            for key, val in state.get("settings", {}).items():
                if hasattr(self, key):
                    setattr(self, key, val)
            # Imported graph diverges from the store; force a full rewrite.
            self._store_synced = False
            self._dirty_nodes.clear()
            self._dirty_edges.clear()
            self._deleted_edge_ids.clear()
        return f"✓ State loaded from {filename}"

    # --------------------------------------------------------- export/insights
    def export_observations(self, format: str = "markdown") -> str:
        with self._mutex:
            self._sync_from_arena()
            nodes = [n for s in self.shards.values() for n in s.nodes.values()
                     if not n.is_super_node]
        nodes.sort(key=lambda n: (n.salience, n.last_accessed), reverse=True)
        top = nodes[:self.config.export_top_n]

        if format == "json":
            return json.dumps([n.to_dict() for n in top], indent=2)

        lines = [f"# Memory Observations for {self.user_id}", ""]
        for n in top:
            lines.append(f"### {n.type.capitalize()} Memory ({n.shard_key})")
            lines.append(f"- **Content**: {n.content}")
            lines.append(f"- **Salience**: {n.salience:.2f}")
            lines.append(f"- **Last Accessed**: {time.ctime(n.last_accessed)}")
            lines.append("")
        return "\n".join(lines)

    def get_insights(self) -> str:
        observations = self.export_observations(format="json")
        system_prompt = f"""Analyze these atomic memories for user '{self.user_id}' and provide a comprehensive psychological and knowledge profile.
Identify long-term patterns, core beliefs, persistent interests, and significant life events reflected in the data.

Structure your response as:
1. **Personality Traits**: Key characteristics detected.
2. **Core Interests & Knowledge**: What the user knows and cares about.
3. **Behavioral Patterns**: How the user typically interacts or works.
4. **Recent Focus**: Most salient topics from recent memories.

Be clinical yet insightful. Do not include conversational filler."""
        return self._call_llm([
            {"role": "system", "content": system_prompt},
            {"role": "user", "content": f"User Observations:\n{observations}"},
        ])

    # ----------------------------------------------------------- observability
    def get_stats(self) -> Dict:
        nodes, edges = self.buffer.size()
        rt = self.telemetry.timer_values("chat.retrieval_ms")
        ct = self.telemetry.timer_values("consolidation.run_ms")
        avg_retrieval = float(np.mean(rt)) if rt else 0
        p95_retrieval = float(np.percentile(rt, 95)) if rt else 0
        avg_consolidation = float(np.mean(ct)) / 1e3 if ct else 0
        cache_hit_rate = self.query_cache.get_hit_rate() if self.query_cache else 0.0
        sem_rate = self._semantic_hit_rate()
        # ISSUE 20 satellite: both cache tiers land in the Telemetry
        # registry, labeled, so the dashboard's /metrics and
        # metrics_summary() read the same numbers this block formats
        self.telemetry.gauge("serve.cache_hit_rate", cache_hit_rate,
                             labels={"tier": "exact"})
        if sem_rate is not None:
            self.telemetry.gauge("serve.cache_hit_rate", sem_rate,
                                 labels={"tier": "semantic"})
        return {
            "buffer_nodes": nodes,
            "buffer_edges": edges,
            "num_shards": len(self.shards),
            "num_super_nodes": len(self.super_nodes),
            "short_term_memories": len(self.short_term_memory),
            "conversation_active": self.conversation_active,
            "conversation_count": self.conversation_count,
            "profile_domains_filled": sum(1 for v in self.profile.data.values() if v),
            "auto_consolidate": self.auto_consolidate,
            "vector_store": "HBM Arena + ArrowStore (Active)" if self.store else "None",
            "performance": {
                "avg_retrieval_ms": f"{avg_retrieval:.1f}",
                "p95_retrieval_ms": f"{p95_retrieval:.1f}",
                "avg_consolidation_s": f"{avg_consolidation:.2f}",
                "cache_hit_rate": f"{cache_hit_rate:.1%}",
                "semantic_cache_hit_rate": (f"{sem_rate:.1%}"
                                            if sem_rate is not None
                                            else None),
                "llm_calls": self.metrics["llm_calls"],
                "embedding_calls": self.metrics["embedding_calls"],
            },
            "index": self.index.stats(),
            "serving": (self.query_scheduler.stats()
                        if self.query_scheduler is not None else None),
            "providers": {
                "llm": type(self.llm).__name__,
                "embedder": type(self.embedder).__name__,
                "llm_health": (self.llm.health()
                               if hasattr(self.llm, "health") else None),
                "embedder_health": (self.embedder.health()
                                    if hasattr(self.embedder, "health") else None),
            },
        }

    def _semantic_hit_rate(self) -> Optional[float]:
        """Semantic-cache hit rate over every dispatch that carried the
        ring (None while the cache is off or untouched)."""
        tel = self.telemetry
        hits = tel.counter_total("serve.semantic_hits")
        misses = tel.counter_total("serve.semantic_misses")
        if hits + misses == 0:
            return None
        return hits / (hits + misses)

    def metrics_summary(self) -> Dict:
        """One JSON-able observability surface (ISSUE 6): the Telemetry
        snapshot — host spans (queue wait, dispatch wall, decode), device
        counters decoded from every fused readback (gate hit/miss, top-k
        shortfall, dedup hits, boost-scatter rows, link-pool occupancy/
        overflow), and gauges (batch occupancy, compile-cache sizes,
        peak-HBM per kernel) — plus the derived headline numbers the CI
        artifact gate checks. The dashboard's Prometheus ``/metrics``
        endpoint renders the SAME registry, so its samples match this
        summary by construction (a test pins that)."""
        tel = self.telemetry
        padded = tel.counter_total("serve.padded_slots")
        live = tel.counter_total("serve.live_requests")
        qw = tel.timer_values("serve.queue_wait_ms")
        peak_hbm = {k: v for k, v in tel.gauges.items()
                    if k.startswith("kernel.peak_hbm_bytes")}
        return {
            "telemetry": tel.snapshot(),
            # Tiered memory (ISSUE 8): the tier gauges also live in the
            # registry snapshot above; this block is the derived headline
            # view (None when tiering is off).
            "tier": (self.index.tiering.stats()
                     if self.index.tiering is not None else None),
            # Paged arena (ISSUE 17): page occupancy + free-list traffic
            # headline (None when the index is dense). The same gauges/
            # counters live in the registry snapshot above.
            "paged_arena": (self.index._page_block()
                            if getattr(self.index, "_pager", None)
                            is not None else None),
            "pad_waste_fraction": ((1.0 - live / padded) if padded else 0.0),
            "queue_wait_ms_p50": (float(np.percentile(qw, 50)) if qw
                                  else None),
            "queue_wait_ms_p95": (float(np.percentile(qw, 95)) if qw
                                  else None),
            "serve_dispatches": tel.counter_total("serve.dispatches"),
            "ingest_dispatches": tel.counter_total("ingest.dispatches"),
            # ISSUE 20: both cache tiers' headline hit rates — "exact"
            # is the text-keyed QueryCache, "semantic" the device ring
            # (None until a ring dispatch ran)
            "cache_hit_rate": {
                "exact": (self.query_cache.get_hit_rate()
                          if self.query_cache else 0.0),
                "semantic": self._semantic_hit_rate(),
            },
            "semantic_stale_evictions": tel.counter_total(
                "serve.semantic_stale_evictions"),
            # ISSUE 16 satellite: rows the non-fused write surface spilled
            # into the exact-scan extras (pod add()) — the residual write
            # path's burden on the coarse structure, as a headline number.
            "ivf_add_extras_spills": tel.counter_total(
                "ivf.add_extras_spills"),
            "link_pool_overflows": self.index.link_pool_overflows,
            "peak_hbm_bytes": peak_hbm or None,
            "scheduler": (self.query_scheduler.stats()
                          if self.query_scheduler is not None else None),
            # Reliability layer (ISSUE 10): breaker state, recovery and
            # shed counters, journal depth — the numbers the fault-matrix
            # CI gate and the dashboard's /api/reliability read.
            "reliability": self.reliability_summary(),
            "counters": {
                "llm_calls": self.metrics["llm_calls"],
                "embedding_calls": self.metrics["embedding_calls"],
                "edges_linked": self.metrics["edges_linked"],
            },
        }

    def reliability_summary(self) -> Dict:
        """Derived reliability view (ISSUE 10): circuit-breaker state,
        dispatch-retry / shed / restart / replay counters, ingest-journal
        depth, and the poisoned flag. Served by the dashboard's
        ``GET /api/reliability`` and embedded in ``metrics_summary()``."""
        tel = self.telemetry
        sched = self.query_scheduler
        jr = self._ingest_journal
        return {
            "poisoned": bool(getattr(self.index, "poisoned", False)),
            "breaker": (sched.breaker.stats()
                        if sched is not None and sched.breaker is not None
                        else None),
            "dispatch_retries": tel.counter_total("serve.dispatch_retries"),
            "load_shed": tel.counter_total("reliability.load_shed"),
            "degraded_requests": tel.counter_total(
                "reliability.degraded_requests"),
            "watchdog_timeouts": tel.counter_total(
                "reliability.watchdog_timeouts"),
            "worker_restarts": tel.counter_total(
                "reliability.worker_restarts"),
            "ingest_failures": tel.counter_total(
                "reliability.ingest_failures"),
            "journal_replayed": tel.counter_total(
                "reliability.journal_replayed"),
            "journal_pending_batches": (jr.pending_count
                                        if jr is not None else None),
            "journal_pending_facts": (jr.pending_facts
                                      if jr is not None else None),
        }

    def display_stats(self) -> str:
        stats = self.get_stats()
        next_consolidation = self.consolidate_every - (
            self.conversation_count % self.consolidate_every)
        return f"""
📊 SCALABLE MEMORY SYSTEM STATS:
STORAGE:
  • Buffer nodes: {stats["buffer_nodes"]} / {self.max_buffer_size} max
  • Buffer edges: {stats["buffer_edges"]}
  • Shards: {stats["num_shards"]}
  • Super-nodes: {stats["num_super_nodes"]}
  • STM: {stats["short_term_memories"]}
  • Conversations: {stats["conversation_count"]}
  • Profile domains: {stats["profile_domains_filled"]}/5

⚡ PERFORMANCE:
  • Avg retrieval: {stats["performance"]["avg_retrieval_ms"]}ms
  • P95 retrieval: {stats["performance"]["p95_retrieval_ms"]}ms
  • Avg consolidation: {stats["performance"]["avg_consolidation_s"]}s
  • Cache hit rate: {stats["performance"]["cache_hit_rate"]}
  • LLM calls: {stats["performance"]["llm_calls"]}
  • Embedding calls: {stats["performance"]["embedding_calls"]}

⚙️ AUTO-MANAGEMENT:
  • Auto-consolidate: {"ON" if stats["auto_consolidate"] else "OFF"} (every {self.consolidate_every})
    → Next in: {next_consolidation} conversation(s)
  • Auto-prune: {"ON" if self.auto_prune else "OFF"} (threshold: {self.prune_threshold})
  • Max buffer: {self.max_buffer_size} nodes
  • Sharding: {"ON" if self.enable_sharding else "OFF"}
  • Hierarchy: {"ON" if self.enable_hierarchy else "OFF"}
  • Caching: {"ON" if self.enable_caching else "OFF"}
  • Async: {"ON" if self.enable_async else "OFF"}
"""

    def display_memories(self, limit: int = 10) -> str:
        if not self.buffer.nodes:
            return "No memories stored yet."
        nodes = self.buffer.get_all_nodes_summary()
        out = [f"\n💭 Stored Memories (showing {min(limit, len(nodes))} of {len(nodes)}):"]
        for i, node in enumerate(nodes[:limit], 1):
            out.append(f"\n{i}. [{node['type']}] 📦 {node['shard']} "
                       f"(salience: {node['salience']:.2f}, accessed: {node['access_count']}x)")
            out.append(f"   {node['content']}")
        return "\n".join(out)

    def display_profile(self) -> str:
        return f"\n👤 User Profile:\n{self.profile.get_context()}\n"

    # ------------------------------------------------------------------- close
    def close(self) -> None:
        pump = getattr(self, "tier_pump", None)
        if pump is not None:
            pump.stop()
        lpump = getattr(self, "lifecycle_pump", None)
        if lpump is not None:
            lpump.stop()
        sched = getattr(self, "query_scheduler", None)
        if sched is not None:
            sched.close()
        if getattr(self, "background_executor", None):
            self.background_executor.shutdown(wait=True)
        # Facts the ingest flush policy deferred must not wait for a next
        # session (the WAL would replay their turns, but landing them now
        # is cheaper than a re-extraction): force one final drain, then
        # flush any queued cache-hit boosts.
        if getattr(self, "_ingest_coalescer", None) and len(self._ingest_coalescer):
            start = time.time()
            wait_ms = self._ingest_coalescer.oldest_age_s() * 1e3
            commit_to = (self._ingest_journal.last_seq
                         if self._ingest_journal is not None else 0)
            drained: List[Tuple[str, str]] = []
            for facts, _n_convs in self._ingest_coalescer.drain():
                self.telemetry.record("ingest.coalesce_wait_ms", wait_ms)
                drained.extend(self._ingest_facts(facts))
            self._finish_consolidation(drained, start)
            if self._ingest_journal is not None:
                self._commit_ingest_journal(commit_to)
        if getattr(self, "_pending_boosts", None):
            self._flush_pending_boosts()
        if hasattr(self, "store") and self.store is not None:
            self.store.close()
