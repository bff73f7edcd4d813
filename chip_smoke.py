#!/usr/bin/env python3
"""On-chip smoke: the memory store's main path, once, on one TPU.

    python3 chip_smoke.py --seed 0            # one chip
    python3 chip_smoke.py --seed 0 --mesh 4   # a four-chip host

One process, no subprocess, no network, every input made from ``--seed``.
Drives ``MemorySystem`` through the entry points a user calls, at the
per-chip share of BASELINE.json's "1M memories on a v5e-8" (131,072 rows ×
768 bf16 preallocated), and checks every answer against a plain NumPy
float32 reference. Phases:

  store    switch_user → start_conversation → add_to_short_term →
           end_conversation (5,000 facts each) under 13 tenants, then
           search_memories, search_memories_batch, chat, one int8 pass,
           an empty tenant; reliability counters must all be 0
  kernels  both Pallas kernels compiled (not interpreted) against their
           XLA references
  encoder  bge-base-en-v1.5 geometry (12 × 768, random weights), 1,024
           texts, against the same forward on this process's CPU device
  extract  one conversation whose extraction call is the on-device
           constrained JSON decode (``small`` LM: a toy width)
  mesh     (≥ 4 devices) the store flow row-sharded over 4 chips, and
           ReplicaPlacement with 2 groups × 2 chips

No phase is wrapped in try/except: a phase passes its checks or the process
dies with the reason on its last lines. Without a TPU it exits non-zero and
prints no result. The last stdout line of a passing run is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``; the line before it
summarises phases, rows and score gaps, and the detail goes to
``chiprun_out/chip_smoke.json``.

``--cpu-debug`` (tiny sizes, any backend) and ``--only`` (a subset of
phases) are for debugging the script itself: such a run exits 3 and prints
no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import re
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

import jax
import jax.numpy as jnp

from lazzaro_tpu import MemorySystem, native
from lazzaro_tpu.config import MemoryConfig
from lazzaro_tpu.core import state as S
from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.core.providers import EncoderEmbedder, OnDeviceLLM
from lazzaro_tpu.models.encoder import EncoderConfig, TextEncoder
from lazzaro_tpu.models.llm import LanguageModel, LMConfig
from lazzaro_tpu.ops.flash_attention import flash_attention, reference_attention
from lazzaro_tpu.parallel.mesh import make_mesh
from lazzaro_tpu.parallel.replica import ReplicaPlacement
from lazzaro_tpu.serve.scheduler import RetrievalRequest
from lazzaro_tpu.utils.compile_cache import place_compile_cache

PHASES = ("store", "kernels", "encoder", "extract", "mesh")
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "chiprun_out")

# Corpus geometry (the bench corpus's, per tenant): groups of GROUP facts at
# cosine ≈ 0.88 (above the 0.5 link gate, below the 0.95 dedup gate), every
# DUP_EVERY-th fact a cosine ≈ 0.97 near-duplicate of its predecessor.
GROUP = 4
DUP_EVERY = 101
TOPICS = ("work", "hobbies", "family", "travel", "health", "food",
          "sports", "music", "books", "tech", "home", "finance")
TOPIC_W, GROUP_W, NOISE_W = 0.5, float(np.sqrt(0.63)), float(np.sqrt(0.12))

# Stated tolerances. bf16 arena: products of bf16 operands are exact in f32,
# only the accumulation order differs. f32 arena: a TPU multiplies f32
# operands in bf16 passes at default precision (ISSUE 21 writes the measured
# gap down; precision is not changed here).
TOL_BF16_ARENA = 1e-4
TOL_F32_ARENA = 2e-2
# The int8 path rescores its survivors with a gathered-row einsum that XLA
# lowers to an f32 multiply-reduce, where it may keep the query in f32
# instead of rounding it to the arena dtype (excess precision): its scores
# then sit one bf16 rounding of the query away from the exact path's.
TOL_INT8_RESCORE = 1e-3
TOL_ENCODER = 2e-2
TOL_FLASH = 5e-2
K = 5


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    full: bool = True                # False: --cpu-debug's tiny geometry
    dim: int = 768
    capacity: int = 131_072 + 64
    max_edges: int = 2 * 131_072 + 64
    facts_per_conv: int = 5_000
    convs_per_tenant: int = 2
    tenants: int = 13                # 26 conversations, 130,000 facts
    min_tenants: int = 8
    extras: int = 8                  # facts the chat conversation adds
    ingest_budget_s: float = 540.0
    search_tenants: int = 4
    probes_per_tenant: int = 8       # 32 search_memories calls
    batch: int = 64
    f32_rows: int = 4_096
    encoder_texts: int = 1_024
    encoder_ref_texts: int = 8
    mesh_tenants: int = 3
    replica_capacity: int = 8_191
    replica_facts: int = 512

    @staticmethod
    def tiny() -> "Sizes":
        return Sizes(full=False, dim=64, capacity=4_096 + 64, max_edges=8_192,
                     facts_per_conv=200, tenants=4, min_tenants=3,
                     probes_per_tenant=4, batch=16, f32_rows=256,
                     encoder_texts=8, encoder_ref_texts=4, mesh_tenants=2,
                     replica_capacity=511, replica_facts=64)

    @property
    def tenant_facts(self) -> int:
        return self.facts_per_conv * self.convs_per_tenant + self.extras


# --------------------------------------------------------------------------
# Seeded data: every vector, text and payload is a pure function of the seed.
# --------------------------------------------------------------------------

def _unit_rows(x: np.ndarray) -> np.ndarray:
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def is_dup(j: np.ndarray) -> np.ndarray:
    return (np.asarray(j) % DUP_EVERY) == DUP_EVERY - 1


def live_count(facts: int) -> int:
    """Nodes ``facts`` consecutive facts of one tenant leave: the seeded
    near-duplicates merge into their predecessors."""
    return facts - int(is_dup(np.arange(facts)).sum())


def tenant_corpus(seed: int, tenant: int, n: int, dim: int) -> np.ndarray:
    """[n, dim] f32 unit vectors of tenant ``tenant``'s facts. Group mates
    sit at stride n/GROUP, i.e. in different conversations: the link scan
    only sees rows of earlier batches."""
    rng = np.random.default_rng([seed, tenant])
    n_groups = max(1, n // GROUP)
    topics = _unit_rows(rng.standard_normal((len(TOPICS), dim)))
    groups = _unit_rows(rng.standard_normal((n_groups, dim)))
    noise = _unit_rows(rng.standard_normal((n, dim)))
    dup_noise = _unit_rows(rng.standard_normal((n, dim)))
    g = np.arange(n) % n_groups
    v = _unit_rows(TOPIC_W * topics[g % len(TOPICS)] + GROUP_W * groups[g]
                   + NOISE_W * noise)
    d = np.nonzero(is_dup(np.arange(n)))[0]
    v[d] = _unit_rows(v[d - 1] + 0.25 * dup_noise[d])   # cosine ≈ 0.970
    return v


def fact_text(tenant: int, j: int) -> str:
    return f"fact {tenant}.{j}: user detail number {j} of tenant {tenant}"


_FACT_RE = re.compile(r"fact (\d+)\.(\d+):")


def fact_of(text: str) -> Optional[Tuple[int, int]]:
    m = _FACT_RE.match(text)
    return (int(m.group(1)), int(m.group(2))) if m else None


def fact_topic(j: int, n: int) -> str:
    return TOPICS[(j % max(1, n // GROUP)) % len(TOPICS)]


def conversation_payload(tenant: int, conv: int, sz: Sizes) -> str:
    base = conv * sz.facts_per_conv
    return json.dumps({"memories": [
        {"content": fact_text(tenant, j), "type": "semantic", "salience": 0.6,
         "topic": fact_topic(j, sz.tenant_facts)}
        for j in range(base, base + sz.facts_per_conv)]})


def extras_payload(tenant: int, sz: Sizes) -> str:
    base = sz.convs_per_tenant * sz.facts_per_conv
    return json.dumps({"memories": [
        {"content": fact_text(tenant, j), "type": "semantic", "salience": 0.6,
         "topic": fact_topic(j, sz.tenant_facts)}
        for j in range(base, base + sz.extras)]})


class SeededEmbedder:
    """EmbeddingProvider: fact texts map to their corpus vector, anything
    else to a vector drawn from a hash of (seed, text)."""

    def __init__(self, seed: int, sz: Sizes):
        self.seed, self.sz, self.dim = seed, sz, sz.dim
        self._corpora: Dict[int, np.ndarray] = {}

    def corpus(self, tenant: int) -> np.ndarray:
        if tenant not in self._corpora:
            self._corpora[tenant] = tenant_corpus(
                self.seed, tenant, self.sz.tenant_facts, self.dim)
        return self._corpora[tenant]

    def _vec(self, text: str) -> np.ndarray:
        f = fact_of(text)
        if f is not None:
            return self.corpus(f[0])[f[1]]
        h = hashlib.blake2b(f"{self.seed}:{text}".encode(), digest_size=8)
        rng = np.random.default_rng(int.from_bytes(h.digest(), "little"))
        return _unit_rows(rng.standard_normal((1, self.dim)))[0]

    def embed(self, text: str) -> List[float]:
        return self._vec(text).tolist()

    def batch_embed(self, texts: Sequence[str]) -> List[List[float]]:
        return np.stack([self._vec(t) for t in texts]).tolist()


_TRANSCRIPT_RE = re.compile(r"transcript of conversation (\d+)\.(\d+)")


class SeededLLM:
    """LLMProvider: a pure function of its messages. The extraction prompt
    of conversation T.C gets that conversation's fact payload; the
    extraction of a chat conversation gets the tenant's extra facts; a chat
    turn gets a fixed acknowledgement (and is kept for the context check)."""

    def __init__(self, sz: Sizes):
        self.sz = sz
        self.last_chat: List[Dict[str, str]] = []

    def completion(self, messages, response_format=None) -> str:
        if response_format and response_format.get("type") == "json_object":
            user = next((m["content"] for m in reversed(messages)
                         if m["role"] == "user"), "")
            m = _TRANSCRIPT_RE.search(user)
            if m:
                return conversation_payload(int(m.group(1)), int(m.group(2)),
                                            self.sz)
            f = _FACT_RE.search(user)
            if f:
                return extras_payload(int(f.group(1)), self.sz)
            return json.dumps({"memories": []})
        self.last_chat = list(messages)
        return "Noted."

    def completion_stream(self, messages, response_format=None):
        yield self.completion(messages, response_format)


# --------------------------------------------------------------------------
# The plain reference: NumPy float32 over the vectors the arena stores.
# --------------------------------------------------------------------------

def stored(v: np.ndarray, dtype) -> np.ndarray:
    """What the arena holds for ``v`` and scores a query with: normalized in
    f32, rounded to the arena dtype, widened back to f32."""
    v = np.asarray(v, np.float32)
    v = v / np.maximum(np.linalg.norm(v, axis=-1, keepdims=True), 1e-9)
    return v.astype(dtype).astype(np.float32)


def reference_topk(rows: np.ndarray, live: np.ndarray, q: np.ndarray, k: int):
    """(scores_sorted[k], idx_sorted[k], all_scores) — masked f32 cosine."""
    scores = rows @ q
    masked = np.where(live, scores, -np.inf)
    order = np.argsort(-masked, kind="stable")[:k]
    return masked[order], order, scores


class Gap:
    """Largest |chip score − reference score| seen, and rank agreement:
    the chip's r-th hit must score, in the reference, what the reference's
    r-th hit scores — equal ids except for ties inside the tolerance."""

    def __init__(self, tol: float, what: str):
        self.tol, self.what, self.max = tol, what, 0.0
        self.checked = 0

    def compare(self, label: str, got_idx: Sequence[int],
                got_scores: Optional[Sequence[float]], rows: np.ndarray,
                live: np.ndarray, q: np.ndarray, k: int) -> None:
        ref_s, ref_i, all_s = reference_topk(rows, live, q, k)
        n_ref = int(np.isfinite(ref_s).sum())
        check(len(got_idx) == n_ref,
              f"{self.what} {label}: {len(got_idx)} hits, reference has {n_ref}")
        for r, j in enumerate(got_idx):
            check(bool(live[j]), f"{self.what} {label}: hit {j} is not a live "
                                 f"row of this tenant")
            if got_scores is not None:
                self.max = max(self.max, abs(float(got_scores[r])
                                             - float(all_s[j])))
            check(abs(float(all_s[j]) - float(ref_s[r])) <= self.tol,
                  f"{self.what} {label}: rank {r} is row {j} (reference score "
                  f"{all_s[j]:.6f}) but the reference's rank {r} is row "
                  f"{ref_i[r]} ({ref_s[r]:.6f}); tolerance {self.tol}")
        check(self.max <= self.tol, f"{self.what} {label}: |chip − reference| "
                                    f"score gap {self.max:.3e} > {self.tol}")
        self.checked += 1


class Compiles:
    """Backend compilations of this process, as jax reports them: a phase
    time that hides a recompile is attributed to it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def since(self, mark: Tuple[int, float]) -> str:
        n, s = self.count - mark[0], self.seconds - mark[1]
        return f"{n} compile(s) {s:.1f}s" if n else "no compile"

    def mark(self) -> Tuple[int, float]:
        return self.count, self.seconds



def device_peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


# --------------------------------------------------------------------------
# Phase: store
# --------------------------------------------------------------------------

def tenant_name(t: int) -> str:
    return f"tenant{t:02d}"


def hits_to_rows(nodes, tenant: int) -> List[int]:
    out = []
    for n in nodes:
        f = fact_of(n.content)
        check(f is not None and f[0] == tenant,
              f"tenant {tenant} was served {n.content!r}")
        out.append(f[1])
    return out


def result_rows(ms: MemorySystem, res) -> List[int]:
    """Fact rows of one scheduler RetrievalResult, in rank order."""
    return [fact_of(ms.buffer.get_node(q.partition(":")[2]).content)[1]
            for q in res.ids]


def scored(ms: MemorySystem, emb: "SeededEmbedder", t: int,
           probes: Sequence[int]):
    """The probes once more through the scheduler search_memories itself
    submits to — the one surface that returns scores."""
    futs = ms.query_scheduler.submit_many([
        RetrievalRequest(query=emb.corpus(t)[j], tenant=tenant_name(t), k=K)
        for j in probes])
    return [f.result() for f in futs]


def ingest_tenants(ms: MemorySystem, tenants: Sequence[int], sz: Sizes,
                   compiles: Compiles) -> Tuple[Dict[int, int], float]:
    """Tenant-major ingest through the conversation API; returns
    ({tenant: conversations landed}, clock when the first one landed).
    Stops early past ``sz.ingest_budget_s``."""
    done: Dict[int, int] = {}
    spent = 0.0
    first_at = 0.0
    for t in tenants:
        if spent > sz.ingest_budget_s:
            break
        ms.switch_user(tenant_name(t))
        for c in range(sz.convs_per_tenant):
            t0, mark = time.perf_counter(), compiles.mark()
            ms.start_conversation()
            ms.add_to_short_term(f"transcript of conversation {t}.{c}",
                                 "episodic", 0.7)
            ms.end_conversation()
            dt = time.perf_counter() - t0
            first_at = first_at or time.perf_counter()
            spent += dt
            done[t] = c + 1
            facts = (c + 1) * sz.facts_per_conv
            want = live_count(facts)
            nodes, edges = ms.buffer.size()
            say(f"  ingest tenant {t} conv {c}: {dt:.1f}s "
                f"({compiles.since(mark)}), tenant nodes {nodes} (want "
                f"{want}), tenant edges {edges}, live rows {len(ms.index)}")
            check(nodes == want, f"tenant {t} holds {nodes} nodes after "
                                 f"{facts} facts, expected {want}")
    return done, first_at


def store_system(work: str, name: str, seed: int, sz: Sizes, mesh=None
                 ) -> Tuple[MemorySystem, SeededEmbedder, SeededLLM]:
    emb, llm = SeededEmbedder(seed, sz), SeededLLM(sz)
    ms = MemorySystem(
        enable_async=False, enable_hierarchy=False, auto_consolidate=False,
        max_buffer_size=2 * sz.capacity, load_from_disk=False,
        db_dir=os.path.join(work, name), llm_provider=llm,
        embedding_provider=emb, verbose=False, mesh=mesh,
        config=MemoryConfig(embed_dim=sz.dim, dtype="bfloat16",
                            initial_capacity=sz.capacity,
                            max_edges=sz.max_edges))
    return ms, emb, llm


def probe_rows(seed: int, tenant: int, n_rows: int, count: int) -> List[int]:
    """``count`` distinct non-duplicate fact indices of one tenant."""
    rng = np.random.default_rng([seed, 7_000 + tenant])
    cand = rng.permutation(n_rows)
    return [int(j) for j in cand[~is_dup(cand)][:count]]


def search_tenant(ms: MemorySystem, emb: SeededEmbedder, t: int, n_rows: int,
                  probes: Sequence[int], gap: Gap, label: str
                  ) -> Dict[int, List[int]]:
    """search_memories for each probe + one scored pass through the same
    scheduler; returns {probe: ranked fact rows}."""
    rows = stored(emb.corpus(t)[:n_rows], ml_dtypes.bfloat16)
    live = ~is_dup(np.arange(n_rows))
    out = {}
    for j in probes:
        got = hits_to_rows(ms.search_memories(fact_text(t, j), limit=K), t)
        check(bool(got) and got[0] == j,
              f"{label}: top-1 of fact {t}.{j} is {got[:1]}")
        gap.compare(f"{label} search {t}.{j}", got, None, rows, live,
                    rows[j], K)
        out[j] = got
    for j, res in zip(probes, scored(ms, emb, t, probes)):
        got = result_rows(ms, res)
        check(got == out[j], f"{label}: scheduler ids {got} != "
                             f"search_memories ids {out[j]}")
        gap.compare(f"{label} scored {t}.{j}", got, res.scores, rows, live,
                    rows[j], K)
    return out


RELIABILITY_COUNTERS = ("reliability.ingest_failures", "serve.dispatch_retries",
                        "reliability.poisoned", "reliability.oom",
                        "plan.split_dispatches")


def check_counters(ms: MemorySystem, label: str) -> Dict[str, int]:
    got = {c: int(ms.telemetry.counter_total(c)) for c in RELIABILITY_COUNTERS}
    say(f"  {label} reliability counters: {got}, poisoned flag "
        f"{ms.index.poisoned}")
    check(not any(got.values()) and not ms.index.poisoned,
          f"{label}: a swallowed failure was counted: {got}")
    return got


def phase_store(work: str, seed: int, sz: Sizes, t_start: float,
                compiles: Compiles) -> Tuple[dict, MemorySystem]:
    ms, emb, llm = store_system(work, "store_db", seed, sz)
    arena_rows = int(ms.index.state.emb.shape[0])
    edge_cap0 = int(ms.index.edge_state.capacity)
    say(f"  arena {arena_rows} rows × {sz.dim} {ms.index.state.emb.dtype}, "
        f"edge capacity {edge_cap0}")
    check(ms.index.capacity >= sz.capacity, "arena smaller than requested")

    t0 = time.perf_counter()
    done, first_conv_at = ingest_tenants(ms, range(sz.tenants), sz, compiles)
    ingest_s = time.perf_counter() - t0
    first_conv_s = first_conv_at - t_start
    full = [t for t, c in done.items() if c == sz.convs_per_tenant]
    facts = sum(done.values()) * sz.facts_per_conv
    say(f"  ingested {sum(done.values())} conversations / {facts} facts under "
        f"{len(full)} tenants in {ingest_s:.1f}s; live rows {len(ms.index)}"
        + ("" if len(full) == sz.tenants else "  [TRUNCATED by the budget]"))
    check(len(full) >= sz.min_tenants,
          f"only {len(full)} tenants fully ingested inside the "
          f"{sz.ingest_budget_s:.0f}s budget; need {sz.min_tenants}")
    n_rows = sz.convs_per_tenant * sz.facts_per_conv
    per_tenant = live_count(n_rows)
    want_live = sum(live_count(c * sz.facts_per_conv) for c in done.values())
    check(len(ms.index) == want_live,
          f"{len(ms.index)} live rows, expected {want_live}")
    peak_ingest = device_peak_bytes()

    # ---- 32 search_memories under 4 tenants (each switch reloads one) -----
    gap = Gap(TOL_BF16_ARENA, "bf16 store")
    visit = sorted({full[0], full[1], full[len(full) // 2], full[-1]}
                   )[:sz.search_tenants]
    results: Dict[str, List[int]] = {}
    t0 = time.perf_counter()
    first_answer_s = None
    for t in visit:
        ms.switch_user(tenant_name(t))
        check(ms.buffer.size()[0] == per_tenant,
              f"tenant {t} reloaded {ms.buffer.size()[0]} nodes, expected "
              f"{per_tenant}")
        probes = probe_rows(seed, t, n_rows, sz.probes_per_tenant)
        if first_answer_s is None:
            ms.search_memories(fact_text(t, probes[0]), limit=K)
            first_answer_s = time.perf_counter() - t_start
            say(f"  first search_memories answer {first_answer_s:.1f}s after "
                f"start (first conversation landed after "
                f"{first_conv_s:.1f}s)")
        got = search_tenant(ms, emb, t, n_rows, probes, gap, "store")
        results.update({f"{t}.{j}": r for j, r in got.items()})
    say(f"  {len(results)} search_memories + scored repeats in "
        f"{time.perf_counter() - t0:.1f}s, score gap so far {gap.max:.3e}")

    # ---- one search_memories_batch of 64 on the last tenant ---------------
    t = visit[-1]
    rows = stored(emb.corpus(t)[:n_rows], ml_dtypes.bfloat16)
    live = ~is_dup(np.arange(n_rows))
    batch = probe_rows(seed + 1, t, n_rows, sz.batch)
    t0 = time.perf_counter()
    fleet = ms.search_memories_batch([fact_text(t, j) for j in batch], limit=K)
    check(len(fleet) == len(batch), "search_memories_batch dropped queries")
    for j, nodes in zip(batch, fleet):
        got = hits_to_rows(nodes, t)
        check(bool(got) and got[0] == j, f"batch: top-1 of {t}.{j} is {got[:1]}")
        gap.compare(f"batch {t}.{j}", got, None, rows, live, rows[j], K)
    say(f"  search_memories_batch({len(batch)}) in "
        f"{time.perf_counter() - t0:.2f}s")

    # ---- three chat turns (donated boost dispatch), then their ingest ----
    chat_probes = probe_rows(seed + 2, t, n_rows, 3)
    for j in chat_probes:
        reply = ms.chat(fact_text(t, j))
        check(reply == "Noted.", f"chat returned {reply!r}")
        context = "\n".join(m["content"] for m in llm.last_chat
                            if m["role"] == "system")
        check(f"- {fact_text(t, j)}" in context,
              f"chat turn for fact {t}.{j} did not retrieve it")
    ms.end_conversation()
    ex = [j for j in range(n_rows, n_rows + sz.extras) if not is_dup(j)]
    check(ms.buffer.size()[0] == per_tenant + len(ex),
          f"after the chat conversation tenant {t} holds "
          f"{ms.buffer.size()[0]} nodes, expected {per_tenant + len(ex)}")
    n_all = sz.tenant_facts
    rows_all = stored(emb.corpus(t), ml_dtypes.bfloat16)
    live_all = ~is_dup(np.arange(n_all))
    got = hits_to_rows(ms.search_memories(fact_text(t, ex[0]), limit=K), t)
    check(got[0] == ex[0], f"fact {t}.{ex[0]} ingested after the chat turns "
                           f"is not its own top-1: {got}")
    gap.compare(f"post-chat {t}.{ex[0]}", got, None, rows_all, live_all,
                rows_all[ex[0]], K)

    # ---- one int8 pass: coarse int8 scan + exact rescore ------------------
    ms.index.int8_serving = True
    int8_gap = gap_f32q = 0.0
    hits = total = 0
    q_f32 = emb.corpus(t) / np.linalg.norm(emb.corpus(t), axis=1, keepdims=True)
    probes = probe_rows(seed + 3, t, n_rows, sz.probes_per_tenant)
    for j, res in zip(probes, scored(ms, emb, t, probes)):
        got = result_rows(ms, res)
        check(bool(got) and got[0] == j, f"int8: top-1 of {t}.{j} is {got[:1]}")
        check(hits_to_rows(ms.search_memories(fact_text(t, j), limit=K), t)
              == got, f"int8: search_memories and the scheduler disagree on "
                      f"{t}.{j}")
        ref_s, ref_i, all_s = reference_topk(rows_all, live_all, rows_all[j], K)
        hits += len(set(got) & set(int(i) for i in ref_i))
        total += K
        got_s = np.asarray(res.scores, np.float32)
        int8_gap = max(int8_gap, float(np.abs(got_s - all_s[got]).max()))
        gap_f32q = max(gap_f32q, float(np.abs(
            got_s - rows_all[got] @ q_f32[j]).max()))
    ms.index.int8_serving = False
    say(f"  int8 pass: recall@{K} {hits / total:.3f} vs the f32 reference; "
        f"rescored score gap {int8_gap:.3e} (against the reference with the "
        f"query kept in f32: {gap_f32q:.3e})")
    check(hits / total >= 0.9, f"int8 recall@{K} {hits / total:.3f} < 0.9")
    check(int8_gap <= TOL_INT8_RESCORE,
          f"int8 rescored scores differ by {int8_gap:.3e}")

    # ---- an empty tenant sees nothing -------------------------------------
    ms.switch_user("nobody")
    check(ms.search_memories(fact_text(t, batch[0]), limit=K) == [],
          "an empty tenant was served another tenant's memories")

    counters = check_counters(ms, "store")
    edge_cap1 = int(ms.index.edge_state.capacity)
    out = {
        "arena_rows": arena_rows, "dim": sz.dim, "dtype": "bfloat16",
        "tenants": len(full), "conversations": sum(done.values()),
        "facts": facts, "live_rows": len(ms.index),
        "truncated": len(full) != sz.tenants,
        "ingest_s": round(ingest_s, 1),
        "first_conversation_s": round(first_conv_s, 1),
        "first_answer_s": round(first_answer_s, 1),
        "edges": int(ms.index.stats()["edges"]),
        "edge_capacity": [edge_cap0, edge_cap1],
        "score_gap_bf16": gap.max, "rank_checks": gap.checked,
        "int8_recall": hits / total, "score_gap_int8": int8_gap,
        "score_gap_int8_f32_query": gap_f32q,
        "counters": counters, "peak_bytes_after_ingest": peak_ingest,
        "peak_bytes": device_peak_bytes(), "results": results,
    }
    return out, ms


def phase_f32_gap(seed: int, sz: Sizes) -> float:
    """Score gap of one small f32 arena (the default MemoryConfig dtype)."""
    n = sz.f32_rows
    v = tenant_corpus(seed, 900, n, sz.dim)
    idx = MemoryIndex(sz.dim, capacity=n + 64, dtype=jnp.float32)
    idx.add([f"t:{i}" for i in range(n)], v, [0.5] * n, [0.0] * n,
            ["semantic"] * n, ["default"] * n, "t")
    rows = stored(v, np.float32)
    live = np.ones(n, bool)
    gap = Gap(TOL_F32_ARENA, "f32 arena")
    probes = probe_rows(seed, 900, n, 32)
    for j, (ids, scores) in zip(probes, idx.search_batch(v[probes], "t", k=K)):
        got = [int(i.partition(":")[2]) for i in ids]
        check(got[0] == j, f"f32 arena: top-1 of row {j} is {got[:1]}")
        gap.compare(f"row {j}", got, scores, rows, live, rows[j], K)
    return gap.max


# --------------------------------------------------------------------------
# Phase: kernels
# --------------------------------------------------------------------------

def phase_kernels(ms: MemorySystem, seed: int, sz: Sizes, compiled: bool
                  ) -> dict:
    state = ms.index.state
    tid = jnp.int32(ms.index.tenant_id(tenant_name(0)))
    q = jnp.asarray(tenant_corpus(seed, 0, sz.tenant_facts, sz.dim)[
        probe_rows(seed, 0, sz.facts_per_conv, 8)])
    if compiled:
        text = S.arena_search.lower(state, q, tid, K, impl="pallas").as_text()
        check("tpu_custom_call" in text, "Pallas top-k lowered without a "
                                         "tpu_custom_call: the interpreter ran")
    xs, xr = (np.asarray(a) for a in S.arena_search(state, q, tid, K, impl="xla"))
    ps, pr = (np.asarray(a) for a in S.arena_search(state, q, tid, K,
                                                    impl="pallas"))
    topk_gap = float(np.abs(xs - ps).max())
    say(f"  pallas top-k vs xla on {state.emb.shape[0]} rows: rows equal "
        f"{np.array_equal(xr, pr)}, score gap {topk_gap:.3e}")
    check(np.array_equal(xr, pr), f"pallas rows {pr} != xla rows {xr}")
    check(topk_gap <= TOL_BF16_ARENA, f"pallas score gap {topk_gap:.3e}")

    # flash attention at the `small` LM's head shape, forward and grad
    cfg = LMConfig.small()
    B, T = 2, 256
    rng = np.random.default_rng([seed, 11])
    dt = jnp.dtype(cfg.dtype)
    qa = jnp.asarray(rng.standard_normal((B, T, cfg.heads, cfg.head_dim)), dt)
    ka = jnp.asarray(rng.standard_normal((B, T, cfg.kv_heads, cfg.head_dim)), dt)
    va = jnp.asarray(rng.standard_normal((B, T, cfg.kv_heads, cfg.head_dim)), dt)
    interpret = False if compiled else None
    causal = jnp.tril(jnp.ones((T, T), bool))[None]

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, 128, 128, interpret)

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_).astype(jnp.float32) ** 2)

    def ref(q_, k_, v_):
        return reference_attention(q_, k_, v_, causal)

    fwd = jax.jit(flash)
    bwd = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))
    if compiled:
        check("tpu_custom_call" in fwd.lower(qa, ka, va).as_text(),
              "flash forward lowered without a tpu_custom_call")
        check("tpu_custom_call" in bwd.lower(qa, ka, va).as_text(),
              "flash backward lowered without a tpu_custom_call")

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        check(bool(np.isfinite(a).all()), "flash attention produced non-finite "
                                          "values")
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))

    fwd_err = rel(fwd(qa, ka, va), jax.jit(ref)(qa, ka, va))
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(qa, ka, va)
    grad_err = max(rel(a, b) for a, b in zip(bwd(qa, ka, va), g_ref))
    say(f"  flash attention [B={B}, T={T}, H={cfg.heads}, Hkv={cfg.kv_heads}, "
        f"D={cfg.head_dim}] {cfg.dtype}: forward rel err {fwd_err:.3e}, grad "
        f"rel err {grad_err:.3e}")
    check(fwd_err <= TOL_FLASH and grad_err <= TOL_FLASH,
          f"flash attention off its reference: fwd {fwd_err:.3e}, grad "
          f"{grad_err:.3e} (tolerance {TOL_FLASH})")
    return {"topk_rows_equal": True, "topk_score_gap": topk_gap,
            "flash_fwd_rel_err": fwd_err, "flash_grad_rel_err": grad_err,
            "compiled": compiled, "peak_bytes": device_peak_bytes()}


# --------------------------------------------------------------------------
# Phase: encoder
# --------------------------------------------------------------------------

def phase_encoder(seed: int, sz: Sizes) -> dict:
    cfg = EncoderConfig.bge_base() if sz.full else EncoderConfig.tiny()
    enc = TextEncoder(cfg, seed=seed)
    n = sz.encoder_texts
    words = [w for t in TOPICS for w in (t, t + "s", "my " + t)]
    rng = np.random.default_rng([seed, 13])
    texts = [" ".join(rng.choice(words, size=int(rng.integers(4, 24))))
             + f" number {i}" for i in range(n)]
    texts[n // 2] = texts[0]                       # same text → same vector
    t0 = time.perf_counter()
    vecs = np.asarray(EncoderEmbedder(enc).batch_embed(texts), np.float32)
    dt = time.perf_counter() - t0
    check(vecs.shape == (n, cfg.hidden), f"encoder output shape {vecs.shape}")
    check(bool(np.isfinite(vecs).all()), "encoder produced non-finite values")
    norm_err = float(np.abs(np.linalg.norm(vecs, axis=1) - 1.0).max())
    check(norm_err <= 1e-3, f"encoder vectors off unit norm by {norm_err:.3e}")
    same = float(np.abs(vecs[0] - vecs[n // 2]).max())
    check(same <= 1e-6, f"one text, two vectors: max diff {same:.3e}")

    # the same forward on this process's CPU device
    cpu = jax.devices("cpu")[0]
    m = sz.encoder_ref_texts
    ids = np.asarray(enc.tokenizer.batch_encode(texts[:m], cfg.max_len), np.int32)
    ref = np.asarray(jax.jit(enc.model.apply)(
        jax.device_put(enc.params, cpu), jax.device_put(ids, cpu)), np.float32)
    cpu_gap = float(np.abs(vecs[:m] - ref).max())
    say(f"  {cfg.arch} {cfg.layers}×{cfg.hidden} L={cfg.max_len} {cfg.dtype}: "
        f"{n} texts in {dt:.1f}s (compile included), unit-norm err "
        f"{norm_err:.1e}, vs CPU forward on {m} texts max abs {cpu_gap:.3e}")
    check(cpu_gap <= TOL_ENCODER, f"encoder differs from its CPU forward by "
                                  f"{cpu_gap:.3e} (tolerance {TOL_ENCODER})")
    return {"geometry": f"{cfg.arch} {cfg.layers}x{cfg.hidden} L{cfg.max_len} "
                        f"{cfg.dtype}", "texts": n, "seconds": round(dt, 1),
            "cpu_gap": cpu_gap, "norm_err": norm_err,
            "peak_bytes": device_peak_bytes()}


# --------------------------------------------------------------------------
# Phase: extract
# --------------------------------------------------------------------------

def phase_extract(work: str, seed: int, sz: Sizes) -> dict:
    cfg = LMConfig.small() if sz.full else LMConfig.tiny()
    scaffold = '{"memories": [{"content": "extracted: '
    recorded: List[str] = []

    class Recording(OnDeviceLLM):
        def completion(self, messages, response_format=None):
            out = super().completion(messages, response_format)
            recorded.append(out)
            return out

    llm = Recording(LanguageModel(cfg, seed=seed), max_new_tokens=96,
                    json_scaffold=scaffold)
    ms = MemorySystem(enable_async=False, load_from_disk=False,
                      db_dir=os.path.join(work, "extract_db"),
                      llm_provider=llm, verbose=False)
    ms.start_conversation()
    for i in range(4):
        ms.add_to_short_term(f"I am user detail {i}: I work on TPU systems "
                             f"and like hiking.", "episodic", 0.7)
    t0 = time.perf_counter()
    ms.end_conversation()
    dt = time.perf_counter() - t0
    check(len(recorded) >= 1, "the extraction call never reached the LLM")
    doc = json.loads(recorded[0])
    check(recorded[0].startswith(scaffold), "constrained decode dropped its "
                                            "scaffold")
    mems = [m for m in doc["memories"] if isinstance(m, dict)
            and len(m.get("content", "")) >= 5]
    nodes = ms.buffer.size()[0]
    say(f"  `small` LM (toy width: {cfg.layers}×{cfg.hidden}) decoded "
        f"{len(recorded[0])} bytes of valid JSON in {dt:.1f}s (compile "
        f"included); {len(mems)} candidate(s) → {nodes} node(s)")
    check(len(mems) >= 1 and nodes >= 1, "nothing was ingested from the "
                                         "on-device extraction")
    hit = ms.search_memories(mems[0]["content"], limit=1)
    check(bool(hit) and hit[0].content == mems[0]["content"],
          "the extracted fact is not retrievable")
    counters = check_counters(ms, "extract")
    ms.close()
    return {"lm": f"small (toy width) {cfg.layers}x{cfg.hidden}",
            "json_bytes": len(recorded[0]), "nodes": nodes,
            "seconds": round(dt, 1), "counters": counters,
            "peak_bytes": device_peak_bytes()}


# --------------------------------------------------------------------------
# Phase: mesh
# --------------------------------------------------------------------------

def phase_mesh(work: str, seed: int, sz: Sizes, n: int,
               single: Optional[dict], compiles: Compiles) -> dict:
    devices = jax.devices()[:n]
    mesh = make_mesh(("data",), (n,), devices=devices)
    ms, emb, _ = store_system(work, "mesh_db", seed, sz, mesh=mesh)
    arr = ms.index.state.emb
    shards = [(str(s.device), tuple(s.data.shape)) for s in arr.addressable_shards]
    say(f"  emb {tuple(arr.shape)} sharded as {arr.sharding.spec} over "
        f"{len(arr.sharding.device_set)} devices; shards {shards}")
    check(len(arr.sharding.device_set) == n,
          f"emb lives on {len(arr.sharding.device_set)} device(s), not {n}")
    check(all(shape[0] * n == arr.shape[0] for _, shape in shards),
          f"shards are not 1/{n} of the rows: {shards}")
    check(len({d for d, _ in shards}) == n, "two shards on one device")

    done, _ = ingest_tenants(ms, range(sz.mesh_tenants), sz, compiles)
    check(all(done.get(t) == sz.convs_per_tenant
              for t in range(sz.mesh_tenants)), f"mesh ingest incomplete: {done}")
    n_rows = sz.convs_per_tenant * sz.facts_per_conv
    gap = Gap(TOL_BF16_ARENA, "mesh store")
    compared = 0
    for t in range(min(2, sz.mesh_tenants)):
        ms.switch_user(tenant_name(t))
        probes = probe_rows(seed, t, n_rows, sz.probes_per_tenant)
        got = search_tenant(ms, emb, t, n_rows, probes, gap, f"mesh{n}")
        for j, r in got.items():
            if single is not None and f"{t}.{j}" in single:
                check(single[f"{t}.{j}"] == r,
                      f"mesh ids {r} != single-chip ids {single[f'{t}.{j}']} "
                      f"for fact {t}.{j}")
                compared += 1
    say(f"  mesh top-{K} ids equal the single-chip phase's on {compared} "
        f"probes; score gap {gap.max:.3e}")
    check(single is None or compared > 0, "no probe shared with the "
                                          "single-chip phase")
    arr = ms.index.state.emb
    check(len(arr.sharding.device_set) == n, "emb left the mesh after ingest")
    counters = check_counters(ms, "mesh")
    ms.close()

    # ---- ReplicaPlacement: 2 groups × n/2 chips ---------------------------
    pl = ReplicaPlacement(2, sz.dim, capacity=sz.replica_capacity,
                          dtype=jnp.bfloat16, devices=devices,
                          journal_path=os.path.join(work, "replica.wal"))
    per = sz.replica_facts // 4
    reqs, corpora = [], []
    for t in range(4):
        v = tenant_corpus(seed, 500 + t, per, sz.dim)
        pl.ingest([f"r{t}:{i}" for i in range(per)], v, tenant_name(t))
        reqs += [RetrievalRequest(query=v[i], tenant=tenant_name(t), k=K)
                 for i in range(4)]
        corpora.append(stored(v, ml_dtypes.bfloat16))
    routed = pl.serve(reqs)
    rgap = Gap(TOL_BF16_ARENA, "replica")
    live = ~is_dup(np.arange(per))
    for n_req, res in enumerate(routed):
        t, i = divmod(n_req, 4)
        check(all(q.startswith(f"r{t}:") for q in res.ids),
              f"replica served tenant {t} the ids {res.ids}")
        rgap.compare(f"r{t}:{i}", [int(q.partition(":")[2]) for q in res.ids],
                     res.scores, corpora[t], live, corpora[t][i], K)
    for g, grp in enumerate(pl.groups):
        own = grp.serve_requests(reqs)
        for a, b in zip(routed, own):
            check(a.ids == b.ids,
                  f"replica group {g} ids {b.ids} != routed ids {a.ids}")
            np.testing.assert_array_max_ulp(
                np.asarray(a.scores, np.float32),
                np.asarray(b.scores, np.float32), maxulp=2)
    say(f"  ReplicaPlacement 2 groups × {n // 2} chips: one serve() of "
        f"{len(reqs)} requests equals each group's own serve_requests() and "
        f"the reference (score gap {rgap.max:.3e})")
    return {"devices": n, "shards": shards, "compared_probes": compared,
            "score_gap_bf16": gap.max, "counters": counters,
            "replica_groups": 2, "peak_bytes": device_peak_bytes()}


# --------------------------------------------------------------------------

def result_line(dev, count: int) -> str:
    """The last stdout line of a passing run: exactly these keys, the device
    as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}})


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=None,
                    help="row-shard the mesh phase over this many chips "
                         "(default: 4 when the machine shows ≥ 4 devices)")
    ap.add_argument("--cpu-debug", action="store_true",
                    help="tiny sizes on any backend; exits 3, no result line")
    ap.add_argument("--only", default="",
                    help="comma-separated phases to run; exits 3, no result")
    args = ap.parse_args(argv)
    only = tuple(p for p in args.only.split(",") if p)
    check(all(p in PHASES for p in only), f"--only takes {PHASES}")
    debug = args.cpu_debug or bool(only)
    wanted = only or PHASES

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_debug:
        raise SystemExit(
            f"chip_smoke: needs a TPU; jax found platform={dev.platform!r} "
            f"kind={dev.device_kind!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). No result.")
    cache_dir = place_compile_cache()
    n_dev = len(jax.devices())
    device = json.loads(result_line(dev, n_dev))["device"]
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    say(f"chip_smoke seed={args.seed} device={device} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"native={'built library' if native.available() else 'python fallback'} "
        f"compile_cache={cache_dir}")
    sz = Sizes.tiny() if args.cpu_debug else Sizes()
    compiled = dev.platform == "tpu"
    mesh_n = args.mesh if args.mesh is not None else (4 if n_dev >= 4 else 0)

    detail: dict = {"seed": args.seed, "device": device,
                    "versions": {"jax": jax.__version__,
                                 "jaxlib": jaxlib.__version__,
                                 "libtpu": libtpu_version},
                    "native": native.available(), "compile_cache": cache_dir}
    seconds: Dict[str, float] = {}
    compiles = Compiles()
    t_start = time.perf_counter()
    single_results = None
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        ms = None
        if "store" in wanted or "kernels" in wanted:
            say("[store]")
            t0 = time.perf_counter()
            detail["store"], ms = phase_store(work, args.seed, sz, t_start,
                                              compiles)
            detail["store"]["score_gap_f32_arena"] = phase_f32_gap(args.seed, sz)
            say(f"  score gap vs NumPy f32: bf16 store "
                f"{detail['store']['score_gap_bf16']:.3e}, {sz.f32_rows}-row "
                f"f32 arena {detail['store']['score_gap_f32_arena']:.3e}")
            single_results = detail["store"].pop("results")
            seconds["store"] = round(time.perf_counter() - t0, 1)
        if "kernels" in wanted:
            say("[kernels]")
            t0 = time.perf_counter()
            detail["kernels"] = phase_kernels(ms, args.seed, sz, compiled)
            seconds["kernels"] = round(time.perf_counter() - t0, 1)
        if ms is not None:
            ms.close()
            del ms
        if "encoder" in wanted:
            say("[encoder]")
            t0 = time.perf_counter()
            detail["encoder"] = phase_encoder(args.seed, sz)
            seconds["encoder"] = round(time.perf_counter() - t0, 1)
        if "extract" in wanted:
            say("[extract]")
            t0 = time.perf_counter()
            detail["extract"] = phase_extract(work, args.seed, sz)
            seconds["extract"] = round(time.perf_counter() - t0, 1)
        if "mesh" in wanted:
            if mesh_n >= 2 and n_dev >= mesh_n:
                say(f"[mesh {mesh_n}]")
                t0 = time.perf_counter()
                detail["mesh"] = phase_mesh(work, args.seed, sz, mesh_n,
                                            single_results, compiles)
                seconds["mesh"] = round(time.perf_counter() - t0, 1)
                mesh_line = f"passed on {mesh_n} devices"
            else:
                check(args.mesh is None or args.mesh == 0,
                      f"--mesh {args.mesh} asked for, {n_dev} device(s) found")
                mesh_line = f"not run, {n_dev} device(s)"
                say(f"mesh: {mesh_line}")
            detail["mesh_summary"] = mesh_line
    seconds["total"] = round(time.perf_counter() - t_start, 1)
    detail["phases"] = seconds
    detail["compiles"] = {"count": compiles.count,
                          "seconds": round(compiles.seconds, 1)}
    say(f"backend compilations: {compiles.count}, {compiles.seconds:.1f}s of "
        f"the {seconds['total']}s")

    if debug:
        say(f"debug run ({'tiny sizes' if args.cpu_debug else 'phase subset'}) "
            f"finished in {seconds['total']}s: phases {seconds}. Not a result.")
        return 3
    st = detail["store"]
    detail["summary"] = {
        "phases": seconds, "rows": st["live_rows"],
        "arena_rows": st["arena_rows"],
        "score_gap": {"bf16": st["score_gap_bf16"],
                      "f32_arena": st["score_gap_f32_arena"]},
        "mesh": detail["mesh_summary"], "claim": None}
    detail["claim"] = None
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(detail, f, indent=1)
    say("summary: " + json.dumps(detail["summary"]))
    print(result_line(dev, n_dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
