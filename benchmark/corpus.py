"""Seeded data of the benchmark: every row, query, text and payload is a pure
function of ``--seed``. Nothing here imports the program.

Two generators share one geometry (``chip_smoke.py``'s clustered corpus, per
tenant: groups of ``GROUP`` facts at cosine ~0.88, above the 0.5 link gate and
below the 0.95 dedup gate; a near-duplicate fact sits at cosine ~0.97 of its
predecessor):

- ``block_rows`` makes arena rows ON THE DEVICE, a block at a time, from an
  integer hash of (seed, tenant, fact, column). The same compiled call on the
  same device returns the same bits, so set-up fills the arena with it and the
  reference regenerates a tenant's rows with it after the window, without
  reading anything the program holds.
- ``tenant_corpus`` (NumPy, a copy of ``chip_smoke.py``'s) makes the vectors
  of the facts that go through the conversation API, where the embedding
  provider runs on the host.
"""

from __future__ import annotations

import functools
import hashlib
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

GROUP = 4
TOPICS = ("work", "hobbies", "family", "travel", "health", "food",
          "sports", "music", "books", "tech", "home", "finance")
TOPIC_W, GROUP_W, NOISE_W = 0.5, float(np.sqrt(0.63)), float(np.sqrt(0.12))
DUP_NOISE_W = 0.25          # v_dup = unit(v_prev + 0.25 * noise): cosine ~0.97
QUERY_NOISE_W = 0.12        # a query is a stored fact nudged: cosine ~0.99


def seed_words(seed: int) -> np.ndarray:
    """``--seed`` may pass 2**31: both 32-bit halves enter the hash."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be a whole number >= 0")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def tenant_starts(rows: int, tenants: int) -> np.ndarray:
    """[tenants + 1] first row of each tenant, tenant-major: tenant t owns
    rows ``[t*rows//tenants, (t+1)*rows//tenants)`` (sizes differ by one)."""
    return (np.arange(tenants + 1, dtype=np.int64) * rows // tenants
            ).astype(np.int32)


# --------------------------------------------------------------------------
# Device generator: integer hash -> uniform[-1, 1) components.
# --------------------------------------------------------------------------

def _mix(x):
    """murmur3's 32-bit finalizer; wraps the same on every backend."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _row_key(seed2, kind: int, tenant, ident):
    h = _mix(seed2[0] ^ jnp.uint32(0x9E3779B9 * kind & 0xFFFFFFFF))
    h = _mix(h ^ seed2[1])
    h = _mix(h + tenant.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    return _mix(h ^ (ident.astype(jnp.uint32) * jnp.uint32(0x165667B1)))


def _uniform(key, dim: int):
    """[n] uint32 keys -> [n, dim] f32 in [-1, 1), exact in f32."""
    col = jnp.arange(dim, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1)
    bits = _mix(_mix(key[:, None] + col[None, :]) ^ key[:, None])
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0


def _unit(v):
    return v * jax.lax.rsqrt(jnp.sum(v * v, axis=-1, keepdims=True))


def _fact_vectors(seed2, tenant, j, n, dim: int):
    """[m, dim] f32 unit vectors of facts ``j`` of tenants ``tenant`` whose
    corpora hold ``n`` facts."""
    n_groups = jnp.maximum(n // GROUP, 1)
    g = j % n_groups
    v = (TOPIC_W * _uniform(_row_key(seed2, 1, tenant, g % len(TOPICS)), dim)
         + GROUP_W * _uniform(_row_key(seed2, 2, tenant, g), dim)
         + NOISE_W * _uniform(_row_key(seed2, 3, tenant, j), dim))
    return _unit(v)


@functools.partial(jax.jit, static_argnames=("block", "dim", "dtype"))
def block_rows(seed2, starts, tenant0, row0, *, block: int, dim: int,
               dtype: str = "bfloat16"):
    """Rows ``[row0, row0 + block)`` of a tenant-major arena whose tenant
    boundaries are ``starts``: (emb [block, dim] in the served dtype,
    tenant [block] i32 counted from ``tenant0``, fact [block] i32)."""
    rows = row0 + jnp.arange(block, dtype=jnp.int32)
    t = jnp.clip(jnp.searchsorted(starts, rows, side="right") - 1,
                 0, starts.shape[0] - 2).astype(jnp.int32)
    j = rows - starts[t]
    n = starts[t + 1] - starts[t]
    v = _fact_vectors(seed2, t + tenant0, j, n, dim)
    return v.astype(jnp.dtype(dtype)), t + tenant0, j


@functools.partial(jax.jit, static_argnames=("dim",))
def query_vectors(seed2, tenant, j, n, salt, *, dim: int):
    """[m, dim] f32 unit queries: fact ``j`` of ``tenant`` nudged by noise
    that ``salt`` (the request's number) makes distinct."""
    v = _fact_vectors(seed2, tenant, j, n, dim)
    noise = _uniform(_row_key(seed2, 4, tenant, salt), dim)
    return _unit(v + QUERY_NOISE_W * _unit(noise))


# --------------------------------------------------------------------------
# The graph a configuration names: data, like the rows, made from them.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nearest",))
def nearest_facts(rows, gate, *, nearest: int):
    """Each fact's ``nearest`` most similar OTHER facts of its tenant, best
    first: (fact [n, nearest] i32, -1 where the cosine is not above ``gate``;
    cosine [n, nearest] f32) of one tenant's stored rows ``[n, dim]`` f32.
    The same compiled call on the same device returns the same bits, so
    set-up installs these edges and the reference makes them again."""
    n = rows.shape[0]
    cos = jnp.matmul(rows, rows.T, precision=jax.lax.Precision.HIGHEST)
    cos = jnp.where(jnp.eye(n, dtype=bool), -jnp.inf, cos)
    best, fact = jax.lax.top_k(cos, min(nearest, n))
    return jnp.where(best > gate, fact, -1).astype(jnp.int32), best


def tenant_edges(rows: np.ndarray, graph: dict) -> List[Tuple[int, int, float]]:
    """(fact, fact, weight) edges of one tenant whose stored rows are
    ``rows`` [n, dim] f32, each ordered pair once, as the ingest path links
    a conversation's facts:
    the chain j -> j + 1 at ``chain_weight``, and from each fact to its
    ``nearest`` most similar facts above ``gate`` at ``weight_scale`` x
    cosine. The configuration's ``graph`` group names the four."""
    n = rows.shape[0]
    edges = {(j, j + 1): float(graph["chain_weight"]) for j in range(n - 1)}
    fact, cos = nearest_facts(jnp.asarray(rows), jnp.float32(graph["gate"]),
                              nearest=int(graph["nearest"]))
    fact, cos = np.asarray(fact), np.asarray(cos)
    scale = float(graph["weight_scale"])
    for j, r in zip(*np.nonzero(fact >= 0)):
        # a pair the chain already joins keeps the chain's edge
        edges.setdefault((int(j), int(fact[j, r])), scale * float(cos[j, r]))
    return [(a, b, w) for (a, b), w in edges.items()]


def neighbour_lists(n: int, edges: Sequence[Tuple[int, int, float]]
                    ) -> List[np.ndarray]:
    """[n] arrays: the facts joined to each fact by an edge in either
    direction, one entry an edge key (a pair linked both ways is listed
    twice, as the program's adjacency lists it)."""
    out: List[List[int]] = [[] for _ in range(n)]
    for a, b, _ in edges:
        out[a].append(b)
        out[b].append(a)
    return [np.asarray(x, np.int64) for x in out]


# --------------------------------------------------------------------------
# Host generator and stand-in providers (conversation API path).
# --------------------------------------------------------------------------

def _unit_rows(x: np.ndarray) -> np.ndarray:
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def is_dup(j, dup_every: int) -> np.ndarray:
    return (np.asarray(j) % dup_every) == dup_every - 1


def tenant_corpus(seed: int, tenant: int, n: int, dim: int,
                  dup_every: int) -> np.ndarray:
    """[n, dim] f32 unit vectors of one tenant's facts; every
    ``dup_every``-th is a near-duplicate of its predecessor."""
    rng = np.random.default_rng([int(seed), int(tenant)])
    n_groups = max(1, n // GROUP)
    topics = _unit_rows(rng.standard_normal((len(TOPICS), dim)))
    groups = _unit_rows(rng.standard_normal((n_groups, dim)))
    noise = _unit_rows(rng.standard_normal((n, dim)))
    dup_noise = _unit_rows(rng.standard_normal((n, dim)))
    g = np.arange(n) % n_groups
    v = _unit_rows(TOPIC_W * topics[g % len(TOPICS)] + GROUP_W * groups[g]
                   + NOISE_W * noise)
    d = np.nonzero(is_dup(np.arange(n), dup_every))[0]
    d = d[d > 0]
    v[d] = _unit_rows(v[d - 1] + DUP_NOISE_W * dup_noise[d])
    return v


def tenant_name(t: int) -> str:
    return f"t{int(t):05d}"


def fact_text(tenant: int, j: int) -> str:
    return f"fact {tenant}.{j}: user detail number {j} of tenant {tenant}"


_FACT_RE = re.compile(r"fact (\d+)\.(\d+):")
_TRANSCRIPT_RE = re.compile(r"transcript of conversation (\d+)\.(\d+)")


def fact_of(text: str) -> Optional[Tuple[int, int]]:
    m = _FACT_RE.match(text)
    return (int(m.group(1)), int(m.group(2))) if m else None


def transcript_text(tenant: int, conv: int) -> str:
    return f"transcript of conversation {tenant}.{conv}"


class SeededEmbedder:
    """EmbeddingProvider stand-in: a fact text maps to its corpus vector,
    anything else to a vector drawn from a hash of (seed, text).
    ``corpus_size(tenant)`` is how many facts that tenant's corpus holds."""

    def __init__(self, seed: int, dim: int, dup_every: int, corpus_size):
        self.seed, self.dim, self.dup_every = int(seed), dim, dup_every
        self.corpus_size = corpus_size
        self._corpora: Dict[int, np.ndarray] = {}

    def corpus(self, tenant: int) -> np.ndarray:
        if tenant not in self._corpora:
            self._corpora[tenant] = tenant_corpus(
                self.seed, tenant, self.corpus_size(tenant), self.dim,
                self.dup_every)
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


class SeededLLM:
    """LLMProvider stand-in: the extraction prompt of tenant T's
    conversation gets that conversation's fact payload — facts
    ``first .. first + facts`` of a corpus of ``size``, as
    ``shape_of(T) -> (first, facts, size)`` says; anything else extracts
    nothing."""

    def __init__(self, shape_of):
        self.shape_of = shape_of

    def payload(self, tenant: int) -> str:
        first, facts, size = self.shape_of(tenant)
        n_groups = max(1, size // GROUP)
        return json.dumps({"memories": [
            {"content": fact_text(tenant, j), "type": "semantic",
             "salience": 0.6,
             "topic": TOPICS[(j % n_groups) % len(TOPICS)]}
            for j in range(first, first + facts)]})

    def completion(self, messages, response_format=None) -> str:
        if response_format and response_format.get("type") == "json_object":
            user = next((m["content"] for m in reversed(messages)
                         if m["role"] == "user"), "")
            m = _TRANSCRIPT_RE.search(user)
            if m:
                return self.payload(int(m.group(1)))
            return json.dumps({"memories": []})
        return "Noted."

    def completion_stream(self, messages, response_format=None):
        yield self.completion(messages, response_format)
