"""Host-side batching helpers shared by the index classes and the encoder.

Static shapes are the XLA contract: every distinct batch size compiles a new
kernel specialization, so hosts bucket batch dims to powers of two. The
decode loop turns kernel output (scores + arena rows with NEG_INF sentinels)
back into host id lists — one implementation, used by both the single-chip
and pod-sharded indexes.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@functools.cache
def _packer(int_flags: Tuple[bool, ...]):
    # Lazy so importing this module never initializes a JAX backend.
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pack(*arrs):
        return jnp.stack([
            a.astype(jnp.int32) if flag else
            jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)
            for a, flag in zip(arrs, int_flags)])
    return pack


def fetch_packed(*arrays) -> Tuple[np.ndarray, ...]:
    """Read N same-shape f32/int device arrays back to host in ONE transfer.

    Every device→host readback blocks the host on a full dispatch round
    trip, so the common kernel-output pattern ``np.asarray(scores);
    np.asarray(rows)`` pays it twice per search/link/evict call. Float
    arrays are bitcast (not cast) to int32 on device, stacked with the int
    arrays, and the single [N, ...] array is fetched; the bitcast is undone
    with a zero-copy ``.view`` on host. The carrier is INT32, never f32: a
    small row id is a denormal bit pattern as a float, and a TPU flushes
    denormals to zero when it moves floats (on a v5e every row id came back
    0). The stack is an extra on-device op, but dispatch is async — only
    readbacks block."""
    int_flags = tuple(np.issubdtype(np.dtype(a.dtype), np.integer)
                      for a in arrays)
    packed = np.asarray(_packer(int_flags)(*arrays))
    return tuple(packed[i] if flag else packed[i].view(np.float32)
                 for i, flag in enumerate(int_flags))


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1 — a single item needs no
    padding; mapping 1 → 2 would double every single-query dispatch)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_to_pow2(arr: np.ndarray) -> np.ndarray:
    """Pad axis 0 with zero rows up to the power-of-two bucket."""
    n = arr.shape[0]
    bucket = next_pow2(n)
    if bucket == n:
        return arr
    pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad])


def bucket_size(n: int, granularity: int) -> int:
    """Query-batch bucket of the ragged serving path (ISSUE 7): LINEAR
    multiples of ``granularity`` instead of powers of two once batches
    pass the granularity — pow2 wastes up to ~50% of every dispatch's
    padded slots (a 33-request batch pays 64 kernel slots), linear
    buckets waste at most ``granularity - 1``. Below the granularity the
    pow2 ladder is kept (1, 2, 4): a lone request must keep costing a
    1-slot dispatch, not ``granularity`` slots. Distinct jit
    specializations stay bounded either way (log2(g) small buckets +
    max_batch/g linear ones)."""
    g = max(1, int(granularity))
    n = max(1, int(n))
    if n <= g:
        return next_pow2(n)
    return -(-n // g) * g


def pad_to_bucket(arr: np.ndarray, granularity: int) -> np.ndarray:
    """Pad axis 0 with zero rows up to the linear ``granularity`` bucket
    (the ragged-serving replacement for :func:`pad_to_pow2`)."""
    n = arr.shape[0]
    bucket = bucket_size(n, granularity)
    if bucket == n:
        return arr
    pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad])


# Columns of the request carrier after its ``dim`` query columns (ISSUE 37):
# one int32 column a per-request field, then one a scalar (f32 bits, read
# from row 0 — a column each, so a one-row bucket carries them too).
REQUEST_FIELDS = ("valid", "tenant", "gate_on", "boost_on", "k", "cap",
                  "nprobe")
REQUEST_SCALARS = ("super_gate", "now", "acc_boost", "nbr_boost")
REQUEST_COLS = len(REQUEST_FIELDS) + len(REQUEST_SCALARS)


class RequestCarrier:
    """Host half of ``core.state._unpack_requests``: everything one serving
    dispatch sends the device about its requests, as ONE
    ``[bucket, dim + REQUEST_COLS]`` int32 array (``buf``) and so one
    host→device transfer. ``q`` is the first ``dim`` columns viewed as
    float32 — the request loop writes each query's bits straight into the
    carrier —, ``fill`` writes per-request columns (pad rows keep valid / k /
    cap / nprobe 0 and tenant −1) and the dispatch's scalars by name.
    int32 for the reason the packed readback's carrier is: every f32 pattern
    survives an integer lane, while not every integer pattern (a tenant of
    −1 is a NaN, a small k a denormal) is promised to survive a float one."""

    def __init__(self, n: int, dim: int, granularity: int):
        self.n, self.dim = int(n), int(dim)
        self.buf = np.zeros((bucket_size(n, granularity),
                             self.dim + REQUEST_COLS), np.int32)
        self.buf[:, self.dim + REQUEST_FIELDS.index("tenant")] = -1
        self.q = self.buf[:, :self.dim].view(np.float32)

    def fill(self, **cols) -> None:
        """By name: a ``REQUEST_FIELDS`` column from the ``n`` live
        requests' values (bool or integer), a ``REQUEST_SCALARS`` scalar
        from a float."""
        tail = self.buf[0, self.dim + len(REQUEST_FIELDS):].view(np.float32)
        for name, value in cols.items():
            if name in REQUEST_SCALARS:
                tail[REQUEST_SCALARS.index(name)] = value
            else:
                self.buf[:self.n,
                         self.dim + REQUEST_FIELDS.index(name)] = value

    @classmethod
    def of(cls, q, *, granularity: int = 1, **cols) -> "RequestCarrier":
        """A carrier from whole columns — how a test or a script states a
        batch; fields left out stay at their pad values (scalars 0)."""
        q = np.asarray(q, np.float32)
        car = cls(q.shape[0], q.shape[1], granularity)
        car.q[:car.n] = q
        car.fill(**cols)
        return car


class LRUKernelCache:
    """Tiny LRU map bounding a compiled-kernel cache (ISSUE 7 satellite):
    before ragged serving, per-(mode × k-bucket) keys grew without bound
    under mixed-k traffic and ``kernel.cache_entries`` could only watch;
    now the cap evicts the least-recently-served program (dropping a jit
    wrapper frees its compiled executables once no caller holds it).
    Not thread-safe by itself — callers serialize through their own
    locks (the serving dispatch already does)."""

    def __init__(self, max_entries: int = 8):
        from collections import OrderedDict
        self.max_entries = max(1, int(max_entries))
        self._d = OrderedDict()
        self.evictions = 0

    def get(self, key):
        v = self._d.get(key)
        if v is not None:
            self._d.move_to_end(key)
        return v

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.max_entries:
            self._d.popitem(last=False)
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d

    def keys(self):
        return list(self._d.keys())


def decode_topk(scores: np.ndarray, rows: np.ndarray,
                row_to_id: Dict[int, str], neg_inf: float,
                limit: Optional[int] = None,
                lengths: Optional[Sequence[int]] = None
                ) -> List[Tuple[List[str], List[float]]]:
    """Per query: drop NEG_INF sentinels, rows without a live id mapping,
    and repeated rows (a slot reused after delete can appear in both a
    stale IVF member slot and the fresh residual — scores are sorted
    descending, so keeping the first occurrence keeps the best); return
    (ids, scores) pairs. ``limit`` caps each list AFTER dedup — the IVF
    serving path over-fetches k + slack so duplicates can't shrink the
    result below k, then trims back here. ``lengths`` is the RAGGED
    decode bound (ISSUE 7): the packed readback's per-query live-length
    counter, so a k=4 request in a K-ceiling batch scans 4 columns of its
    row instead of all K (live entries are a sorted prefix — everything
    past a query's own k was masked to NEG_INF on device)."""
    out: List[Tuple[List[str], List[float]]] = []
    for qi in range(scores.shape[0]):
        ids: List[str] = []
        sc: List[float] = []
        seen = set()
        n_cols = scores.shape[1]
        if lengths is not None:
            n_cols = min(n_cols, max(0, int(lengths[qi])))
        for s, r in zip(scores[qi, :n_cols], rows[qi, :n_cols]):
            if limit is not None and len(ids) >= limit:
                break
            if s <= neg_inf / 2:
                continue
            r = int(r)
            if r in seen:
                continue
            seen.add(r)
            node_id = row_to_id.get(r)
            if node_id is not None:
                ids.append(node_id)
                sc.append(float(s))
        out.append((ids, sc))
    return out


def empty_results(n: int) -> List[Tuple[List[str], List[float]]]:
    """n independent ([], []) pairs — NOT `[([], [])] * n`, which aliases
    the same two lists across every entry."""
    return [([], []) for _ in range(n)]


# Column names of the device-counter tail every fused serving readback
# carries (core.state.RETRIEVAL_TAIL int32 columns after the fast bit):
# live top-k hits, in-kernel dedup drops, access-boost rows scattered,
# neighbor-boost rows scattered, semantic-cache verdict (0 = miss,
# 1 + ring slot on a hit).
RETRIEVAL_COUNTERS = ("live", "dedup_dropped", "acc_boost_rows",
                      "nbr_boost_rows", "semantic")


def unpack_retrieval(host: np.ndarray, k: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray, np.ndarray, np.ndarray]:
    """Host half of ``core.state._pack_retrieval``: split the ONE
    [Q, 3 + 2k + 5] int32 packed readback into (gate_scores, gate_rows,
    ann_scores, ann_rows, fast, counters). Score columns were bitcast
    (not cast) on device, so the f32 view reverses them losslessly;
    ``counters`` is the [Q, 5] int32 device-counter tail (column names in
    :data:`RETRIEVAL_COUNTERS` — ISSUE 6 observability riding the existing
    transfer). Shared by the single-chip and the pod-sharded fused serving
    decoders."""
    ann_s = np.ascontiguousarray(host[:, 2:2 + k]).view(np.float32)
    ann_r = host[:, 2 + k:2 + 2 * k]
    gate_s = np.ascontiguousarray(host[:, 0:1]).view(np.float32)[:, 0]
    gate_r = host[:, 1]
    fast = host[:, 2 + 2 * k] != 0
    counters = host[:, 3 + 2 * k:3 + 2 * k + len(RETRIEVAL_COUNTERS)]
    return gate_s, gate_r, ann_s, ann_r, fast, counters


class FlushPolicy:
    """Time/size flush decision of ``IngestCoalescer``.

    A batch flushes when it holds ``max_items`` entries OR when its oldest
    entry has waited ``max_wait_s`` — so bursty load coalesces into dense
    device batches while trickle load is never held hostage to a size
    threshold it will not reach. ``max_wait_s <= 0`` means "flush on every
    check" (the eager pre-policy behavior)."""

    def __init__(self, max_items: int, max_wait_s: float):
        self.max_items = max(1, int(max_items))
        self.max_wait_s = float(max_wait_s)
        self._oldest: Optional[float] = None

    def note_add(self, now: float) -> None:
        if self._oldest is None:
            self._oldest = now

    def should_flush(self, n_items: int, now: float) -> bool:
        if n_items <= 0:
            return False
        if self.max_wait_s <= 0 or n_items >= self.max_items:
            return True
        oldest = self._oldest
        return oldest is not None and (now - oldest) >= self.max_wait_s

    @property
    def oldest(self) -> Optional[float]:
        """First-add time of the current buffer (None when empty) — the
        coalesce-wait telemetry reads it at drain time."""
        return self._oldest

    def reset(self) -> None:
        self._oldest = None


class IngestCoalescer:
    """Cross-conversation ingest batcher for the fused single-dispatch
    pipeline.

    Consolidation extracts a fact list per drained conversation; this
    buffer coalesces the lists of EVERY buffered conversation into padded
    mega-batches so the fused ingest kernel (``state.ingest_fused``)
    dispatches once per mega-batch instead of once per conversation.
    Conversations are kept whole when they fit under ``max_facts`` — the
    cap bounds the padded jit bucket (and the [B, capacity] link-scan
    tile) — and only oversized single conversations are split.

    ``drain`` returns ``(facts, n_conversations)`` mega-batches and empties
    the buffer; nothing is ever withheld across a drain, so durability
    bookkeeping (WAL, in-flight batches) stays with the caller.

    With ``max_wait_s > 0`` the coalescer also carries a time/size flush
    policy (``FlushPolicy``): ``should_flush`` stays False while the buffer
    is small AND young, so a steady trickle of single conversations
    accumulates into one dense fused dispatch instead of draining one
    conversation at a time (ROADMAP open item 3). The caller decides when
    to consult the policy and remains responsible for durability of
    deferred facts (the source turns stay in the WAL until their facts are
    ingested). ``max_wait_s = 0`` (default) preserves the eager behavior:
    every check says flush.
    """

    def __init__(self, max_facts: int = 8192, max_wait_s: float = 0.0):
        self.max_facts = max(1, int(max_facts))
        self.policy = FlushPolicy(self.max_facts, max_wait_s)
        self._convs: List[List[dict]] = []

    def add_conversation(self, facts: Sequence[dict],
                         now: Optional[float] = None) -> None:
        if facts:
            import time as _time
            self._convs.append(list(facts))
            self.policy.note_add(now if now is not None else _time.time())

    def should_flush(self, now: Optional[float] = None) -> bool:
        import time as _time
        return self.policy.should_flush(
            len(self), now if now is not None else _time.time())

    def oldest_age_s(self, now: Optional[float] = None) -> float:
        """Age of the oldest buffered conversation (0.0 when empty) — the
        per-mega-batch coalesce-wait the ingest telemetry records at drain
        time (ISSUE 9 satellite: the write-path twin of the serving
        queue-wait span)."""
        import time as _time
        oldest = self.policy.oldest
        if oldest is None:
            return 0.0
        return max(0.0, (now if now is not None else _time.time()) - oldest)

    def __len__(self) -> int:
        return sum(len(c) for c in self._convs)

    @property
    def pending_conversations(self) -> int:
        return len(self._convs)

    def requeue(self, batches: Sequence[Tuple[Sequence[dict], int]],
                now: Optional[float] = None) -> None:
        """Put drained-but-not-ingested mega-batches BACK at the front of
        the buffer (ISSUE 10): an ingest dispatch failure must not lose
        the facts the drain already popped — they retry on the next
        flush, ahead of anything buffered since, and the durable ingest
        journal keeps them crash-safe meanwhile."""
        if not batches:
            return
        import time as _time
        self._convs = [list(facts) for facts, _ in batches
                       if facts] + self._convs
        if self._convs:
            self.policy.note_add(now if now is not None else _time.time())

    def drain(self) -> List[Tuple[List[dict], int]]:
        batches: List[Tuple[List[dict], int]] = []
        batch: List[dict] = []
        n_convs = 0
        convs, self._convs = self._convs, []
        self.policy.reset()
        for conv in convs:
            while len(conv) > self.max_facts:          # oversized: split
                if batch:
                    batches.append((batch, n_convs))
                    batch, n_convs = [], 0
                batches.append((conv[:self.max_facts], 1))
                conv = conv[self.max_facts:]
            if batch and len(batch) + len(conv) > self.max_facts:
                batches.append((batch, n_convs))
                batch, n_convs = [], 0
            if conv:
                batch = batch + conv
                n_convs += 1
        if batch:
            batches.append((batch, n_convs))
        return batches
