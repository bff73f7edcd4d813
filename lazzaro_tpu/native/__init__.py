"""Native host runtime: SIMD masked top-k, blake2b hash tokenization, WAL.

High-level, numpy-facing API over ``csrc/lazzaro_native.cc`` (built lazily by
``build.py``). Every entry point has a pure-Python fallback so the framework
runs unchanged on hosts without a C++ toolchain:

- ``masked_topk(emb, alive, query, k)``   — host cosine top-k (multithreaded
  C++, else vectorized numpy). Device-side search lives in ``core.state`` /
  ``ops.topk``; this backs store-only consumers (ArrowStore.search_nodes,
  reference vector_store.py:132-140).
- ``encode_batch(texts, vocab, max_len)`` — HashTokenizer-compatible batch
  encoding (bit-identical for ASCII; non-ASCII rows route through Python).
- ``WriteAheadLog``                        — CRC-framed durable journal with
  torn-tail recovery; used by MemorySystem to make short-term turns survive a
  crash (the reference persists only at conversation end,
  memory_system.py:648, and loses in-flight turns).
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from lazzaro_tpu.native.build import build, load, so_path  # noqa: F401
from lazzaro_tpu.utils.telemetry import default_registry, file_op


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# masked top-k
# ---------------------------------------------------------------------------


def _topk_numpy(emb: np.ndarray, alive: Optional[np.ndarray],
                query: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    n = emb.shape[0]
    qn = float(np.linalg.norm(query))
    scores = np.full(n, -1e30, np.float32)
    if n and qn > 0:
        norms = np.linalg.norm(emb, axis=1)
        ok = norms > 0
        if alive is not None:
            ok &= alive.astype(bool)
        scores[ok] = emb[ok] @ query.astype(np.float32) / (norms[ok] * qn)
    k_eff = min(k, n)
    idx = np.argpartition(-scores, k_eff - 1)[:k_eff] if k_eff else np.array([], np.int64)
    order = idx[np.lexsort((idx, -scores[idx]))]
    out_scores = np.full(k, -1e30, np.float32)
    out_rows = np.full(k, -1, np.int64)
    valid = scores[order] > -1e30
    order = order[valid]
    out_scores[: len(order)] = scores[order]
    out_rows[: len(order)] = order
    return out_scores, out_rows


def masked_topk(emb: np.ndarray, alive: Optional[np.ndarray],
                query: np.ndarray, k: int,
                nthreads: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Cosine top-k over row-major [n, d] f32 with an optional alive mask.

    Returns (scores[k] f32 desc, rows[k] i64); missing slots are
    (-1e30, -1). Ties break on the lower row index, matching the C++ side.
    """
    emb = np.ascontiguousarray(emb, np.float32)
    query = np.ascontiguousarray(query, np.float32)
    n, d = emb.shape
    lib = load()
    if lib is None or n == 0:
        return _topk_numpy(emb, alive, query, k)
    alive_arr = None
    alive_ptr = ctypes.POINTER(ctypes.c_uint8)()
    if alive is not None:
        alive_arr = np.ascontiguousarray(alive, np.uint8)
        alive_ptr = alive_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    out_scores = np.empty(k, np.float32)
    out_rows = np.empty(k, np.int64)
    lib.lz_masked_topk_f32(
        emb.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), alive_ptr,
        query.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, d, k,
        nthreads,
        out_scores.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return out_scores, out_rows


# ---------------------------------------------------------------------------
# batch tokenization
# ---------------------------------------------------------------------------


def encode_batch(texts: Sequence[str], vocab_size: int,
                 max_len: int) -> np.ndarray:
    """[n, max_len] int32 token ids, HashTokenizer-compatible."""
    from lazzaro_tpu.models.tokenizer import HashTokenizer

    n = len(texts)
    out = np.empty((n, max_len), np.int32)
    lib = load()
    native_rows: List[int] = []
    python_rows: List[int] = []
    for i, t in enumerate(texts):
        (native_rows if (lib is not None and t.isascii()) else python_rows).append(i)

    if native_rows:
        blobs = [texts[i].encode("utf-8") for i in native_rows]
        offsets = np.zeros(len(blobs) + 1, np.int64)
        np.cumsum([len(b) for b in blobs], out=offsets[1:])
        concat = np.frombuffer(b"".join(blobs) or b"\0", np.uint8).copy()
        sub = np.empty((len(blobs), max_len), np.int32)
        lib.lz_encode_batch(
            concat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(blobs), vocab_size, max_len,
            sub.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        out[native_rows] = sub
    if python_rows:
        tok = HashTokenizer(vocab_size, max_len)
        for i in python_rows:
            out[i] = tok.encode(texts[i])
    return out


def blake2b8(data: bytes) -> int:
    lib = load()
    if lib is None:
        import hashlib
        return int.from_bytes(
            hashlib.blake2b(data, digest_size=8).digest(), "little")
    buf = np.frombuffer(data or b"\0", np.uint8).copy()
    return int(lib.lz_blake2b8(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(data)))


# ---------------------------------------------------------------------------
# write-ahead log
# ---------------------------------------------------------------------------


class WriteAheadLog:
    """Append-only CRC-framed journal (native when available, else Python).

    A crash mid-append leaves at most one torn tail record; ``replay``
    silently discards it. Record payloads are opaque bytes.
    """

    _MAGIC = 0x4C5A5731

    def __init__(self, path: str, fsync: bool = True, telemetry=None):
        self.path = path
        self.fsync = fsync
        # append / replay / reset are the log's file operations: one
        # ``lz.journal.io`` span and one ``store.file_ops{op}`` count each
        self.telemetry = (telemetry if telemetry is not None
                          else default_registry())
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)

    def append(self, payload: bytes) -> None:
        with file_op(self.telemetry, "journal", "wal_append"):
            self._append(payload)
        self.telemetry.bump("store.bytes_written", len(payload))

    def replay(self) -> List[bytes]:
        with file_op(self.telemetry, "journal", "wal_replay"):
            return self._replay()

    def reset(self) -> None:
        with file_op(self.telemetry, "journal", "wal_reset"):
            self._reset()

    def _append(self, payload: bytes) -> None:
        lib = load()
        if lib is not None:
            buf = np.frombuffer(payload or b"\0", np.uint8).copy()
            rc = lib.lz_wal_append(
                self.path.encode(),
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                len(payload), 1 if self.fsync else 0)
            if rc != 0:
                raise OSError(f"WAL append failed (rc={rc}) for {self.path}")
            return
        import struct
        import zlib
        rec = struct.pack("<III", self._MAGIC, len(payload),
                          zlib.crc32(payload)) + payload
        with open(self.path, "ab") as f:
            f.write(rec)
            f.flush()
            if self.fsync:
                os.fsync(f.fileno())

    def _replay(self) -> List[bytes]:
        lib = load()
        if lib is not None:
            out_len = ctypes.c_int64()
            ptr = lib.lz_wal_load(self.path.encode(), ctypes.byref(out_len))
            if not ptr or out_len.value <= 0:
                if ptr:
                    lib.lz_free(ptr)
                return []
            raw = ctypes.string_at(ptr, out_len.value)
            lib.lz_free(ptr)
            records, pos = [], 0
            while pos + 4 <= len(raw):
                ln = int.from_bytes(raw[pos:pos + 4], "little")
                records.append(raw[pos + 4:pos + 4 + ln])
                pos += 4 + ln
            return records
        import struct
        import zlib
        try:
            with open(self.path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return []
        records, pos = [], 0
        while pos + 12 <= len(raw):
            magic, ln, crc = struct.unpack_from("<III", raw, pos)
            if magic != self._MAGIC or pos + 12 + ln > len(raw):
                break
            payload = raw[pos + 12:pos + 12 + ln]
            if zlib.crc32(payload) != crc:
                break
            records.append(payload)
            pos += 12 + ln
        return records

    def _reset(self) -> None:
        lib = load()
        if lib is not None:
            rc = lib.lz_wal_reset(self.path.encode())
            if rc != 0:
                raise OSError(f"WAL reset failed (rc={rc}) for {self.path}")
            return
        with open(self.path, "wb"):
            pass
