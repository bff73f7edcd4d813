"""Durable host-side store: segmented Arrow/Parquet tables + atomic version
counter.

Replaces the reference's ``LanceDBStore`` (``core/vector_store.py``, 244 LoC).
Same Store protocol (11 methods), same role split:
- The HOT path (ANN search) does not live here — it runs on the HBM arena
  (``core.index.MemoryIndex``). ``search_nodes`` is still implemented (exact
  top-k over durable rows) for protocol parity and store-only consumers.
- The store is the system of record across restarts AND the multi-process
  sync channel: every COMMIT bumps a version counter persisted via atomic
  rename, so dashboard-style readers can poll ``get_latest_version`` exactly
  like the reference polls LanceDB table versions (vector_store.py:150-156).
  A commit is one write call (``add_nodes``, ``delete_edges``,
  ``save_profile``, ``compact`` ...) or, inside ``with store.commit():``,
  the whole group: the counter is read once and written once, after the
  group's last rename, and only if something landed. It is read from the
  file at every commit — other processes write it too.

Write path is LSM-lite so bulk graphs stay cheap to mutate: each
``add_nodes``/``delete_nodes`` call appends one small *delta segment* parquet
(upserted rows, or id-only tombstones) and updates an atomically-renamed
manifest — never rewriting the base table. Readers merge base + segments
last-wins; when segments pile up the writer folds everything into a fresh
base (compaction). The reference's delete-all-then-rewrite habit
(memory_system.py:1275-1302) is thereby replaced at the storage layer:
writing 10 new memories into a 1M-row graph costs one 10-row file.

Schema notes vs the reference: embedding dimension is free per row (the
reference hardcodes 1536, vector_store.py:37 — breaking 768-dim providers);
edge ids include the edge_type so typed parallel edges can't collide
(reference id = "src_tgt", vector_store.py:170, collides across types);
user_id never passes through string-interpolated SQL (injection quirk at
vector_store.py:118,137,145). A ``decay_pass`` column stamps each row with
the decay epoch it was written at, so the orchestrator can replay uniform
decay in closed form on reload instead of rewriting every row per sweep.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time
import types
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from lazzaro_tpu.utils.telemetry import default_registry, file_op

_NODE_SCHEMA = pa.schema([
    ("id", pa.string()),
    ("user_id", pa.string()),
    ("content", pa.string()),
    ("embedding", pa.list_(pa.float32())),
    ("type", pa.string()),
    ("timestamp", pa.float64()),
    ("access_count", pa.int64()),
    ("last_accessed", pa.float64()),
    ("salience", pa.float64()),
    ("is_super_node", pa.bool_()),
    ("child_ids", pa.string()),
    ("parent_id", pa.string()),
    ("shard_key", pa.string()),
    ("metadata", pa.string()),
    ("decay_pass", pa.int64()),
    ("_deleted", pa.bool_()),
])

_EDGE_SCHEMA = pa.schema([
    ("id", pa.string()),
    ("user_id", pa.string()),
    ("source_id", pa.string()),
    ("target_id", pa.string()),
    ("weight", pa.float64()),
    ("edge_type", pa.string()),
    ("co_occurrence", pa.int64()),
    ("last_updated", pa.float64()),
    ("metadata", pa.string()),
    ("decay_pass", pa.int64()),
    ("_deleted", pa.bool_()),
])

_SCHEMAS = {"nodes": _NODE_SCHEMA, "edges": _EDGE_SCHEMA}

_FIELD_DEFAULTS = {
    pa.string(): "",
    pa.float64(): 0.0,
    pa.int64(): 0,
    pa.bool_(): False,
}

# Compaction policy: fold segments into the base when either trips.
_COMPACT_MAX_SEGMENTS = 16
_COMPACT_MIN_ROWS = 4096


def _atomic_write(path: str, data: bytes, telemetry=None) -> None:
    """The one funnel every durable write of the store goes through (temp
    file, write, rename): one ``lz.store.io`` span and one
    ``store.file_ops{op="write"}`` count each."""
    tel = telemetry if telemetry is not None else default_registry()
    d = os.path.dirname(path)
    with file_op(tel, "store", "write"):
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(data)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    tel.bump("store.bytes_written", len(data))


def _table_bytes(table: pa.Table) -> bytes:
    buf = pa.BufferOutputStream()
    pq.write_table(table, buf)
    return buf.getvalue().to_pybytes()


class ArrowStore:
    """Per-(table, user) manifest + base parquet + delta segments under
    ``db_dir``. Single-writer per user; cross-process readers go through the
    atomically-replaced manifest, retrying once if compaction swaps files
    underneath them."""

    def __init__(self, db_dir: str = "db", telemetry=None):
        self.db_dir = db_dir
        # every file operation below is one ``lz.store.io`` span and one
        # ``store.file_ops{op}`` count in this registry
        self.telemetry = (telemetry if telemetry is not None
                          else default_registry())
        os.makedirs(db_dir, exist_ok=True)
        self._lock = threading.Lock()
        # the open commit scope of each thread (``commit``)
        self._scope = threading.local()
        # (kind, user) -> json.dumps of the sidecar content this instance
        # last wrote or read (``_holds``)
        self._sidecars: Dict[Any, str] = {}
        self._closed = False

    # ------------------------------------------------------------- plumbing
    @staticmethod
    def _encode_user(user_id: str) -> str:
        """Reversible, collision-free filename encoding (percent-encoding);
        a lossy sanitizer would alias distinct tenants onto one file."""
        from urllib.parse import quote
        return quote(user_id, safe="")

    @staticmethod
    def _decode_user(encoded: str) -> str:
        from urllib.parse import unquote
        return unquote(encoded)

    def _stem(self, table: str, user_id: str) -> str:
        return os.path.join(self.db_dir, f"{table}__{self._encode_user(user_id)}")

    def _manifest_path(self, table: str, user_id: str) -> str:
        return self._stem(table, user_id) + ".manifest.json"

    def _version_path(self) -> str:
        return os.path.join(self.db_dir, "VERSION")

    def _io(self, op: str):
        return file_op(self.telemetry, "store", op)

    def _write(self, path: str, data: bytes) -> None:
        _atomic_write(path, data, self.telemetry)

    def _read_json(self, path: str, op: str = "read_json"):
        """The parsed file, or None when it is missing or torn."""
        with self._io(op):
            try:
                with open(path) as f:
                    return json.load(f)
            except (FileNotFoundError, json.JSONDecodeError):
                return None

    def _read_table(self, name: str) -> pa.Table:
        with self._io("read_table"):
            return pq.read_table(os.path.join(self.db_dir, name))

    def _unlink(self, path: str) -> None:
        with self._io("unlink"):
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass

    def _bump_version(self) -> int:
        v = self.get_latest_version() + 1
        self._write(self._version_path(), str(v).encode())
        self.telemetry.bump("store.commits")
        return v

    def _landed(self) -> None:
        """Every write funnel's last step, under the lock: the open commit
        scope of this thread takes note, or the call is a commit itself."""
        mark = getattr(self._scope, "mark", None)
        if mark is None:
            self._bump_version()
        else:
            mark.landed = True

    @contextlib.contextmanager
    def commit(self):
        """Group this thread's writes into ONE commit: each still lands
        (temp file, rename) before its call returns, and ``VERSION`` is read
        and written once on the way out — after the last rename, also when
        a later write raised, and not at all if nothing landed. Yields the
        scope's mark: ``landed`` (a write did) and ``version`` (the new
        number; None while open, and when nothing landed). A scope opened
        inside another belongs to it."""
        mark = getattr(self._scope, "mark", None)
        if mark is not None:
            yield mark
            return
        mark = self._scope.mark = types.SimpleNamespace(landed=False,
                                                         version=None)
        try:
            yield mark
        finally:
            self._scope.mark = None
            if mark.landed:
                with self._lock:
                    mark.version = self._bump_version()

    def get_latest_version(self) -> int:
        with self._io("read_version"):
            try:
                with open(self._version_path()) as f:
                    return int(f.read().strip())
            except (FileNotFoundError, ValueError):
                return 0

    # ----------------------------------------------------- manifest handling
    def _load_manifest(self, table: str, user_id: str) -> Optional[Dict[str, Any]]:
        """Current manifest, or a synthesized one for the legacy single-file
        layout (``{table}__{user}.parquet`` with no manifest)."""
        man = self._read_json(self._manifest_path(table, user_id),
                              "read_manifest")
        if man is not None:
            return man
        legacy = self._stem(table, user_id) + ".parquet"
        if os.path.exists(legacy):
            return {"base": os.path.basename(legacy), "segments": [], "gen": 0}
        return None

    def _store_manifest(self, table: str, user_id: str, man: Dict[str, Any]) -> None:
        self._write(self._manifest_path(table, user_id),
                    json.dumps(man).encode())

    def _conform(self, t: pa.Table, schema: pa.Schema) -> pa.Table:
        """Add any missing columns (legacy files predate decay_pass/_deleted)
        and order/cast to the canonical schema."""
        cols = []
        for f in schema:
            if f.name in t.column_names:
                cols.append(t.column(f.name).cast(f.type))
            else:
                default = _FIELD_DEFAULTS.get(f.type)
                if default is None:          # list<float32> embedding
                    arr = pa.array([[]] * t.num_rows, type=f.type)
                else:
                    arr = pa.array([default] * t.num_rows, type=f.type)
                cols.append(arr)
        return pa.Table.from_arrays(cols, schema=schema)

    # Vector-inheritance contract: an upsert row whose embedding is NULL
    # means "no new vector — keep the stored one"; an EMPTY LIST means the
    # row explicitly has no vector; a tombstone blocks inheritance across a
    # delete. This is what lets the orchestrator upsert metadata-only deltas
    # without ever re-writing, or degrading, the stored float32 vectors.

    @staticmethod
    def _emb_state(t: pa.Table):
        """(emb_array, has_vec, is_null) for the embedding column, or
        (None, zeros, zeros) for tables without one (edges)."""
        n = t.num_rows
        if "embedding" not in t.column_names:
            return None, np.zeros(n, bool), np.zeros(n, bool)
        emb = t.column("embedding").combine_chunks()
        lengths = np.diff(emb.offsets.to_numpy(zero_copy_only=False))
        nulls = emb.is_null().to_numpy(zero_copy_only=False)
        return emb, (~nulls) & (lengths > 0), nulls

    @classmethod
    def _merge_read(cls, t: pa.Table) -> pa.Table:
        """Reader merge: last-wins by id, tombstones dropped, NULL vectors
        resolved to the latest stored vector for that id."""
        ids = t.column("id").to_pylist()
        deleted = t.column("_deleted").to_pylist()
        emb, has_vec, nulls = cls._emb_state(t)
        last: Dict[str, int] = {}
        last_emb: Dict[str, int] = {}
        for i, rid in enumerate(ids):
            last[rid] = i
            if deleted[i]:
                last_emb.pop(rid, None)
            elif has_vec[i]:
                last_emb[rid] = i
        keep = sorted(i for rid, i in last.items() if not deleted[i])
        src = [last_emb[ids[i]] if nulls[i] and ids[i] in last_emb else i
               for i in keep]
        if len(keep) == t.num_rows and src == keep:
            return t
        out = t.take(pa.array(keep, type=pa.int64()))
        if src != keep:
            emb_fixed = emb.take(pa.array(src, type=pa.int64()))
            fi = t.schema.get_field_index("embedding")
            out = out.set_column(fi, t.schema.field("embedding"), emb_fixed)
        return out

    @classmethod
    def _merge_fold(cls, t: pa.Table) -> pa.Table:
        """Segments-only fold: last-wins by id, tombstones KEPT (the base
        still holds the rows they delete). A NULL-vector row whose
        inheritance was blocked by an intervening tombstone materializes an
        explicit empty vector, so the fold can never let the base's deleted
        vector resurface. Segments are small, so this path may go through
        Python lists."""
        ids = t.column("id").to_pylist()
        deleted = t.column("_deleted").to_pylist()
        emb, has_vec, nulls = cls._emb_state(t)
        last: Dict[str, int] = {}
        last_emb: Dict[str, int] = {}
        blocked: set = set()
        for i, rid in enumerate(ids):
            last[rid] = i
            if deleted[i]:
                last_emb.pop(rid, None)
                blocked.add(rid)
            elif has_vec[i]:
                last_emb[rid] = i
                blocked.discard(rid)
        keep = sorted(last.values())
        if emb is None:
            return t.take(pa.array(keep, type=pa.int64()))
        emb_py = emb.to_pylist()
        final_emb = []
        for i in keep:
            rid = ids[i]
            if nulls[i] and not deleted[i]:
                if rid in last_emb:
                    final_emb.append(emb_py[last_emb[rid]])
                elif rid in blocked:
                    final_emb.append([])       # tombstone blocks base inherit
                else:
                    final_emb.append(None)     # still inherits from the base
            else:
                final_emb.append(emb_py[i])
        out = t.take(pa.array(keep, type=pa.int64()))
        fi = t.schema.get_field_index("embedding")
        return out.set_column(fi, t.schema.field("embedding"),
                              pa.array(final_emb, type=pa.list_(pa.float32())))

    def _read_merged(self, table: str, user_id: str) -> Optional[pa.Table]:
        """base + segments merged (see ``_merge_rows``), tombstones dropped.
        Returns None ONLY when the user genuinely has no rows (no manifest).
        Retries if a concurrent compaction unlinked a file between the
        manifest read and the parquet read; exhausting the retries raises
        rather than silently presenting a populated table as empty."""
        schema = _SCHEMAS[table]
        last_err: Optional[FileNotFoundError] = None
        for _attempt in range(4):
            man = self._load_manifest(table, user_id)
            if man is None:
                return None
            try:
                parts = []
                names = ([man["base"]] if man.get("base") else []) + man["segments"]
                for name in names:
                    parts.append(self._conform(self._read_table(name),
                                               schema))
            except FileNotFoundError as e:
                last_err = e
                continue
            if not parts:
                return None
            t = pa.concat_tables(parts) if len(parts) > 1 else parts[0]
            return self._merge_read(t)
        raise RuntimeError(
            f"{table} read for user {user_id!r} kept racing compaction; "
            f"refusing to return an empty view") from last_err

    def _append_segment(self, table: str, user_id: str, rows_table: pa.Table) -> None:
        """One delta segment + manifest swap (+ compaction when due).
        Caller holds the lock."""
        man = self._load_manifest(table, user_id) or {"base": None, "segments": [], "gen": 0}
        gen = int(man["gen"]) + 1
        name = f"{os.path.basename(self._stem(table, user_id))}.seg-{gen:06d}.parquet"
        self._write(os.path.join(self.db_dir, name), _table_bytes(rows_table))
        man["segments"].append(name)
        man["gen"] = gen
        self._row_counts(man, {name: rows_table.num_rows})
        self._store_manifest(table, user_id, man)
        self._maybe_compact(table, user_id, man)
        self._landed()

    def _row_counts(self, man: Dict[str, Any],
                    fresh: Optional[Dict[str, int]] = None) -> None:
        """``man["rows"]``: the row count of each file the manifest lists,
        written with it by whoever wrote the file (``fresh``: the one just
        written). A name it lacks (a legacy manifest, or one an older
        process wrote) is asked of the file's footer here, so the next
        manifest written is complete."""
        known = {**(man.get("rows") or {}), **(fresh or {})}
        rows = {}
        for name in ([man["base"]] if man.get("base") else []) + man["segments"]:
            if name in known:
                rows[name] = known[name]
                continue
            with self._io("read_meta"):
                try:
                    rows[name] = pq.read_metadata(
                        os.path.join(self.db_dir, name)).num_rows
                except FileNotFoundError:
                    rows[name] = 0
        man["rows"] = rows

    def _maybe_compact(self, table: str, user_id: str, man: Dict[str, Any]) -> None:
        rows = man["rows"]          # complete: _append_segment just wrote it
        segs = man["segments"]
        seg_rows = sum(rows[name] for name in segs)
        base_rows = rows[man["base"]] if man.get("base") else 0
        # Amortized (LSM-style): rewrite the base only once the deltas are a
        # meaningful fraction of it, so total compaction IO stays O(N log N).
        if seg_rows >= max(_COMPACT_MIN_ROWS, base_rows // 2):
            self._compact(table, user_id, man)
        elif len(segs) >= _COMPACT_MAX_SEGMENTS:
            # Too many tiny deltas hurt read amplification, but don't justify
            # an O(base) rewrite — fold just the segments into one.
            self._fold_segments(table, user_id, man)

    def _fold_segments(self, table: str, user_id: str, man: Dict[str, Any]) -> None:
        """Merge all delta segments into ONE segment, last-wins per id,
        KEEPING tombstones (the base still holds the rows they delete)."""
        schema = _SCHEMAS[table]
        parts = []
        for name in man["segments"]:
            try:
                parts.append(self._conform(self._read_table(name), schema))
            except FileNotFoundError:
                pass
        if not parts:
            return
        t = pa.concat_tables(parts) if len(parts) > 1 else parts[0]
        # keep tombstones (the base still holds the rows they delete) AND
        # resolve vector inheritance before earlier segment rows are dropped
        t = self._merge_fold(t)
        old = list(man["segments"])
        gen = int(man["gen"]) + 1
        name = f"{os.path.basename(self._stem(table, user_id))}.seg-{gen:06d}.parquet"
        self._write(os.path.join(self.db_dir, name), _table_bytes(t))
        man["segments"] = [name]
        man["gen"] = gen
        self._row_counts(man, {name: t.num_rows})
        self._store_manifest(table, user_id, man)
        for old_name in old:
            self._unlink(os.path.join(self.db_dir, old_name))

    def _compact(self, table: str, user_id: str, man: Dict[str, Any]) -> None:
        merged = self._read_merged(table, user_id)
        old = ([man["base"]] if man.get("base") else []) + man["segments"]
        gen = int(man["gen"]) + 1
        if merged is None or merged.num_rows == 0:
            new_man = {"base": None, "segments": [], "gen": gen, "rows": {}}
        else:
            name = f"{os.path.basename(self._stem(table, user_id))}.base-{gen:06d}.parquet"
            self._write(os.path.join(self.db_dir, name), _table_bytes(merged))
            new_man = {"base": name, "segments": [], "gen": gen,
                       "rows": {name: merged.num_rows}}
        self._store_manifest(table, user_id, new_man)
        for name in old:
            self._unlink(os.path.join(self.db_dir, name))

    def compact(self, user_id: str = "default") -> None:
        """Fold all delta segments into fresh bases (both tables)."""
        with self._lock:
            for table in ("nodes", "edges"):
                man = self._load_manifest(table, user_id)
                if man is not None:
                    self._compact(table, user_id, man)
            self._landed()

    def _drop_all(self, table: str, user_id: str) -> None:
        """Delete-all parity (reference vector_store.py:143-145). The user's
        sidecars are written anew by whatever save follows."""
        for kind in ("profile", "sys_meta"):
            self._sidecars.pop((kind, user_id), None)
        man = self._load_manifest(table, user_id)
        if man is not None:
            for name in ([man["base"]] if man.get("base") else []) + man["segments"]:
                self._unlink(os.path.join(self.db_dir, name))
        for path in (self._manifest_path(table, user_id),
                     self._stem(table, user_id) + ".parquet"):
            self._unlink(path)

    # ----------------------------------------------------------------- nodes
    @staticmethod
    def _node_row(n: Dict[str, Any], user_id: str, now: float) -> Dict[str, Any]:
        emb = n.get("embedding")
        if emb is None:
            emb = n.get("vector")
        if isinstance(emb, np.ndarray):
            emb = emb.astype(np.float32).tolist()
        elif emb is not None:
            emb = [float(x) for x in emb]
        if not emb:
            # None/empty reaches the segment as NULL = "no new vector"; the
            # merge inherits the stored vector (_merge_read). An explicit
            # empty list would instead *destroy* it under the merge contract,
            # so normalize both spellings of "nothing" to NULL.
            emb = None
        return {
            "id": n["id"],
            "user_id": user_id,
            "content": n.get("content", ""),
            "embedding": emb,
            "type": n.get("type", "semantic"),
            "timestamp": float(n.get("timestamp", now)),
            "access_count": int(n.get("access_count", 0)),
            "last_accessed": float(n.get("last_accessed", now)),
            "salience": float(n.get("salience", 0.5)),
            "is_super_node": bool(n.get("is_super_node", False)),
            "child_ids": json.dumps(n.get("child_ids", [])),
            "parent_id": n.get("parent_id") or "",
            "shard_key": n.get("shard_key") or "",
            "metadata": json.dumps(n.get("metadata", {})),
            "decay_pass": int(n.get("decay_pass", 0)),
            "_deleted": False,
        }

    def add_nodes(self, nodes: List[Dict[str, Any]], user_id: str = "default") -> None:
        """Upsert: one delta segment, row-granularity last-wins. A row with
        no ``embedding`` keeps the stored vector (the orchestrator holds
        vectors in the device arena, not on host nodes; an embedding-less
        upsert means "metadata changed", never "drop the vector")."""
        if not nodes:
            return
        now = time.time()
        rows = [self._node_row(n, user_id, now) for n in nodes]
        with self._lock:
            self._append_segment("nodes", user_id,
                                 pa.Table.from_pylist(rows, schema=_NODE_SCHEMA))

    def add_nodes_columns(self, ids: Sequence[str], contents: Sequence[str],
                          embeddings: np.ndarray, types: Sequence[str],
                          saliences: Sequence[float],
                          timestamps: Sequence[float],
                          shard_keys: Sequence[str], decay_pass: int = 0,
                          user_id: str = "default") -> None:
        """Columnar bulk insert for the ingest hot path: fresh nodes only
        (access_count 0, no hierarchy fields). The embedding column is built
        from ONE flat float32 buffer + offsets instead of n×d Python floats
        — at 5k × 768 this is the difference between ~1 s and ~50 ms per
        conversation of store time. Semantics identical to ``add_nodes``
        with the same field defaults (one delta segment, last-wins)."""
        n = len(ids)
        if n == 0:
            return
        emb = np.ascontiguousarray(np.asarray(embeddings, np.float32))
        if emb.ndim != 2 or emb.shape[0] != n:
            raise ValueError(f"embeddings must be [n, d], got {emb.shape}")
        d = emb.shape[1]
        now = time.time()
        offsets = pa.array(np.arange(0, (n + 1) * d, d, dtype=np.int32),
                           type=pa.int32())
        emb_col = pa.ListArray.from_arrays(offsets, pa.array(emb.reshape(-1)))
        cols = [
            pa.array(list(ids), pa.string()),
            pa.array([user_id] * n, pa.string()),
            pa.array(list(contents), pa.string()),
            emb_col,
            pa.array(list(types), pa.string()),
            pa.array(np.asarray(timestamps, np.float64)),
            pa.array(np.zeros(n, np.int64)),            # access_count
            pa.array(np.full(n, now, np.float64)),      # last_accessed
            pa.array(np.asarray(saliences, np.float64)),
            pa.array(np.zeros(n, bool)),                # is_super_node
            pa.array(["[]"] * n, pa.string()),          # child_ids
            pa.array([""] * n, pa.string()),            # parent_id
            pa.array(list(shard_keys), pa.string()),
            pa.array(["{}"] * n, pa.string()),          # metadata
            pa.array(np.full(n, decay_pass, np.int64)),
            pa.array(np.zeros(n, bool)),                # _deleted
        ]
        t = pa.Table.from_arrays(cols, schema=_NODE_SCHEMA)
        with self._lock:
            self._append_segment("nodes", user_id, t)

    def get_nodes(self, user_id: str = "default") -> List[Dict[str, Any]]:
        with self._lock:
            t = self._read_merged("nodes", user_id)
        if t is None:
            return []
        rows = t.drop_columns(["_deleted"]).to_pylist()
        for r in rows:
            r["child_ids"] = json.loads(r.get("child_ids") or "[]")
            r["metadata"] = json.loads(r.get("metadata") or "{}")
            r["parent_id"] = r.get("parent_id") or None
        return rows

    def get_nodes_columns(self, user_id: str = "default") -> Optional[Dict[str, Any]]:
        """Columnar bulk read — the 1M-row load path. Strings come back as
        Python lists, numerics as numpy arrays, and ``embedding`` as ONE
        [N, d] float32 matrix plus a boolean ``has_embedding`` mask (rows
        whose stored length differs from the modal dimension are flagged
        off). ``child_ids``/``metadata`` stay JSON-encoded; callers decode
        the few rows that need them (super nodes)."""
        with self._lock:
            t = self._read_merged("nodes", user_id)
        if t is None or t.num_rows == 0:
            return None
        out: Dict[str, Any] = {}
        for name in ("id", "content", "type", "shard_key", "parent_id",
                     "child_ids"):
            out[name] = t.column(name).to_pylist()
        for name in ("timestamp", "access_count", "last_accessed", "salience",
                     "is_super_node", "decay_pass"):
            out[name] = t.column(name).to_numpy(zero_copy_only=False)
        emb_col = t.column("embedding").combine_chunks()
        offsets = emb_col.offsets.to_numpy(zero_copy_only=False)
        lengths = np.diff(offsets)
        values = emb_col.values.to_numpy(zero_copy_only=False).astype(np.float32)
        n = t.num_rows
        present = lengths > 0
        dim = int(np.bincount(lengths[present]).argmax()) if present.any() else 0
        ok = lengths == dim
        if dim and bool(ok.all()):
            matrix = values.reshape(n, dim)
        else:
            matrix = np.zeros((n, dim), np.float32)
            for i in np.nonzero(ok)[0] if dim else []:
                matrix[i] = values[offsets[i]:offsets[i + 1]]
        out["embedding"] = matrix
        out["has_embedding"] = ok & (lengths > 0)
        # Rows whose stored vector length differs from the modal dimension
        # (provider migration, per-row free dimension) ride along ragged so
        # callers can preserve them instead of silently zeroing them out.
        ragged = {}
        for i in np.nonzero((lengths > 0) & ~ok)[0]:
            ragged[int(i)] = values[offsets[i]:offsets[i + 1]].copy()
        out["ragged_embeddings"] = ragged
        return out

    def search_nodes(self, embedding: List[float], user_id: str = "default",
                     limit: int = 10) -> List[str]:
        """Protocol-parity exact cosine top-k over durable rows (the serving
        path uses the HBM arena instead). Columnar read + the native
        multithreaded kernel when built, else vectorized numpy — both replace
        the reference's per-row LanceDB round trip for store-only consumers."""
        cols = self.get_nodes_columns(user_id)
        if cols is None or not len(embedding):
            return []
        q = np.asarray(embedding, np.float32)
        if np.linalg.norm(q) == 0:
            return []
        if cols["embedding"].shape[1] == q.size:
            idx = np.nonzero(cols["has_embedding"])[0]
            if idx.size == 0:
                return []
            embs = cols["embedding"][idx]
        else:
            # Per-row free dimension: serve the rows matching the query's
            # dimension even when they are not the store's modal dimension.
            matches = sorted(i for i, v in cols["ragged_embeddings"].items()
                             if v.size == q.size)
            if not matches:
                return []
            idx = np.asarray(matches, np.int64)
            embs = np.stack([cols["ragged_embeddings"][int(i)] for i in idx])
        from lazzaro_tpu import native
        _, top_rows = native.masked_topk(embs, None, q, min(limit, idx.size))
        ids = cols["id"]
        return [ids[idx[i]] for i in top_rows if i >= 0]

    def delete_nodes(self, node_ids: List[str], user_id: str = "default") -> None:
        with self._lock:
            if not node_ids:
                # Parity: empty list deletes ALL the user's rows
                # (reference vector_store.py:143-145).
                self._drop_all("nodes", user_id)
                self._landed()
                return
            if self._load_manifest("nodes", user_id) is None:
                return
            rows = [{"id": i, "user_id": user_id, "_deleted": True}
                    for i in node_ids]
            t = self._conform(pa.Table.from_pylist(rows), _NODE_SCHEMA)
            self._append_segment("nodes", user_id, t)

    # ----------------------------------------------------------------- edges
    @staticmethod
    def _edge_id(e: Dict[str, Any]) -> str:
        src = e.get("source_id") or e.get("source")
        tgt = e.get("target_id") or e.get("target")
        et = e.get("edge_type", "relates_to")
        return e.get("id") or f"{src}|{tgt}|{et}"

    def add_edges(self, edges: List[Dict[str, Any]], user_id: str = "default") -> None:
        if not edges:
            return
        now = time.time()
        rows = []
        for e in edges:
            rows.append({
                "id": self._edge_id(e),
                "user_id": user_id,
                "source_id": e.get("source_id") or e.get("source"),
                "target_id": e.get("target_id") or e.get("target"),
                "weight": float(e.get("weight", 0.5)),
                "edge_type": e.get("edge_type") or e.get("type", "relates_to"),
                "co_occurrence": int(e.get("co_occurrence", 1)),
                "last_updated": float(e.get("last_updated", now)),
                "metadata": json.dumps(e.get("metadata", {})),
                "decay_pass": int(e.get("decay_pass", 0)),
                "_deleted": False,
            })
        with self._lock:
            self._append_segment("edges", user_id,
                                 pa.Table.from_pylist(rows, schema=_EDGE_SCHEMA))

    def get_edges(self, user_id: str = "default") -> List[Dict[str, Any]]:
        with self._lock:
            t = self._read_merged("edges", user_id)
        if t is None:
            return []
        rows = t.drop_columns(["_deleted"]).to_pylist()
        for r in rows:
            r["metadata"] = json.loads(r.get("metadata") or "{}")
        return rows

    def get_edges_columns(self, user_id: str = "default") -> Optional[Dict[str, Any]]:
        """Columnar bulk edge read (strings as lists, numerics as numpy)."""
        with self._lock:
            t = self._read_merged("edges", user_id)
        if t is None or t.num_rows == 0:
            return None
        out: Dict[str, Any] = {}
        for name in ("id", "source_id", "target_id", "edge_type"):
            out[name] = t.column(name).to_pylist()
        for name in ("weight", "co_occurrence", "last_updated", "decay_pass"):
            out[name] = t.column(name).to_numpy(zero_copy_only=False)
        return out

    def delete_edges(self, edge_ids: List[str], user_id: str = "default") -> None:
        with self._lock:
            if not edge_ids:
                self._drop_all("edges", user_id)
                self._landed()
                return
            if self._load_manifest("edges", user_id) is None:
                return
            rows = [{"id": i, "user_id": user_id, "_deleted": True}
                    for i in edge_ids]
            t = self._conform(pa.Table.from_pylist(rows), _EDGE_SCHEMA)
            self._append_segment("edges", user_id, t)

    # --------------------------------------------------------------- profile
    def _holds(self, kind: str, user_id: str, content: str) -> bool:
        """Whether the user's ``kind`` sidecar already holds ``content``
        (``json.dumps`` of what a save is handed): this instance last wrote
        or read exactly that, and the user has one writer. Then the save is
        no file operation. Caller holds the lock."""
        if self._sidecars.get((kind, user_id)) != content:
            return False
        self.telemetry.bump("store.writes_skipped", labels={"kind": kind})
        return True

    def _remember(self, kind: str, user_id: str, loaded) -> None:
        """What a load found in the file; None (absent, torn): nothing."""
        if loaded is None:
            self._sidecars.pop((kind, user_id), None)
        else:
            self._sidecars[(kind, user_id)] = json.dumps(loaded)

    def save_profile(self, profile: Dict[str, Any], user_id: str = "default") -> None:
        """``updated_at`` is when the profile last CHANGED: saving the
        ``data`` the file holds again writes nothing."""
        data = json.dumps(profile)
        with self._lock:
            if self._holds("profile", user_id, data):
                return
            payload = json.dumps({"user_id": user_id, "data": profile,
                                  "updated_at": time.time()}).encode()
            self._write(self._stem("profiles", user_id) + ".json", payload)
            self._sidecars[("profile", user_id)] = data
            self._landed()

    def load_profile(self, user_id: str = "default") -> Optional[Dict[str, Any]]:
        prof = self._read_json(self._stem("profiles", user_id) + ".json")
        data = prof.get("data") if prof is not None else None
        self._remember("profile", user_id, data)
        return data

    # -------------------------------------------------------------- sys meta
    def save_sys_meta(self, meta: Dict[str, Any], user_id: str = "default") -> None:
        """Small orchestrator-owned sidecar (decay-pass counter, node counter).
        Presence of this method is how the orchestrator detects that the
        store supports incremental persistence."""
        data = json.dumps(meta)
        with self._lock:
            if self._holds("sys_meta", user_id, data):
                return
            self._write(self._stem("sysmeta", user_id) + ".json",
                        data.encode())
            self._sidecars[("sys_meta", user_id)] = data
            self._landed()

    def load_sys_meta(self, user_id: str = "default") -> Dict[str, Any]:
        meta = self._read_json(self._stem("sysmeta", user_id) + ".json")
        self._remember("sys_meta", user_id, meta)
        return meta if meta is not None else {}

    # ------------------------------------------------------------------ misc
    def get_all_users(self) -> List[str]:
        import re
        files = os.listdir(self.db_dir)
        manifests = {f[len("nodes__"):-len(".manifest.json")] for f in files
                     if f.startswith("nodes__") and f.endswith(".manifest.json")}
        users = set(manifests)
        gen_tag = re.compile(r"(.+)\.(?:seg|base)-\d{6,}$")
        for fname in files:
            if not (fname.startswith("nodes__") and fname.endswith(".parquet")):
                continue
            stem = fname[len("nodes__"):-len(".parquet")]
            m = gen_tag.match(stem)
            if m and m.group(1) in manifests:
                continue          # generation file of a manifest-known user
            users.add(stem)       # legacy single-file layout
        return sorted(self._decode_user(u) for u in users)

    def close(self) -> None:
        self._closed = True
