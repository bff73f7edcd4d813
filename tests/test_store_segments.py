"""Segmented (LSM-lite) ArrowStore behavior: delta-segment upserts instead of
full rewrites, tombstone deletes, last-wins merge, compaction, legacy-layout
migration, columnar bulk readers, and the sys-meta sidecar."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from lazzaro_tpu.core.store import ArrowStore
from lazzaro_tpu.utils.telemetry import Telemetry

from tests.fakes import file_ops


@pytest.fixture()
def store(tmp_path):
    s = ArrowStore(str(tmp_path / "db"))
    yield s
    s.close()


def _node(i, dim=4, **kw):
    row = {"id": f"node_{i}", "content": f"fact {i}",
           "embedding": [float(i)] * dim, "salience": 0.5}
    row.update(kw)
    return row


def _segments(store, table="nodes", user="default"):
    with open(store._manifest_path(table, user)) as f:
        return json.load(f)


def test_upsert_appends_segment_not_rewrite(store):
    store.add_nodes([_node(i) for i in range(100)])
    man1 = _segments(store)
    store.add_nodes([_node(100)])
    man2 = _segments(store)
    assert len(man2["segments"]) == len(man1["segments"]) + 1
    # the delta holds ONE row, not 101
    seg = os.path.join(store.db_dir, man2["segments"][-1])
    assert pq.read_metadata(seg).num_rows == 1
    assert len(store.get_nodes()) == 101


def test_last_wins_and_tombstones(store):
    store.add_nodes([_node(1, salience=0.3), _node(2)])
    store.add_nodes([_node(1, salience=0.9)])     # upsert
    store.delete_nodes(["node_2"])                # tombstone
    rows = store.get_nodes()
    assert [r["id"] for r in rows] == ["node_1"]
    assert rows[0]["salience"] == pytest.approx(0.9)


def test_segment_folding_bounds_read_amplification(store):
    for i in range(20):   # > _COMPACT_MAX_SEGMENTS individual writes
        store.add_nodes([_node(i)])
    man = _segments(store)
    # tiny deltas don't justify an O(base) rewrite: they fold into one
    # segment once the count cap trips, keeping the manifest shallow
    assert len(man["segments"]) < 16
    assert len(store.get_nodes()) == 20
    # the folded segment files are gone; only live ones remain
    segs = [f for f in os.listdir(store.db_dir) if ".seg-" in f]
    assert len(segs) == len(man["segments"])


def test_row_heavy_deltas_trigger_base_compaction(store):
    store.add_nodes([_node(i) for i in range(3000)])
    store.add_nodes([_node(i) for i in range(3000, 6000)])   # crosses 4096 rows
    man = _segments(store)
    assert man["base"] is not None
    assert man["segments"] == []
    assert len(store.get_nodes()) == 6000


def test_tombstones_survive_segment_folding(store):
    store.add_nodes([_node(i) for i in range(5)])
    store.compact()                           # rows now live in the base
    store.delete_nodes(["node_2"])
    for i in range(20):                       # force a segments-only fold
        store.add_nodes([_node(100 + i)])
    man = _segments(store)
    assert man["base"] is not None            # base untouched by the fold
    ids = {r["id"] for r in store.get_nodes()}
    assert "node_2" not in ids                # tombstone still effective
    assert {"node_0", "node_104"} <= ids


def test_explicit_compact_and_versions(store):
    store.add_nodes([_node(1)])
    store.add_nodes([_node(2)])
    v_before = store.get_latest_version()
    store.compact()
    assert store.get_latest_version() > v_before
    assert {r["id"] for r in store.get_nodes()} == {"node_1", "node_2"}


def test_legacy_single_file_layout_still_reads(store):
    # simulate a round-1 database: one parquet, no manifest, no new columns
    legacy = pa.Table.from_pylist([{
        "id": "node_9", "user_id": "default", "content": "old row",
        "embedding": [1.0, 0.0], "type": "semantic", "timestamp": 5.0,
        "access_count": 2, "last_accessed": 6.0, "salience": 0.7,
        "is_super_node": False, "child_ids": "[]", "parent_id": "",
        "shard_key": "work", "metadata": "{}",
    }])
    buf = pa.BufferOutputStream()
    pq.write_table(legacy, buf)
    with open(os.path.join(store.db_dir, "nodes__default.parquet"), "wb") as f:
        f.write(buf.getvalue().to_pybytes())

    rows = store.get_nodes()
    assert rows[0]["id"] == "node_9"
    assert rows[0]["decay_pass"] == 0       # missing column defaulted
    # incremental write on top of the legacy base keeps both rows
    store.add_nodes([_node(10, dim=2)])
    assert {r["id"] for r in store.get_nodes()} == {"node_9", "node_10"}


def test_columnar_node_reader(store):
    store.add_nodes([_node(i, dim=3) for i in range(5)])
    store.add_nodes([{"id": "super_1", "content": "topic", "embedding": [],
                      "is_super_node": True, "child_ids": ["node_0"]}])
    cols = store.get_nodes_columns()
    assert cols["embedding"].shape == (6, 3)
    assert cols["embedding"].dtype == np.float32
    assert cols["has_embedding"].sum() == 5          # super row has no vector
    sup = cols["id"].index("super_1")
    assert bool(cols["is_super_node"][sup])
    assert json.loads(cols["child_ids"][sup]) == ["node_0"]


def test_columnar_edge_reader(store):
    store.add_edges([{"source": "a", "target": "b", "weight": 0.6},
                     {"source": "b", "target": "c", "weight": 0.4}])
    cols = store.get_edges_columns()
    assert cols["source_id"] == ["a", "b"]
    np.testing.assert_allclose(cols["weight"], [0.6, 0.4])


def test_delete_all_parity_drops_everything(store):
    store.add_nodes([_node(1)])
    store.delete_nodes([])
    assert store.get_nodes() == []
    assert store.get_nodes_columns() is None


def test_sys_meta_roundtrip(store):
    assert store.load_sys_meta() == {}
    store.save_sys_meta({"decay_pass": 7, "node_counter": 42})
    assert store.load_sys_meta() == {"decay_pass": 7, "node_counter": 42}
    # per-user isolation
    assert store.load_sys_meta("alice") == {}


def test_search_nodes_over_segments(store):
    store.add_nodes([_node(1, embedding=[1.0, 0.0, 0.0, 0.0])])
    store.add_nodes([_node(2, embedding=[0.0, 1.0, 0.0, 0.0])])
    assert store.search_nodes([1.0, 0.05, 0.0, 0.0], limit=1) == ["node_1"]


def test_cross_process_reader_sees_segments(tmp_path):
    a = ArrowStore(str(tmp_path / "db"))
    b = ArrowStore(str(tmp_path / "db"))
    a.add_nodes([_node(1)])
    v1 = b.get_latest_version()
    a.add_nodes([_node(2)])
    assert b.get_latest_version() > v1
    assert {r["id"] for r in b.get_nodes()} == {"node_1", "node_2"}


def test_empty_embedding_upsert_preserves_stored_vector(store):
    store.add_nodes([_node(1, embedding=[0.1, 0.2, 0.3, 0.4])])
    # metadata-only upsert (no vector on host): the stored vector survives
    store.add_nodes([{"id": "node_1", "content": "updated", "embedding": [],
                      "salience": 0.9}])
    rows = store.get_nodes()
    assert rows[0]["content"] == "updated"
    assert rows[0]["embedding"] == pytest.approx([0.1, 0.2, 0.3, 0.4])


def test_mixed_dimension_rows_search_and_survive(store):
    store.add_nodes([{"id": "old", "content": "legacy", "embedding": [1.0] * 8},
                     {"id": "new1", "content": "n1", "embedding": [0.5] * 4},
                     {"id": "new2", "content": "n2", "embedding": [-0.5] * 4}])
    # non-modal query still serves its rows
    assert store.search_nodes([1.0] * 8, limit=1) == ["old"]
    # metadata upsert of the non-modal row keeps its 8-dim vector
    store.add_nodes([{"id": "old", "content": "legacy2", "embedding": []}])
    row = [r for r in store.get_nodes() if r["id"] == "old"][0]
    assert len(row["embedding"]) == 8


def test_get_all_users_with_tricky_names(tmp_path):
    s = ArrowStore(str(tmp_path / "db"))
    s.add_nodes([_node(1)], user_id="metrics.seg-a")
    s.add_nodes([_node(2)], user_id="default")
    assert s.get_all_users() == ["default", "metrics.seg-a"]


def test_columnar_bulk_insert_matches_dict_path(tmp_path):
    """add_nodes_columns (the ingest hot path: one flat embedding buffer)
    round-trips identically to add_nodes dict rows."""
    store = ArrowStore(str(tmp_path / "db"))
    emb = np.arange(12, dtype=np.float32).reshape(3, 4)
    store.add_nodes_columns(
        ids=["a", "b", "c"], contents=["one", "two", "three"],
        embeddings=emb, types=["semantic", "episodic", "semantic"],
        saliences=[0.5, 0.6, 0.7], timestamps=[1.0, 2.0, 3.0],
        shard_keys=["work", "", "health"], decay_pass=4)
    store.add_nodes([{"id": "d", "content": "four", "embedding": [9.0] * 4,
                      "type": "semantic", "salience": 0.8, "timestamp": 4.0,
                      "shard_key": "work", "decay_pass": 4}])
    rows = {r["id"]: r for r in store.get_nodes()}
    assert len(rows) == 4
    assert rows["b"]["type"] == "episodic"
    assert rows["b"]["embedding"] == [4.0, 5.0, 6.0, 7.0]
    assert rows["c"]["salience"] == 0.7 and rows["c"]["shard_key"] == "health"
    assert rows["a"]["decay_pass"] == 4 and rows["a"]["access_count"] == 0
    # last-wins upsert across the two paths
    store.add_nodes_columns(ids=["d"], contents=["four v2"],
                            embeddings=np.full((1, 4), 2.0, np.float32),
                            types=["semantic"], saliences=[0.9],
                            timestamps=[5.0], shard_keys=["work"])
    rows = {r["id"]: r for r in store.get_nodes()}
    assert rows["d"]["content"] == "four v2" and rows["d"]["salience"] == 0.9
    store.close()


# ------------------------------------------------ row counts in the manifest
def _footers(store, man):
    """What the files themselves say: {name: rows} over the listed files."""
    names = ([man["base"]] if man.get("base") else []) + man["segments"]
    return {n: pq.read_metadata(os.path.join(store.db_dir, n)).num_rows
            for n in names}


def test_manifest_carries_row_counts_after_append_fold_and_compact(tmp_path):
    store = ArrowStore(str(tmp_path / "db"), telemetry=Telemetry())
    store.add_nodes([_node(i) for i in range(7)])
    store.add_nodes([_node(7)])
    man = _segments(store)
    assert list(man["rows"].values()) == [7, 1]            # append
    store.compact()
    man = _segments(store)
    assert man["rows"] == {man["base"]: 8}                 # compact
    for i in range(16):                                    # the 16th folds
        store.add_nodes([_node(100 + i)])
    man = _segments(store)
    assert len(man["segments"]) == 1                       # fold
    assert man["rows"] == {man["base"]: 8, man["segments"][0]: 16}
    assert man["rows"] == _footers(store, man)
    store.delete_nodes([f"node_{i}" for i in range(8)])    # tombstones count
    man = _segments(store)
    assert man["rows"] == _footers(store, man)
    assert "read_meta" not in file_ops(store.telemetry)    # nobody asked a footer
    store.delete_nodes([])
    store.add_nodes([_node(1)])
    store.delete_nodes(["node_1"])
    store.compact()                                        # nothing is left
    assert _segments(store) == {"base": None, "segments": [], "gen": 3,
                                "rows": {}}


@pytest.mark.parametrize("batches", [
    [3000, 3000],                    # row-heavy deltas: _COMPACT_MIN_ROWS
    [10000, 4500, 600],              # ... or half the base, if that is more
    [1] * 33,                        # tiny deltas: _COMPACT_MAX_SEGMENTS
    [4095, 1],                       # the row threshold to the row
], ids=["min_rows", "half_base", "max_segments", "edge"])
def test_compaction_decision_is_the_one_the_footers_give(store, batches):
    from lazzaro_tpu.core.store import (_COMPACT_MAX_SEGMENTS,
                                        _COMPACT_MIN_ROWS)
    seen, nxt = set(), 0
    for n in batches:
        before = (_segments(store) if os.path.exists(
            store._manifest_path("nodes", "default"))
            else {"base": None, "segments": [], "gen": 0})
        foot = _footers(store, before)
        seg_rows = sum(foot[s] for s in before["segments"]) + n
        base_rows = foot[before["base"]] if before["base"] else 0
        if seg_rows >= max(_COMPACT_MIN_ROWS, base_rows // 2):
            want = "compact"
        elif len(before["segments"]) + 1 >= _COMPACT_MAX_SEGMENTS:
            want = "fold"
        else:
            want = "append"
        store.add_nodes([_node(i, dim=1) for i in range(nxt, nxt + n)])
        nxt += n
        after = _segments(store)
        got = ("compact" if after["segments"] == [] else
               "fold" if after["gen"] == before["gen"] + 2 else "append")
        assert got == want, (n, before, after)
        assert after["rows"] == _footers(store, after)
        seen.add(got)
    assert len(seen) > 1                 # the case drove a threshold
    assert len(store.get_nodes()) == nxt


def test_manifest_without_row_counts_is_read_once_and_completed(tmp_path):
    store = ArrowStore(str(tmp_path / "db"), telemetry=Telemetry())
    store.add_nodes([_node(i) for i in range(5)])
    store.compact()
    store.add_nodes([_node(5), _node(6)])
    man = _segments(store)
    counts = man.pop("rows")
    # as an older process left it: the files listed, no counts
    with open(store._manifest_path("nodes", "default"), "w") as f:
        json.dump(man, f)
    store.telemetry.reset()
    store.add_nodes([_node(7)])
    assert file_ops(store.telemetry)["read_meta"] == 2         # the base and the segment
    done = _segments(store)
    assert done["rows"] == {**counts, done["segments"][-1]: 1}
    store.add_nodes([_node(8)])                  # complete: no footer again
    assert file_ops(store.telemetry)["read_meta"] == 2
    # an older process appends a name without its count: that one is asked
    done = _segments(store)
    done["rows"].pop(done["segments"][-1])
    with open(store._manifest_path("nodes", "default"), "w") as f:
        json.dump(done, f)
    store.add_nodes([_node(9)])
    assert file_ops(store.telemetry)["read_meta"] == 3
    man = _segments(store)
    assert man["rows"] == _footers(store, man)
    assert len(store.get_nodes()) == 10
