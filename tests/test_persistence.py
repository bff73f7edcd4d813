"""Persistence + cross-instance sync (reference test_v03_migration.py pattern,
SURVEY §4(e)): two MemorySystem instances sharing one store dir — save in A,
version-poll + reload in B. This is the framework's "multi-node without a real
cluster" approximation; the real multi-chip path is tested via the mesh tests."""

import pytest

from lazzaro_tpu import MemorySystem

from tests.fakes import (MockEmbedder, MockLLM, extraction_response,
                         file_ops)

FACT = {"content": "User plays the violin", "type": "semantic",
        "salience": 0.8, "topic": "personal"}


def make_ms(tmp_db, load=False, **kw):
    llm = MockLLM(sniffers={
        "Extract distinct, atomic facts": extraction_response([FACT]),
    })
    defaults = dict(enable_async=False, auto_consolidate=False,
                    load_from_disk=load, db_dir=tmp_db,
                    llm_provider=llm, embedding_provider=MockEmbedder(),
                    verbose=False)
    defaults.update(kw)
    return MemorySystem(**defaults)


def ingest_one(ms):
    ms.start_conversation()
    ms.add_to_short_term("I play violin", "episodic", 0.7)
    ms.end_conversation()


def test_save_restart_reload(tmp_db):
    a = make_ms(tmp_db)
    ingest_one(a)
    assert a.buffer.size()[0] == 1
    a.close()

    b = make_ms(tmp_db, load=True)
    assert b.buffer.size()[0] == 1
    node = b.buffer.get_node("node_1")
    assert node.content == FACT["content"]
    assert node.shard_key == "personal"
    # node_counter restored from max node_N id
    assert b.node_counter == 1
    # the reloaded node is searchable through the arena
    results = b.search_memories("User plays the violin")
    assert [n.id for n in results] == ["node_1"]
    b.close()


def test_cross_instance_version_sync(tmp_db):
    a = make_ms(tmp_db)
    b = make_ms(tmp_db, load=True)
    assert b.buffer.size()[0] == 0
    assert b.check_for_updates() is False  # nothing new yet

    ingest_one(a)  # A writes; store version bumps

    assert b.check_for_updates() is True
    assert b.buffer.size()[0] == 1
    assert b.buffer.get_node("node_1").content == FACT["content"]
    a.close()
    b.close()


def test_switch_user_isolates_graphs(tmp_db):
    ms = make_ms(tmp_db)
    ingest_one(ms)
    assert ms.buffer.size()[0] == 1

    ms.switch_user("bob")
    assert ms.user_id == "bob"
    assert ms.buffer.size()[0] == 0
    assert ms.search_memories("violin") == []

    ms.switch_user("default")
    assert ms.buffer.size()[0] == 1
    assert [n.id for n in ms.search_memories("User plays the violin")] == ["node_1"]
    ms.close()


def test_save_load_state_json(tmp_db, tmp_path):
    ms = make_ms(tmp_db)
    ingest_one(ms)
    path = str(tmp_path / "snapshot.json")
    assert "saved" in ms.save_state(path)

    ms2 = make_ms(str(tmp_path / "db2"))
    assert "loaded" in ms2.load_state(path)
    assert ms2.buffer.size()[0] == 1
    assert ms2.node_counter == 1
    # arena rebuilt: search works after snapshot load
    assert [n.id for n in ms2.search_memories("User plays the violin")] == ["node_1"]
    ms.close()
    ms2.close()


def test_eviction_deletes_from_store(tmp_db):
    facts = [{"content": f"User fact number {i} about topic {i}",
              "type": "semantic", "salience": 0.5, "topic": "personal"}
             for i in range(6)]
    llm = MockLLM(sniffers={
        "Extract distinct, atomic facts": extraction_response(facts)})
    ms = MemorySystem(enable_async=False, auto_consolidate=False,
                      load_from_disk=False, db_dir=tmp_db, max_buffer_size=3,
                      llm_provider=llm,
                      embedding_provider=MockEmbedder(dim=16),
                      verbose=False)
    ms.start_conversation()
    ms.add_to_short_term("many facts", "episodic", 0.7)
    ms.end_conversation()

    nodes, _ = ms.buffer.size()
    assert nodes == 3  # evicted down to the buffer limit
    stored = ms.store.get_nodes(user_id="default")
    assert len(stored) == 3
    ms.close()


# ------------------------------------------- a save is ONE commit (PR 40)
def test_each_save_is_one_commit_and_a_clean_one_is_none(tmp_db):
    a = make_ms(tmp_db, load=True)
    b = make_ms(tmp_db, load=True)
    assert a.store.get_latest_version() == 0
    ingest_one(a)              # consolidation's save, then the decay's
    assert a.telemetry.counters["store.commits"] == 3     # + the fresh node
    assert a._last_version == a.store.get_latest_version() == 3
    assert b.check_for_updates() is True
    assert b.buffer.get_node("node_1").content == FACT["content"]
    assert b.check_for_updates() is False
    # nothing is dirty: the save touches no file and the version stands
    before = dict(a.telemetry.counters)
    a._save_to_persistence()
    moved = {k: v - before.get(k, 0) for k, v in a.telemetry.counters.items()
             if v != before.get(k, 0)}
    assert moved == {'store.writes_skipped{kind="profile"}': 1,
                     'store.writes_skipped{kind="sys_meta"}': 1}
    assert a._last_version == 3 and b.check_for_updates() is False
    a.close()
    b.close()


def test_switch_user_saves_a_clean_tenant_without_a_file_operation(tmp_db):
    ms = make_ms(tmp_db, load=True)
    ingest_one(ms)
    ms.telemetry.reset()
    ms.switch_user("bob")
    # what is left is bob's load (two sidecars, the node manifest he has
    # not got, the version) and his journals
    ops = file_ops(ms.telemetry)
    assert {k: v for k, v in ops.items() if not k.startswith("wal_")} == {
        "read_json": 2, "read_manifest": 1, "read_version": 1}
    ms.close()


def test_restart_reloads_what_the_commit_scope_saved(tmp_db):
    a = make_ms(tmp_db)                  # first save: the full rewrite
    ingest_one(a)
    a.switch_user("bob")
    ingest_one(a)                        # bob's saves: incremental
    a.profile.data["preferences"] = "User likes strings"
    a._save_to_persistence()
    want = {u: (sorted(n["id"] for n in a.store.get_nodes(u)),
                a.store.load_sys_meta(u), a.store.load_profile(u))
            for u in ("default", "bob")}
    version = a.store.get_latest_version()
    a.close()

    b = make_ms(tmp_db, load=True)
    assert b.store.get_latest_version() == b._last_version == version
    for user in ("default", "bob"):
        b.switch_user(user)
        assert b.buffer.size()[0] == len(want[user][0]) == 1
        assert (sorted(n["id"] for n in b.store.get_nodes(user)),
                b.store.load_sys_meta(user),
                b.store.load_profile(user)) == want[user]
        assert [n.content for n in b.search_memories(
            "User plays the violin")] == [FACT["content"]]
    assert b.profile.data["preferences"] == "User likes strings"
    # the reloading instance wrote nothing on the way
    assert b.store.get_latest_version() == version
    b.close()
