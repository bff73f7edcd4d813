"""ArrowStore: round-trips, empty-list delete-all parity, versioning."""

import json
import os

import pytest

from lazzaro_tpu.core.store import ArrowStore
from lazzaro_tpu.utils.telemetry import Telemetry


@pytest.fixture()
def store(tmp_db):
    s = ArrowStore(tmp_db)
    yield s
    s.close()


def make_node(i, dim=4):
    emb = [0.0] * dim
    emb[i % dim] = 1.0
    return {"id": f"node_{i}", "content": f"content {i}", "embedding": emb,
            "type": "semantic", "salience": 0.5, "shard_key": "default",
            "child_ids": [], "metadata": {"k": i}}


def test_node_round_trip(store):
    store.add_nodes([make_node(1), make_node(2)], user_id="u1")
    rows = store.get_nodes(user_id="u1")
    assert {r["id"] for r in rows} == {"node_1", "node_2"}
    r1 = next(r for r in rows if r["id"] == "node_1")
    assert r1["content"] == "content 1"
    assert r1["metadata"] == {"k": 1}
    assert r1["child_ids"] == []


def test_add_nodes_upserts(store):
    store.add_nodes([make_node(1)], user_id="u1")
    updated = make_node(1)
    updated["content"] = "updated"
    store.add_nodes([updated], user_id="u1")
    rows = store.get_nodes(user_id="u1")
    assert len(rows) == 1
    assert rows[0]["content"] == "updated"


def test_user_isolation(store):
    store.add_nodes([make_node(1)], user_id="u1")
    store.add_nodes([make_node(2)], user_id="u2")
    assert {r["id"] for r in store.get_nodes(user_id="u1")} == {"node_1"}
    assert {r["id"] for r in store.get_nodes(user_id="u2")} == {"node_2"}
    assert store.get_all_users() == ["u1", "u2"]


def test_search_nodes_brute_force(store):
    store.add_nodes([make_node(0), make_node(1)], user_id="u1")
    ids = store.search_nodes([1.0, 0.0, 0.0, 0.0], user_id="u1", limit=1)
    assert ids == ["node_0"]


def test_delete_empty_list_deletes_all(store):
    # parity quirk: empty id list ⇒ delete ALL user rows (vector_store.py:143-145)
    store.add_nodes([make_node(1), make_node(2)], user_id="u1")
    store.add_nodes([make_node(3)], user_id="u2")
    store.delete_nodes([], user_id="u1")
    assert store.get_nodes(user_id="u1") == []
    assert len(store.get_nodes(user_id="u2")) == 1


def test_edges_round_trip_typed_ids(store):
    store.add_edges([
        {"source": "a", "target": "b", "weight": 0.7, "edge_type": "relates_to"},
        {"source": "a", "target": "b", "weight": 0.4, "edge_type": "causes"},
    ], user_id="u1")
    rows = store.get_edges(user_id="u1")
    # typed parallel edges must not collide (reference id='src_tgt' collides)
    assert len(rows) == 2


def test_profile_round_trip(store):
    store.save_profile({"data": {"preferences": "tea"}}, user_id="u1")
    assert store.load_profile(user_id="u1") == {"data": {"preferences": "tea"}}
    assert store.load_profile(user_id="nobody") is None


def test_version_bumps_on_every_write(store):
    v0 = store.get_latest_version()
    store.add_nodes([make_node(1)], user_id="u1")
    v1 = store.get_latest_version()
    store.save_profile({"x": 1}, user_id="u1")
    v2 = store.get_latest_version()
    assert v0 < v1 < v2


# ------------------------------- no write of content the file already holds
STEMS = {"profile": "profiles", "sys_meta": "sysmeta"}


def _sidecar(store, kind, user):
    with open(store._stem(STEMS[kind], user) + ".json") as f:
        return json.load(f)


def _skipped(store, kind):
    return store.telemetry.counters.get(
        'store.writes_skipped{kind="%s"}' % kind, 0)


def _writes(store):
    return store.telemetry.counters.get('store.file_ops{op="write"}', 0)


SIDECARS = {
    "profile": (ArrowStore.save_profile, ArrowStore.load_profile,
                {"data": {"preferences": "tea"}, "last_updated": 1.5},
                {"data": {"preferences": "mate"}, "last_updated": 2.5}),
    "sys_meta": (ArrowStore.save_sys_meta, ArrowStore.load_sys_meta,
                 {"decay_pass": 1, "node_counter": 7},
                 {"decay_pass": 2, "node_counter": 7}),
}


@pytest.fixture()
def counted(tmp_db):
    s = ArrowStore(tmp_db, telemetry=Telemetry())
    yield s
    s.close()


@pytest.mark.parametrize("kind", sorted(SIDECARS))
def test_saving_the_content_the_sidecar_holds_is_no_file_operation(
        counted, kind):
    save, load, first, second = SIDECARS[kind]
    save(counted, first, user_id="u1")
    v1, on_disk = counted.get_latest_version(), _sidecar(counted, kind, "u1")
    counted.telemetry.reset()
    save(counted, dict(first), user_id="u1")          # an equal copy
    assert counted.telemetry.counters == {
        'store.writes_skipped{kind="%s"}' % kind: 1}
    assert _sidecar(counted, kind, "u1") == on_disk
    assert counted.get_latest_version() == v1
    # the same content for ANOTHER user, or other content, is written
    save(counted, first, user_id="u2")
    save(counted, second, user_id="u1")
    assert _writes(counted) == 4 and _skipped(counted, kind) == 1
    assert load(counted, "u1") == second and load(counted, "u2") == first
    assert counted.get_latest_version() == v1 + 2


def test_profile_updated_at_is_the_time_of_the_last_change(counted,
                                                           monkeypatch):
    from lazzaro_tpu.core import store as store_mod
    clock = iter([100.0, 200.0, 300.0])
    monkeypatch.setattr(store_mod.time, "time", lambda: next(clock))
    _, _, first, second = SIDECARS["profile"]
    counted.save_profile(first, user_id="u")
    counted.save_profile(first, user_id="u")           # skipped: no stamp
    assert _sidecar(counted, "profile", "u")["updated_at"] == 100.0
    counted.save_profile(second, user_id="u")
    assert _sidecar(counted, "profile", "u") == {
        "user_id": "u", "data": second, "updated_at": 200.0}


@pytest.mark.parametrize("kind", sorted(SIDECARS))
def test_what_a_load_read_counts_as_held_and_a_fresh_instance_writes(
        tmp_db, kind):
    save, load, first, second = SIDECARS[kind]
    a = ArrowStore(tmp_db, telemetry=Telemetry())
    save(a, first, user_id="u")
    # a new instance knows nothing of the file: it writes
    b = ArrowStore(tmp_db, telemetry=Telemetry())
    save(b, first, user_id="u")
    assert (_writes(b), _skipped(b, kind)) == (2, 0)
    # one that LOADED the content holds it ...
    c = ArrowStore(tmp_db, telemetry=Telemetry())
    assert load(c, "u") == first
    save(c, first, user_id="u")
    assert (_writes(c), _skipped(c, kind)) == (0, 1)
    # ... until a load finds the file gone
    os.unlink(c._stem(STEMS[kind], "u") + ".json")
    assert not load(c, "u")
    save(c, first, user_id="u")
    assert (_writes(c), _skipped(c, kind)) == (2, 1)
    assert load(a, "u") == first


@pytest.mark.parametrize("table", ["nodes", "edges"])
def test_delete_all_forgets_what_the_sidecars_hold(counted, table):
    counted.add_nodes([make_node(1)], user_id="u1")
    for kind, (save, _, first, _) in SIDECARS.items():
        save(counted, first, user_id="u1")
        save(counted, first, user_id="u2")
    # the reference's delete-all-then-rewrite: what follows writes anew
    getattr(counted, f"delete_{table}")([], user_id="u1")
    counted.telemetry.reset()
    for kind, (save, _, first, _) in SIDECARS.items():
        save(counted, first, user_id="u1")
        save(counted, first, user_id="u2")         # another user's: held
        assert _skipped(counted, kind) == 1
    assert _writes(counted) == 4                   # two sidecars, two bumps
