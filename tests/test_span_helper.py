"""``Telemetry.span`` — the ONE way the program times a region (PR 25; tier-1,
CPU): nesting and the thread-local parent, the timer it records, what
``enabled=False`` keeps, which span a compilation is charged to, and one
count per file operation at the store's and the journals' funnels."""

import inspect
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lazzaro_tpu.core.store import ArrowStore, _atomic_write
from lazzaro_tpu.native import WriteAheadLog
from lazzaro_tpu.reliability import IngestJournal
from lazzaro_tpu.utils import telemetry as T
from lazzaro_tpu.utils.telemetry import (REGISTRY, Span, Telemetry,
                                         current_span)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helper
def test_nesting_names_the_enclosing_span_as_parent():
    tel = Telemetry()
    assert current_span() is None
    with tel.span("api.end_conversation") as outer:
        assert outer.parent is None and current_span() is outer
        with tel.span("store.save") as mid:
            with tel.span("store.io") as inner:
                assert (mid.parent, inner.parent) == ("api.end_conversation",
                                                      "store.save")
                assert current_span() is inner
            assert current_span() is mid
        with tel.span("store.load") as sibling:
            assert sibling.parent == "api.end_conversation"
    assert current_span() is None


def test_the_stack_is_per_thread():
    tel = Telemetry()
    seen = {}
    inside = threading.Event()
    go = threading.Event()

    def worker():
        seen["before"] = current_span()
        with tel.span("sched.idle") as s:
            seen["parent"] = s.parent
            inside.set()
            go.wait(timeout=10)
        seen["after"] = current_span()

    with tel.span("api.switch_user") as mine:
        t = threading.Thread(target=worker)
        t.start()
        assert inside.wait(timeout=10)
        assert current_span() is mine          # the worker's span is not mine
        go.set()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen == {"before": None, "parent": None, "after": None}


@pytest.mark.parametrize("kw,key", [
    ({}, "index.pack_ms"),
    ({"timer": "serve.decode_ms"}, "serve.decode_ms"),
    ({"timer": "serve.dispatch_ms", "labels": {"mode": "exact"}},
     'serve.dispatch_ms{mode="exact"}'),
    ({"labels": {"kind": "x"}}, 'index.pack_ms{kind="x"}'),
])
def test_the_timer_is_recorded_under_its_name_and_labels(kw, key):
    tel = Telemetry()
    with tel.span("index.pack", **kw):
        pass
    with tel.span("index.pack", **kw):
        pass
    assert list(tel.timers) == [key]
    a, b = tel.timers[key]
    assert 0.0 <= a < 1000.0 and 0.0 <= b < 1000.0


def test_a_raising_body_still_pops_the_stack_and_records():
    tel = Telemetry()
    with pytest.raises(KeyError):
        with tel.span("write.apply"):
            with tel.span("store.add"):
                raise KeyError("boom")
    assert current_span() is None
    assert sorted(tel.timers) == ["store.add_ms", "write.apply_ms"]


def test_disabled_registry_skips_the_timer_and_keeps_the_span():
    tel = Telemetry(enabled=False)
    with tel.span("index.stage") as s:
        assert current_span() is s and s.parent is None
    assert tel.snapshot() == {"timers": {}, "counters": {}, "gauges": {}}


def test_the_helper_is_a_class_not_a_generator():
    tel = Telemetry()
    assert not inspect.isgeneratorfunction(Telemetry.span)
    s = tel.span("x")
    assert isinstance(s, Span) and isinstance(s, jax.profiler.TraceAnnotation)
    assert not inspect.isgenerator(s) and not hasattr(s, "gi_frame")
    assert inspect.isfunction(Span.__enter__) and inspect.isfunction(Span.__exit__)
    assert not hasattr(T, "timed") and not hasattr(Telemetry, "summary")


def test_spans_reach_the_profilers_trace_enabled_or_not(tmp_path):
    """The annotation is what "on" means: started, the profiler holds every
    span under ``lz.<name>`` on its clock, whatever ``enabled`` says."""
    sys.path.insert(0, ROOT)
    from benchmark import tracing

    on, off = Telemetry(), Telemetry(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with on.span("api.end_conversation"):
            with off.span("store.save"):
                np.asarray(jnp.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
    spans = tracing.read_xplane(tracing.newest_xplane(str(tmp_path)))["spans"]
    got = {n: (s, s + d) for n, s, d in spans}
    assert set(got) == {"lz.api.end_conversation", "lz.store.save"}
    (a0, a1), (b0, b1) = got["lz.api.end_conversation"], got["lz.store.save"]
    assert a0 <= b0 <= b1 <= a1
    assert list(on.timers) == ["api.end_conversation_ms"] and not off.timers


# ---------------------------------------------------------- compile events
class _HarnessStyleCompiles:
    """As benchmark/harness.py counts them."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == T.COMPILE_EVENT:
            self.count += 1

    def close(self):
        jax._src.monitoring.unregister_event_duration_listener(self._on)


@pytest.fixture()
def compiles():
    c = _HarnessStyleCompiles()
    yield c
    c.close()


def test_compile_events_name_the_span_that_recompiled(compiles):
    tel = Telemetry()

    @jax.jit
    def f(x):
        return (x * 3 + 1).sum()

    with tel.span("index.stage"):
        with tel.span("dispatch.launch"):
            f(jnp.ones((5,))).block_until_ready()
    first = compiles.count
    assert first >= 1
    assert tel.counters == {'compile.events{span="dispatch.launch"}': first}
    assert tel.timer_count("compile.ms") == first
    with tel.span("dispatch.launch"):
        f(jnp.ones((5,))).block_until_ready()       # warm: no event
    assert compiles.count == first
    with tel.span("write.decay"):
        f(jnp.ones((6,))).block_until_ready()       # a new shape recompiles
    forced = compiles.count - first
    assert forced >= 1
    assert tel.counters['compile.events{span="write.decay"}'] == forced
    assert tel.counter_total("compile.events") == compiles.count


def test_a_compile_outside_any_span_goes_to_the_default_registry(compiles):
    before = REGISTRY.counters.get('compile.events{span="none"}', 0)
    jax.jit(lambda x: x - 7)(jnp.ones((3,))).block_until_ready()
    assert compiles.count >= 1
    assert (REGISTRY.counters['compile.events{span="none"}'] - before
            == compiles.count)


def test_one_listener_for_the_process_however_many_registries():
    Telemetry(), Telemetry()
    ours = [cb for cb in jax._src.monitoring.get_event_duration_listeners()
            if cb is T._on_compile]
    assert len(ours) == 1


# ------------------------------------------------------------- file funnels
def _ops(tel):
    return {T.split_key(k)[1]: v for k, v in tel.counters.items()
            if k.startswith("store.file_ops")}


def test_atomic_write_counts_one_operation_and_its_bytes(tmp_path):
    tel = Telemetry()
    _atomic_write(str(tmp_path / "a.json"), b"12345", tel)
    _atomic_write(str(tmp_path / "a.json"), b"123", tel)
    assert _ops(tel) == {'{op="write"}': 2}
    assert tel.counters["store.bytes_written"] == 8
    assert tel.timer_count("store.io_ms") == 2


def test_store_funnels_bump_once_per_call(tmp_path):
    tel = Telemetry()
    store = ArrowStore(str(tmp_path / "db"), telemetry=tel)
    assert _ops(tel) == {}
    store.save_sys_meta({"decay_pass": 1}, user_id="u")
    # the sidecar, then the version: read, bump, write
    assert _ops(tel) == {'{op="write"}': 2, '{op="read_version"}': 1}
    assert store.load_sys_meta("u") == {"decay_pass": 1}
    assert store.load_profile("u") is None
    assert _ops(tel)['{op="read_json"}'] == 2
    tel.reset()
    store.add_nodes([{"id": "n1", "content": "c", "embedding": [1.0, 0.0]}],
                    user_id="u")
    # manifest read (none yet), segment + manifest (with the segment's row
    # count: no footer is read for the compaction decision) + version
    # written, the version read
    assert _ops(tel) == {'{op="read_manifest"}': 1, '{op="write"}': 3,
                         '{op="read_version"}': 1}
    assert tel.counters["store.commits"] == 1
    tel.reset()
    assert [n["id"] for n in store.get_nodes("u")] == ["n1"]
    assert _ops(tel) == {'{op="read_manifest"}': 1, '{op="read_table"}': 1}
    tel.reset()
    store.delete_nodes([], user_id="u")
    assert _ops(tel)['{op="unlink"}'] == 3     # segment, manifest, legacy file
    assert tel.timer_count("store.io_ms") == sum(_ops(tel).values())


def _written(store, monkeypatch):
    """The base names ``store`` writes from here on, in order."""
    names, write = [], store._write

    def record(path, data):
        names.append(os.path.basename(path))
        write(path, data)

    monkeypatch.setattr(store, "_write", record)
    return names


def test_commit_scope_bumps_the_version_once_after_the_last_write(
        tmp_path, monkeypatch):
    tel = Telemetry()
    store = ArrowStore(str(tmp_path / "db"), telemetry=tel)
    names = _written(store, monkeypatch)
    with store.commit() as commit:
        store.add_nodes([{"id": "n1", "content": "c", "embedding": [1.0]}],
                        user_id="u")
        store.save_profile({"data": {}}, user_id="u")
        store.save_sys_meta({"decay_pass": 1}, user_id="u")
        assert commit.version is None and "VERSION" not in names
    # segment, manifest, two sidecars, THEN the version: one read, one write
    assert names[-1] == "VERSION" and names.count("VERSION") == 1
    assert _ops(tel) == {'{op="read_manifest"}': 1, '{op="write"}': 5,
                         '{op="read_version"}': 1}
    assert commit.version == store.get_latest_version() == 1
    assert tel.counters["store.commits"] == 1
    # a funnel outside any scope is a commit of its own again
    store.save_sys_meta({"decay_pass": 2}, user_id="u")
    assert store.get_latest_version() == 2
    assert tel.counters["store.commits"] == 2


def test_commit_scope_in_which_nothing_landed_is_no_operation(tmp_path):
    tel = Telemetry()
    store = ArrowStore(str(tmp_path / "db"), telemetry=tel)
    store.save_sys_meta({"decay_pass": 1}, user_id="u")
    tel.reset()
    with store.commit() as commit:
        store.add_nodes([], user_id="u")                   # no rows
        store.delete_edges(["e"], user_id="u")             # no such table
        store.save_sys_meta({"decay_pass": 1}, user_id="u")   # held already
    assert _ops(tel) == {'{op="read_manifest"}': 1}
    assert commit.version is None and "store.commits" not in tel.counters
    assert store.get_latest_version() == 1


def test_commit_scope_whose_second_write_raises_bumps_for_the_first(tmp_path):
    store = ArrowStore(str(tmp_path / "db"))
    with pytest.raises(TypeError):
        with store.commit() as commit:
            store.save_sys_meta({"decay_pass": 1}, user_id="u")
            store.save_sys_meta({"decay_pass": object()}, user_id="u")
    # the first write is on disk, so a poller has to hear of it
    assert commit.version == store.get_latest_version() == 1
    assert store.load_sys_meta("u") == {"decay_pass": 1}
    # and the scope is closed: the next funnel commits by itself
    store.save_sys_meta({"decay_pass": 2}, user_id="u")
    assert store.get_latest_version() == 2


def test_second_store_sees_the_version_rise_by_one_a_commit(tmp_path):
    writer = ArrowStore(str(tmp_path / "db"))
    poller = ArrowStore(str(tmp_path / "db"))
    for i in range(1, 4):
        with writer.commit():
            writer.add_nodes([{"id": f"n{i}", "content": "c",
                               "embedding": [1.0]}], user_id="u")
            writer.save_sys_meta({"decay_pass": i}, user_id="u")
        assert poller.get_latest_version() == i
    # the counter is the FILE's: the other instance's commit counts on
    with poller.commit() as commit:
        poller.save_profile({"data": {}}, user_id="v")
    assert commit.version == writer.get_latest_version() == 4


def test_commit_scopes_of_many_threads_lose_no_bump(tmp_path):
    """A scope is its thread's own; the bump is read-modify-write under the
    store's lock, so N commits leave VERSION at N and no two share one."""
    store = ArrowStore(str(tmp_path / "db"))
    versions, errors = [], []

    def writer(t):
        try:
            for i in range(5):
                with store.commit() as commit:
                    store.save_sys_meta({"decay_pass": i}, user_id=f"u{t}")
                    store.save_profile({"data": {"i": i}}, user_id=f"u{t}")
                versions.append(commit.version)
        except Exception as e:                  # pragma: no cover
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads)
    assert sorted(versions) == list(range(1, 81))
    assert store.get_latest_version() == 80


@pytest.mark.parametrize("native", [True, False])
def test_wal_funnels_bump_once_per_call(tmp_path, monkeypatch, native):
    if not native:
        monkeypatch.setattr("lazzaro_tpu.native.load", lambda: None)
    tel = Telemetry()
    wal = WriteAheadLog(str(tmp_path / "j.wal"), fsync=False, telemetry=tel)
    wal.append(b"abc")
    wal.append(b"defg")
    assert wal.replay() == [b"abc", b"defg"]
    wal.reset()
    assert wal.replay() == []
    assert _ops(tel) == {'{op="wal_append"}': 2, '{op="wal_replay"}': 2,
                         '{op="wal_reset"}': 1}
    assert tel.counters["store.bytes_written"] == 7
    assert tel.timer_count("journal.io_ms") == 5


def test_ingest_journal_passes_its_registry_to_the_log(tmp_path):
    tel = Telemetry()
    j = IngestJournal(str(tmp_path / "i.wal"), telemetry=tel)
    assert _ops(tel) == {'{op="wal_replay"}': 1}          # opening replays
    seq = j.append([{"content": "a fact"}])
    j.commit(seq)                                 # nothing pending: a reset
    assert _ops(tel) == {'{op="wal_replay"}': 1, '{op="wal_append"}': 1,
                         '{op="wal_reset"}': 1}
