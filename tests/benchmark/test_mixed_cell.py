"""Readers beside a writer (PR 43): the generator's fourth loop ``mixed`` — an
installed stock, open-loop readers over it, one conversation writer over
tenants of its own, in ONE window — and its cell ``share.mixed``. Sound runs
are correct on both sides; the int8 control and a timed path broken on either
side come out NOT correct; a mixed mix is data (a ``lme5m`` one arrives as a
file and appended entries). CPU debug runs at tiny sizes; no number read here
is a device number."""

import hashlib
import json
import os
import shutil
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
import faults  # noqa: E402
from benchmark import corpus, harness, loadgen, mix_metrics  # noqa: E402

CELL = "share.mixed"
M = harness.manifest(ROOT)
STREAMS = json.load(open(os.path.join(HERE, "data", "streams_seed7.json")))
E2E = {"search_p50_ms", "search_p95_ms", "ingest_mem_per_s", "setup_s"}
READ_SIDE = {"search_p50_ms", "search_p95_ms"}
WRITE_SIDE = {"ingest_mem_per_s"}
MIX_READERS = ("mix.writer_active_pct", "mix.read_p50_in_write_ms")


def run(**kw):
    return contracts.debug_run(CELL, kw.pop("seed", 43), ROOT, **kw)


def _reported(kind):
    cell = harness.cell_files(CELL, ROOT)[0]
    return {m["name"] for m in harness.metrics_of(cell, kind, ROOT)}


# ------------------------------------------------------------ the cell's runs

def test_both_sides_are_attempted_and_the_three_metrics_reported():
    res = run()
    assert res["correct"] is True and res["failed"] == 0
    _, _, mix = harness.cell_files(CELL, ROOT, debug=True)
    reads = int(round(mix["readers"]["rate_rps"] * 0.6))
    assert res["attempted"] > reads            # and conversations beside them
    assert set(res["metrics"]) == _reported("end_to_end") <= E2E
    # a metric of each side (which of the readers' two: PERF.md section 2)
    assert "ingest_mem_per_s" in res["metrics"] and READ_SIDE & set(res["metrics"])
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())


def test_traced_run_reports_every_entry_a_cpu_can_read_and_compiles_nothing():
    res = run(traced=True, seconds=1.0)
    assert res["correct"] is True
    want = {n for n in _reported("per_layer")
            if not n.startswith(("kernel.", "device.idle"))}
    # under 200 reads at the debug rate: their median says None here
    assert want - set(res["metrics"]) <= set(MIX_READERS[1:])
    assert [v["value"] for n, v in res["metrics"].items()
            if n.startswith("device.compiles")] == [0.0]
    # back to back: the writer is inside a conversation all the window long
    assert 90.0 < res["metrics"]["mix.writer_active_pct"]["value"] <= 100.0
    assert res["metrics"]["api.end_conversation_p50_ms"]["value"] > 0.0
    assert res["metrics"]["sched.batch_requests_mean.mix"]["value"] >= 1.0


@pytest.mark.parametrize("dropped,kept,gone", [
    ("writer", READ_SIDE, WRITE_SIDE), ("readers", WRITE_SIDE, READ_SIDE)])
def test_an_arm_without_one_group_reports_the_other_side_alone(dropped, kept, gone):
    res = run(mix_override={dropped: None})
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    names = set(res["metrics"])
    assert kept & _reported("end_to_end") <= names and not gone & names


def test_wrong_tenant_mask_fails_it_on_the_read_side():
    res = run(sabotage=contracts.wrong_tenant_mask)
    assert res["correct"] is False
    assert res["compared"]["foreign_ids"]["value"] > 0


def test_a_fact_dropped_before_the_ingest_fails_it_by_the_node_count():
    res = run(sabotage=faults.fact_dropped)
    assert res["correct"] is False
    assert res["compared"]["count_errors"]["value"] > 0
    assert res["compared"]["foreign_ids"]["value"] == 0


def test_a_window_tenant_s_row_served_to_a_stock_tenant_is_foreign():
    res = run(sabotage=faults.window_row_served)
    assert res["correct"] is False
    # every compared read's first hit is another tenant's
    reads = res["attempted"] - 20                  # conversations are fewer
    assert res["compared"]["foreign_ids"]["value"] >= min(reads, 8 * 8) / 2


def test_set_up_s_writes_to_the_index_s_privates_fail_by_name_on_a_rename():
    """``deploy.presize_csr`` / ``beside_a_reader`` write private attributes
    of the index (the program has no public warm-up of them): every sound
    run above found them; an index that renamed one is refused by name."""
    from benchmark import deploy

    class Renamed:
        _csr_dirty, _SOLE_REFS = True, 3

    assert deploy._with_privates(Renamed(), "_SOLE_REFS").__class__ is Renamed
    with pytest.raises(AttributeError, match="Renamed has no _csr_pad_hwm: "):
        deploy._with_privates(Renamed(), "_csr_pad_hwm", "_csr_dirty")


# ----------------------------------------------------- a mixed mix is refused

def _groups(**change):
    _, cfg, mix = harness.cell_files(CELL, ROOT, debug=True)
    for k, v in change.items():
        mix[k] = v
    return harness.mixed_groups(cfg, mix)


def test_a_mixed_mix_is_refused_by_name():
    stock, readers, writer = _groups()
    assert stock["rows"] == 1024 and readers["rate_rps"]
    assert writer["window_tenants"] == [16, 150]
    with pytest.raises(ValueError, match="'mixed-open-writer' has neither "
                       "'readers' nor 'writer'"):
        _groups(readers=None, writer=None)
    with pytest.raises(ValueError, match=r"stock.rows 1000 is no multiple of "
                       r"the configuration's fill_block_rows 1024"):
        _groups(stock={"rows": 1000, "tenants": 8})
    with pytest.raises(ValueError, match="inside its 2048 rows"):
        _groups(stock={"rows": 4096, "tenants": 8})
    _, _, mix = harness.cell_files(CELL, ROOT, debug=True)
    with pytest.raises(ValueError, match="outside the stock's 8"):
        _groups(writer=dict(mix["writer"], window_tenants=[4, 20]))


def test_an_unknown_loop_is_refused_with_the_four_the_generator_has(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "mixes", "mixed-open-writer.json")
    mix = harness.load_json(path)
    mix["loop"] = "bursts"
    json.dump(mix, open(path, "w"))
    with pytest.raises(ValueError, match=r"'bursts' is none of open, closed, "
                       r"conversations, mixed"):
        harness.run_cell(CELL, 1, 0.4, False, root=root, debug=True)
    assert list(harness.LOOPS) == ["open", "closed", "conversations", "mixed"]


# --------------------------------------------------- the loop, a fake clock

class _Clock:
    """Moves only when a thread sleeps or works; two threads share it."""

    def __init__(self):
        self.now, self.lock = 100.0, threading.Lock()

    def __call__(self):
        return self.now

    def sleep(self, s):
        with self.lock:
            self.now += s
        threading.Event().wait(0.0005)          # let the other thread run


class _Now:
    """A request's handle that is answered as it is submitted."""

    def __init__(self, value):
        self.value = value

    def add_done_callback(self, fn):
        fn(self)

    def cancelled(self):
        return False

    def exception(self):
        return None

    def result(self):
        return self.value


class _Notes:
    def __init__(self):
        self.seen = []

    def __call__(self, name):
        self.seen.append((name, threading.current_thread().name))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _mixed(n_reads, gap, tenants, seconds, talk=0.03):
    clock, notes = _Clock(), _Notes()
    reads = (lambda r: _Now(r), list(range(n_reads)), np.arange(n_reads) * gap,
             np.arange(n_reads) % 5 == 0, 5.0)
    writes = (lambda t: clock.sleep(talk), tenants)
    s, log = loadgen.run_mixed(reads if n_reads else None,
                               writes if tenants is not None else None,
                               seconds, notes, clock=clock, sleep=clock.sleep)
    return s, log, notes


def test_run_mixed_both_sides_share_t0_and_the_writer_keeps_the_deadline():
    s, log, notes = _mixed(50, 0.004, range(100), 0.2)
    assert s.t0 == log.t0 == 100.0
    assert s.ok.all() and sorted(s.answers) == list(range(0, 50, 5))
    assert (s.due == 100.0 + np.arange(50) * 0.004).all()
    # no conversation is started once the seconds have passed; the one
    # started inside them is finished and counted
    assert log.starts and max(log.starts) < log.t0 + 0.2 and not log.ran_out
    assert len(log.starts) == len(log.tenants) == len(log.seconds) < 100
    assert log.tenants == list(range(len(log.tenants)))
    # back to back, and the writer's own time runs from the window's start
    # to the end of its last conversation
    assert all(b - a >= 0.03 - 1e-9 for a, b in zip(log.starts, log.starts[1:]))
    assert log.t1 >= log.starts[-1] + 0.03 - 1e-9 and log.t1 - log.t0 >= 0.2
    # ONE window for both, opened on the caller's thread; the conversations
    # are annotated on the writer's
    assert [n for n in notes.seen if n[0] == "bench.window"] == [
        ("bench.window", threading.current_thread().name)]
    talks = [n for n in notes.seen if n[0] == "bench.conversation"]
    assert len(talks) == len(log.tenants) and {n[1] for n in talks} == {"bench-writer"}


def test_run_mixed_ran_out_closes_the_writer_and_not_the_readers():
    s, log, _ = _mixed(200, 0.004, range(3), 0.8)
    assert log.ran_out and log.tenants == [0, 1, 2]
    assert len(s.ok) == 200 and s.ok.all()           # every read still went
    assert s.sent[-1] >= s.t0 + 199 * 0.004


def test_run_mixed_with_one_side_alone_and_a_failed_conversation():
    s, log, _ = _mixed(20, 0.004, None, 0.2)
    assert log is None and s.ok.all()
    s, log, _ = _mixed(0, 0.004, range(4), 1.0)
    assert s is None and log.ran_out and len(log.seconds) == 4
    clock = _Clock()

    def failing(t):
        clock.sleep(0.01)
        if t == 1:
            raise OSError("disk")
    _, log = loadgen.run_mixed(None, (failing, range(3)), 1.0, _Notes(),
                               clock=clock, sleep=clock.sleep)
    assert np.isnan(log.seconds[1]) and len(log.errors) == 1
    assert log.tenants == [0, 1, 2] and len(log.starts) == 3


def test_the_writer_s_rate_is_over_all_its_time_whatever_the_loop():
    """A stall BETWEEN conversations counts: ``ingest_mem_per_s`` divides by
    the writer's time from the window's start to the end of its last
    conversation, in the mixed loop as in ``share.ingest``'s."""
    cell, cfg, mix = harness.cell_files(CELL, ROOT, debug=True)
    writer = harness.Writer(cfg, mix["writer"], 7)
    log = loadgen.ConversationLog()
    log.t0, log.t1 = 50.0, 54.0
    log.tenants, log.starts = [16, 17, 18], [50.0, 50.5, 53.5]   # 2.5 s idle
    log.seconds = [0.5, 0.5, 0.5]
    r = harness.Run(cell, cfg, mix, 7, 4.0, False, ROOT)
    assert writer.note(r, log) == [16, 17, 18]
    assert r.window_s == 4.0 and float(r.conversation_s.sum()) == 1.5
    assert _read("ingest_mem_per_s", r) == 3 * writer.facts / 4.0
    ing_cell, ing_cfg, ing_mix = harness.cell_files("share.ingest", ROOT, debug=True)
    r2 = harness.Run(ing_cell, ing_cfg, ing_mix, 7, 4.0, False, ROOT)
    harness.Writer(ing_cfg, ing_mix, 7).note(r2, log)
    assert r2.window_s == r.window_s


# --------------------------------------------------- the two mix.* readers

def _hand_run(due, lat, spans, seconds=10.0):
    cell, cfg, mix = harness.cell_files(CELL, ROOT)
    r = harness.Run(cell, cfg, mix, 1, seconds, True, ROOT)
    r.due_s, r.latency_ms = np.asarray(due, float), np.asarray(lat, float)
    r.conversation_spans = np.asarray(spans, float).reshape(-1, 2)
    return r


def _read(name, r):
    return harness.reader(name, ROOT)(r)


def test_mix_readers_split_the_reads_by_when_they_fell_due():
    due = np.arange(1000) * 0.01                      # 0 .. 9.99 s
    lat = np.where((due % 2.0) < 1.0, 8.0, 3.0)       # slow in even seconds
    spans = [(a, a + 1.0) for a in range(0, 10, 2)]   # conversations there
    r = _hand_run(due, lat, spans)
    assert _read("mix.writer_active_pct", r) == pytest.approx(50.0)
    assert _read("mix.read_p50_in_write_ms", r) == 8.0
    assert int(mix_metrics.in_write(r).sum()) == 500
    # a span's end is outside it, its start inside; a conversation that runs
    # past the window's seconds counts up to them
    r = _hand_run([0.999, 1.0, 2.0], [1, 1, 1], [(0.0, 1.0), (2.0, 13.0)])
    assert mix_metrics.in_write(r).tolist() == [True, False, True]
    assert _read("mix.writer_active_pct", r) == pytest.approx(90.0)


@pytest.mark.parametrize("spans,inside", [
    ([(0.0, 10.0)], 5.0),                        # every read inside
    ([(20.0, 30.0)], None),                      # every read outside
    ([(0.0, 1.99)], None),                       # 199 inside: under 200
    ([(0.0, 2.0)], 5.0)])                        # 200 inside, 800 outside
def test_the_median_inside_conversations_says_none_under_200_reads(spans, inside):
    r = _hand_run(np.arange(1000) * 0.01, np.full(1000, 5.0), spans)
    assert _read("mix.read_p50_in_write_ms", r) == inside


def test_mix_readers_say_none_without_a_writer_or_without_readers():
    no_writer = _hand_run(np.arange(500) * 0.01, np.ones(500), [])
    no_readers = _hand_run([], [], [(0.0, 4.0)])
    for r in (no_writer, no_readers):
        assert _read("mix.read_p50_in_write_ms", r) is None
    assert _read("mix.writer_active_pct", no_writer) is None
    assert _read("mix.writer_active_pct", no_readers) == pytest.approx(40.0)


# ----------------------------- the accepted cells send what they sent before

def _digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("cell", sorted(STREAMS))
def test_an_accepted_cell_s_plan_and_request_stream_are_the_parent_s(cell):
    """``data/streams_seed7.json`` was written by the PARENT's harness
    (commit 7232ed6) for every accepted cell at its debug sizes, seed 7."""
    want = STREAMS[cell]
    _, cfg, mix = harness.cell_files(cell, ROOT, debug=True)
    if mix["loop"] == "conversations":
        order = harness.Writer(cfg, mix, 7).order
        assert len(order) == want["n"]
        assert [int(t) for t in order[:6]] == want["order_head"]
        assert _digest(order.astype(np.int64)) == want["order"]
        return
    seen = {}

    def make(queries, tenants, k, boost):
        seen.update(
            query_head=np.asarray(queries, np.float32)[:3, :4].ravel(),
            query_shape=list(np.shape(queries)),
            tenants=_digest(np.asarray(tenants, np.int64)), k=int(k),
            boost=None if boost is None else _digest(np.asarray(boost, bool)))
        return [(int(t), k) for t in tenants]
    starts = corpus.tenant_starts(cfg["rows"], cfg["tenants"])
    plan = harness.ServePlan(cfg, mix, 7, 2.0, starts, 0, make)
    assert len(plan.tenant) == want["n"]
    assert (None if plan.due is None else
            _digest(np.round(plan.due * 1e9).astype(np.int64))) == want["due"]
    assert _digest(plan.tenant.astype(np.int64)) == want["tenant"]
    assert _digest(plan.fact.astype(np.int64)) == want["fact"]
    assert _digest(np.flatnonzero(plan.keep).astype(np.int64)) == want["keep"]
    assert [int(t) for t in plan.check_tenants] == want["check_tenants"]
    handed = want["handed"]
    np.testing.assert_allclose(seen.pop("query_head"),
                               handed.pop("query_head"), rtol=0, atol=1e-6)
    assert seen == handed


def test_the_accepted_cells_and_their_mixes_are_all_in_the_golden():
    assert set(STREAMS) == {w["name"] for w in M["workloads"]} - {CELL}


# ------------------------------------- a lme5m mixed mix arrives as data

def test_a_later_pr_s_writer_over_lme5m_is_a_mix_file_and_appended_entries(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for p in M["paths"]:
        shutil.copytree(os.path.join(ROOT, p), os.path.join(root, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, names in os.walk(root):
        for f in names:
            if f != "BENCHMARK.json":
                before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    mix = {"name": "fill-writer", "loop": "mixed", "why": "w",
           "stock": {"rows": 3750000, "tenants": 375},
           "writer": {"facts_per_conversation": 100, "first_fact": 1,
                      "window_tenants": [500, 600], "warm_tenants": [1200, 2],
                      "k": 5, "check_tenants": 12, "check_facts": 8},
           "debug": {"stock": {"rows": 3072, "tenants": 6},
                     "writer": {"facts_per_conversation": 20,
                                "window_tenants": [8, 40],
                                "warm_tenants": [60, 2], "check_tenants": 3,
                                "check_facts": 4}}}
    json.dump(mix, open(os.path.join(root, "benchmark/mixes/fill-writer.json"), "w"))
    m = harness.manifest(root)
    m["workloads"].append({"name": "fill.ingest", "config": "lme5m",
                           "traffic": "fill-writer", "chips": 1, "why": "w"})
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ("ingest_mem_per_s", "dispatch.p50_ms.ing",
                         "api.end_conversation_p50_ms", "device.compiles.ing"):
            e["workloads"].append("fill.ingest")
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))

    contracts.manifest_wide(root)
    seen = {}
    res = contracts.debug_run("fill.ingest", 44, root, traced=True,
                              sabotage=lambda ms: seen.update(
                                  stock=len(ms.index.id_to_row),
                                  tenants=len(ms.index.tenant_nodes)))
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    # three of lme5m's four debug blocks are the stock's, two warm-up
    # conversations of 20 facts beside them
    assert seen == {"stock": 3072 + 2 * 20, "tenants": 6 + 2}
    assert set(res["metrics"]) == {"dispatch.p50_ms.ing", "device.compiles.ing",
                                   "api.end_conversation_p50_ms"}
    line = contracts.debug_run("fill.ingest", 45, root)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ingest_mem_per_s", "setup_s"}
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} had to be edited"
