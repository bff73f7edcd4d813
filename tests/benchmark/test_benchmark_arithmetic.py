"""The arithmetic the yardstick rests on: percentiles of all requests, a
generator that times from the due time and owns up to its lateness, the
trace reduction against a small recorded trace, the demand functions by
hand-computed values, and the comparison's limits."""

import json
import os
import sys
import threading
from concurrent.futures import Future
from contextlib import nullcontext

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import (corpus, files, harness, loadgen, peaks, readers,  # noqa: E402
                       reference, stats, tracing)

TRACE = json.load(open(os.path.join(HERE, "data", "small_trace.json")))
TRACE = {"devices": {k: [tuple(e) for e in v] for k, v in TRACE["devices"].items()},
         "spans": [tuple(e) for e in TRACE["spans"]]}


def _annotate(_name):
    return nullcontext()


# ------------------------------------------------------------- percentiles

def test_percentile_is_nearest_rank_over_all_samples():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50 and stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100 and stats.median([3, 1, 2]) == 2
    # one slow request in twenty IS the p95: nothing is trimmed
    assert stats.percentile([1.0] * 19 + [500.0], 95) == 1.0
    assert stats.percentile([1.0] * 18 + [500.0] * 2, 95) == 500.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


# --------------------------------------------------------------- generator

def test_every_seed_draws_the_same_population_in_another_order():
    a = loadgen.tenant_sequence(5000, 50, 0.99, np.random.default_rng(1))
    b = loadgen.tenant_sequence(5000, 50, 0.99, np.random.default_rng(2))
    assert not np.array_equal(a, b)
    assert np.array_equal(np.bincount(a, minlength=50), np.bincount(b, minlength=50))
    counts = loadgen.zipf_counts(5000, 50, 0.99)
    assert counts.sum() == 5000 and counts[0] == counts.max()
    assert counts[0] / counts[9] == pytest.approx(10 ** 0.99, rel=0.05)
    da = loadgen.poisson_schedule(2000, 400.0, np.random.default_rng(1))
    db = loadgen.poisson_schedule(2000, 400.0, np.random.default_rng(2))
    ga, gb = np.diff(da, prepend=0.0), np.diff(db, prepend=0.0)
    assert np.allclose(np.sort(ga), np.sort(gb)) and not np.allclose(ga, gb)
    assert da[-1] == pytest.approx(1999.5 / 400.0)
    assert np.std(ga) / np.mean(ga) == pytest.approx(1.0, abs=0.05)  # exponential


class _Clock:
    """The tests' own clock: it moves only when something sleeps or works."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


class _Answer:
    """A future that comes back ``at`` on the tests' clock."""

    def __init__(self, clock, at, value):
        self.clock, self.at, self.value = clock, at, value

    def add_done_callback(self, fn):
        now, self.clock.now = self.clock.now, self.at
        fn(self)
        self.clock.now = now

    def cancelled(self):
        return False

    def exception(self):
        return None

    def result(self):
        return self.value


class _ClockedScheduler:
    """Answers each request ``service_s`` after it was submitted, except that
    submitting request ``stall_at`` holds the caller up for ``stall_s``."""

    def __init__(self, clock, service_s, stall_at=None, stall_s=0.0):
        self.clock, self.service_s = clock, service_s
        self.stall_at, self.stall_s = stall_at, stall_s

    def submit(self, req):
        if req == self.stall_at:
            self.clock.sleep(self.stall_s)
        return _Answer(self.clock, self.clock.now + self.service_s, req)


def _open(n, gap, **stall):
    clock = _Clock()
    return loadgen.run_open(
        _ClockedScheduler(clock, 0.002, **stall).submit, list(range(n)),
        np.arange(n) * gap, np.arange(n) % 7 == 0, 5.0, _annotate,
        clock=clock, sleep=clock.sleep)


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    n, gap = 60, 0.004
    quiet = _open(n, gap)
    assert quiet.ok.all() and sorted(quiet.answers) == list(range(0, n, 7))
    assert np.allclose(quiet.sent, quiet.due)           # never late
    assert np.allclose(quiet.done - quiet.due, 0.002)   # the service time
    stalled = _open(n, gap, stall_at=10, stall_s=0.08)
    lat = (stalled.done - stalled.due) * 1e3
    late = (stalled.sent - stalled.due) * 1e3
    # request 10 is sent on time and answered 80 ms late; the 20 requests
    # that fall due meanwhile are sent late, and each is timed from when it
    # was DUE, not from when it was sent: the stall is in their latency and
    # in the lateness tail
    assert np.allclose(late[:11], 0) and np.allclose(lat[:10], 2)
    assert lat[10] == pytest.approx(82)
    assert np.allclose(late[11:31], 76 - 4 * np.arange(20))
    assert np.allclose(lat[11:], late[11:] + 2) and np.allclose(late[31:], 0)
    assert stats.percentile(lat, 95) == pytest.approx(70)
    assert stats.percentile(late, 95) == pytest.approx(64)
    assert (stalled.done - stalled.sent)[11:].max() == pytest.approx(0.002)


class _TimerScheduler:
    """Answers each request ``service_s`` after it was submitted, from a
    thread of its own, and notes what each calling thread sent."""

    def __init__(self, service_s):
        self.service_s, self.sent_by, self.overlapped = service_s, {}, False

    def submit(self, req):
        mine = self.sent_by.setdefault(threading.current_thread().name, [])
        self.overlapped |= any(not f.done() for _, f in mine)
        fut = Future()
        mine.append((req, fut))
        threading.Timer(self.service_s, fut.set_result, args=(req,)).start()
        return fut


def test_closed_loop_sends_the_next_when_the_last_returned():
    clients, seconds = 4, 0.25
    sched = _TimerScheduler(0.01)
    s = loadgen.run_closed(sched.submit, list(range(7)), clients,
                           np.arange(7) < 2, seconds, 2.0, _annotate)
    assert s.ok.all() and sorted(s.answers) == [0, 1]
    assert (s.done - s.sent >= 0.0099).all()
    # no client can send faster than its answers come; each sent at least one
    assert clients <= len(s.done) <= clients * (seconds / 0.01 + 1)
    # client c walks requests c, c + clients, ... and never has two in flight
    assert not sched.overlapped and len(sched.sent_by) == clients
    for c in range(clients):
        reqs = [r for r, _ in sched.sent_by[f"bench-client-{c}"]]
        assert reqs == [(c + clients * j) % 7 for j in range(len(reqs))]
    assert sum(len(v) for v in sched.sent_by.values()) == len(s.done)


def test_conversation_writer_counts_whole_conversations_to_the_windows_end():
    clock = _Clock()
    log = loadgen.run_conversations(lambda t: clock.sleep(0.03), range(100),
                                    0.2, _annotate, clock=clock)
    # starts at 0, 0.03, ... 0.18; the one started inside the window is
    # finished and counted, and the window runs to its end
    assert log.tenants == list(range(7)) and not log.ran_out
    assert np.allclose(log.seconds, 0.03)
    assert log.t1 - log.t0 == pytest.approx(0.21)
    # a writer that gets through all the tenants closes the window early
    clock = _Clock()
    log = loadgen.run_conversations(lambda t: clock.sleep(0.03), range(3),
                                    0.2, _annotate, clock=clock)
    assert log.ran_out and log.tenants == [0, 1, 2]
    assert log.t1 - log.t0 == pytest.approx(0.09)

    def failing(t):
        clock.sleep(0.01)
        if t == 1:
            raise OSError("disk")
    log = loadgen.run_conversations(failing, range(3), 0.2, _annotate,
                                    clock=clock)
    assert np.isnan(log.seconds[1]) and len(log.errors) == 1


# ------------------------------------------------------------------- trace

def test_trace_reduction_against_the_recorded_trace():
    busy = tracing.device_busy(TRACE)
    assert busy["busy_s"] == pytest.approx(25e-9)
    assert busy["window_s"] == pytest.approx(100e-9)
    assert busy["idle_share"] == pytest.approx(0.75)
    assert tracing.device_ns_per_span(TRACE, "lz.serve.batch") == [15.0, 5.0]
    assert tracing.top_ops(TRACE)[:2] == [["fusion.1_f32_8_128_", 15e-9],
                                         ["fusion.2_f32_8_2112_", 10e-9]]
    gaps = dict(tracing.idle_gaps_by_span(TRACE))
    assert gaps == {"_no_span_": pytest.approx(55e-9),
                    "lz.serve.batch": pytest.approx(15e-9),
                    "lz.serve.exact": pytest.approx(5e-9)}
    assert sum(gaps.values()) == pytest.approx(75e-9)     # all the idle time
    b = tracing.Busy(tracing.union(TRACE["devices"]["/device:TPU:0"]))
    assert b.inside(0, 1000) == 40 and b.inside(12, 20) == 8
    assert b.inside(25, 40) == 0 and b.inside(44, 96) == 2


def _fake_run(trace=TRACE):
    cell, cfg, mix = harness.cell_files("fill.serve", ROOT)
    run = harness.Run(cell, cfg, mix, 1, 10.0, True)
    run.trace = trace
    run.device_kind = "TPU v5 lite"
    return run


class _Tel:
    def __init__(self, counters, timers=None):
        self.c, self.t = counters, timers or {}

    def counter_total(self, name):
        return self.c.get(name, 0)

    def timer_values(self, name):
        return self.t.get(name, [])


def test_readers_on_the_recorded_trace():
    run = _fake_run()
    assert readers.idle_pct(run) == pytest.approx(75.0)
    assert readers.device_ms_per_span(run, "lz.serve.batch") == pytest.approx(10e-6)
    assert readers.device_ms_per_span(run, "lz.ingest.") is None
    assert readers.span_minus_child_p50_ms(
        run, "lz.serve.batch", "lz.serve.") == pytest.approx(10e-6)
    run.trace = None
    assert readers.idle_pct(run) is None
    assert readers.serve_roofline_pct(run) is None     # never 0 for "not read"
    assert readers.timer_p50(run, "serve.dispatch_ms") is None


def test_roofline_share_divides_least_time_by_device_time():
    # 2 dispatches, 96 live requests -> mean batch 48; device 10 ms each
    trace = {"devices": {"/device:TPU:0": [("scan", 1e6, 10e6), ("scan", 21e6, 10e6)]},
             "spans": [("bench.window", 0, 40e6), ("lz.serve.batch", 0, 12e6),
                       ("lz.serve.batch", 20e6, 12e6)]}
    run = _fake_run(trace)
    run.telemetry = _Tel({"serve.live_requests": 96, "serve.batches": 2})
    need = files.load_module(run.cfg["demand"]).need(run.cfg, 48.0)
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert readers.serve_roofline_pct(run) == pytest.approx(
        100 * least["seconds"] / 10e-3)
    assert 90 < readers.serve_roofline_pct(run) < 100


def _two_dispatches(second_start):
    """One device plane, two passes of 100 ns back to back (100..200,
    200..300), and two ``lz.serve.batch`` spans of 150 ns: the first from
    60, the second from ``second_start``."""
    return {"devices": {"/device:TPU:0": [("lz_select_scan.1", 100.0, 100.0),
                                          ("lz_select_scan.1", 200.0, 100.0),
                                          ("top_k.2", 300.0, 4.0)]},
            "spans": [("bench.window", 0.0, 1000.0),
                      ("lz.serve.batch", 60.0, 150.0),
                      ("lz.serve.batch", second_start, 150.0),
                      ("lz.index.stage", 62.0, 5.0)]}


def test_overlapping_dispatches_are_read_once_per_dispatch():
    # since PR 30 the second batch's span opens while the first's pass runs:
    # 160..310 covers 40 ns of the first pass, 60..210 covers 10 of the second
    trace = _two_dispatches(160.0)
    each = tracing.device_ns_per_span(trace, "lz.serve.batch")
    assert each == [110.0, 144.0]              # the shared 50 ns counted twice
    assert sum(each) / 2 == 127.0
    # busy inside the union 60..310 is 204 ns: 102 a dispatch, the by-name sum
    assert tracing.device_ns_per_dispatch(trace, "lz.serve.batch") == 102.0
    run = _fake_run(trace)
    assert readers.device_ms_per_span(run, "lz.serve.batch") == 102e-6
    study = tracing.dispatch_study(trace, "lz.serve.batch", "lz_select_scan")
    assert study == {"spans": 2, "each_span_ms": 127e-6, "union_ms": 102e-6,
                     "by_name_ms": {"lz_select_scan": 100e-6, "rest": 2e-6,
                                    "sum": 102e-6}}
    # the roofline share follows the new reading: never the doubled time
    run.telemetry = _Tel({"serve.live_requests": 128, "serve.batches": 2})
    need = files.load_module(run.cfg["demand"]).need(run.cfg, 64.0)
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert readers.serve_roofline_pct(run) == pytest.approx(
        100 * least["seconds"] / 102e-9, rel=1e-12)


@pytest.mark.parametrize("second_start", [210.0, 250.0, 700.0])
def test_disjoint_dispatches_read_as_they_always_did(second_start):
    trace = _two_dispatches(second_start)
    each = tracing.device_ns_per_span(trace, "lz.serve.batch")
    assert tracing.device_ns_per_dispatch(trace, "lz.serve.batch") \
        == sum(each) / len(each)
    assert readers.device_ms_per_span(_fake_run(trace), "lz.serve.batch") \
        == sum(each) / len(each) / 1e6


def test_device_time_per_dispatch_on_the_recorded_traces_and_empty_cases():
    assert tracing.device_ns_per_dispatch(TRACE, "lz.serve.batch") == 10.0
    assert tracing.device_ns_per_dispatch(TRACE, "lz.ingest.") is None
    no_plane = {"devices": {}, "spans": TRACE["spans"]}
    assert tracing.device_ns_per_dispatch(no_plane, "lz.serve.batch") is None
    assert tracing.dispatch_study(no_plane, "lz.serve.batch", "x") == {}


# ------------------------------------------------------------------ demand

def test_demand_of_both_configurations_by_hand():
    _, lme, _ = harness.cell_files("fill.serve", ROOT)
    need = files.load_module(lme["demand"], ROOT).need(lme, 64)
    assert need["bytes"] == 5_000_000 * 768 * 2 + 5_000_000 * 5 + 64 * 768 * 2 + 64 * 5 * 8
    assert need["ops"] == 2 * 64 * 5_000_000 * 768
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert least["bound"] == "hbm"
    assert least["bytes_s"] == pytest.approx(7.7051e9 / 819e9, rel=1e-3)   # 9.41 ms
    assert least["ops_s"] == pytest.approx(4.9152e11 / 197e12, rel=1e-3)   # 2.49 ms
    _, share, _ = harness.cell_files("share.serve", ROOT)
    need = files.load_module(share["demand"], ROOT).need(share, 8)
    assert need["bytes"] == 131_072 * 768 * 2 + 131_072 * 5 + 8 * 768 * 2 + 8 * 5 * 8
    assert need["ops"] == 2 * 8 * 131_072 * 768
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert least["seconds"] == pytest.approx(2.0199e8 / 819e9, rel=1e-3)   # 0.247 ms


@pytest.mark.parametrize("batch,want_bytes,want_ops", [
    (1, 7_705_001_576, 7_680_000_000.0),
    (64, 7_705_100_864, 491_520_000_000.0)])
def test_lme5m_demand_is_the_dict_it_was_accepted_with(batch, want_bytes, want_ops):
    # the file's prose was reworded after PR 26 took the score tile out of
    # the program; what a dispatch has to move did not change
    _, lme, _ = harness.cell_files("fill.serve", ROOT)
    assert files.load_module(lme["demand"], ROOT).need(lme, batch) == {
        "bytes": want_bytes, "ops": want_ops, "ops_peak": "bf16_flops_per_s"}


# -------------------------------------------------------------- comparison

LIMITS = {"score_gap": 1e-4, "rank_errors": 0, "foreign_ids": 0,
          "count_errors": 0, "unanswered": 0, "swallowed": 0}


def _tenant(n=64, d=32, seed=0):
    rng = np.random.default_rng(seed)
    rows = reference.stored(rng.standard_normal((n, d)), "bfloat16")
    q = reference.stored(rows[:4] + 0.05 * rng.standard_normal((4, d)), "bfloat16")
    return rows, np.ones(n, bool), q


def test_comparison_accepts_the_reference_and_rejects_each_fault():
    rows, live, q = _tenant()
    ref_s, ref_i, all_s = reference.topk_exact(rows, live, q, 5)
    assert [int(i[0]) for i in ref_i] == [0, 1, 2, 3]

    def compare(mutate=None):
        c = reference.Comparison(LIMITS)
        for n in range(4):
            idx, sc = [int(j) for j in ref_i[n]], [float(s) for s in ref_s[n]]
            if mutate:
                idx, sc = mutate(n, idx, sc)
            c.answer(f"q{n}", idx, sc, [(ref_s[n], ref_i[n], all_s[n])], live)
        return c

    assert compare().correct and compare().score_gap == 0.0
    assert not reference.Comparison(LIMITS).correct          # nothing compared
    wrong_row = compare(lambda n, i, s: ([i[0], 63] + i[2:], s))
    assert not wrong_row.correct and wrong_row.rank_errors >= 1
    short = compare(lambda n, i, s: (i[:4], s[:4]))
    assert not short.correct and short.count_errors == 4
    off = compare(lambda n, i, s: (i, [x + 1e-3 for x in s]))
    assert not off.correct and off.score_gap == pytest.approx(1e-3, rel=1e-2)
    swapped = compare(lambda n, i, s: ([int(j) for j in ref_i[(n + 1) % 4]],
                                       [float(x) for x in ref_s[(n + 1) % 4]]))
    assert not swapped.correct                               # a demux fault
    dead = live.copy(); dead[int(ref_i[0][1])] = False       # a duplicate fact
    d_s, d_i, d_all = reference.topk_exact(rows, dead, q, 5)
    c = reference.Comparison(LIMITS)
    c.answer("q0", [int(j) for j in ref_i[0]], None, [(d_s[0], d_i[0], d_all[0])], dead)
    assert not c.correct and c.rank_errors >= 1
    c = compare(); c.foreign("q0", "'t00009:f1'")
    assert not c.correct and c.numbers()["foreign_ids"]["value"] == 1
    c = compare(); c.swallowed = 1
    assert not c.correct
    with pytest.raises(ValueError):
        reference.Comparison({"score_gap": 1e-4})


def test_int8_control_comes_out_not_correct():
    for seed in range(3):
        rows, live, q = _tenant(n=256, d=64, seed=seed)
        ref_s, ref_i, all_s = reference.topk_exact(rows, live, q, 5)
        c = reference.Comparison(LIMITS)
        for n, (idx, sc) in enumerate(reference.int8_answers(rows, live, q, 5)):
            c.answer(f"q{n}", idx, sc, [(ref_s[n], ref_i[n], all_s[n])], live)
        assert not c.correct and c.score_gap > 3 * LIMITS["score_gap"]


def test_an_answer_may_agree_with_either_query_precision_as_a_whole():
    rng = np.random.default_rng(5)
    rows = reference.stored(rng.standard_normal((256, 64)), "bfloat16")
    live = np.ones(256, bool)
    q = reference.unit(rows[:8] + 0.1 * rng.standard_normal((8, 64)))
    variants = reference.query_variants(rows, live, q, 5, "bfloat16")
    assert np.abs(variants[0][0] - variants[1][0]).max() > 1e-4   # they differ
    tight = dict(LIMITS, score_gap=1e-5)
    for var in variants:                      # either computation is accepted
        c = reference.Comparison(tight)
        for n in range(8):
            c.answer(f"q{n}", [int(j) for j in var[1][n]],
                     [float(x) for x in var[0][n]],
                     [tuple(v[n] for v in w) for w in variants], live)
        assert c.correct and c.score_gap < 1e-6
    c = reference.Comparison(tight)           # but not a blend of the two
    mid = 0.5 * (variants[0][0] + variants[1][0])
    for n in range(8):
        c.answer(f"q{n}", [int(j) for j in variants[0][1][n]],
                 [float(x) for x in mid[n]],
                 [tuple(v[n] for v in w) for w in variants], live)
    assert not c.correct


def test_read_back_near_tie_needs_both_query_precisions():
    """What refused PR 24's first check (share.ingest, seed 387355596, tenant
    198, fact 50): facts 26 and 90 score 2.6e-4 apart under the bf16 query
    and change places under the f32 query, which the single-request program
    of search_memories keeps. Judged by the bf16 variant alone that answer
    reads as two rank errors; with both, as the serve cells had it, as none."""
    c = corpus.tenant_corpus(387355596, 198, 105, 768, 101)
    rows = reference.stored(c, "bfloat16")
    j = np.arange(1, 101)
    live = np.zeros(105, bool)
    live[1:101] = ~corpus.is_dup(j, 101)
    variants = reference.query_variants(rows, live, c[[50]], 5, "bfloat16")
    by_bf16, by_f32 = ([int(x) for x in v[1][0]] for v in variants)
    assert by_bf16[3:] == [26, 90] and by_f32[3:] == [90, 26]
    one = reference.Comparison(LIMITS)
    one.answer("t198 f50", by_f32, None, [tuple(v[0] for v in variants[0])], live)
    assert one.rank_errors == 2 and not one.correct
    both = reference.Comparison(LIMITS)
    both.answer("t198 f50", by_f32, None,
                [tuple(v[0] for v in w) for w in variants], live)
    assert both.correct
