"""The hold in the benchmark's cells (ISSUE 32; tier-1, CPU, debug geometry):
``fill.serve``'s closed loop of callers that wait is where the rule engages,
``share.serve``'s open loop of callbacks is the control and must never meet
it. The three per-layer metrics the issue names are not in the manifest yet
(PERF.md section 7), so this reads the program's own counters — what those
metrics will read — from the registry the timed window filled, through
``scripts/hold_counters.py``, which is how PERF.md's figures were read on
the chip."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.join(ROOT, "scripts"))

import hold_counters  # noqa: E402  (scripts/)
from benchmark import harness  # noqa: E402


def _window_counters(cell, seed):
    seen = []
    res = harness.run_cell(cell, seed, 0.8, True, root=ROOT, debug=True,
                           sabotage=seen.append)      # breaks nothing
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    tel = seen[0].telemetry
    return res, tel, {n: tel.counter_total(n) for n in hold_counters.COUNTERS}


@pytest.mark.parametrize("totals,mean,hold", [
    ({}, None, None),                                  # served nothing
    ({"serve.batches": 8, "serve.requests": 40}, 5.0, None),   # no marker
    ({"serve.batches": 8, "serve.requests": 260,
      "serve.queue_wait_us": 5}, 32.5, 0.0),           # the parent's counters
    ({"serve.batches": 8, "serve.requests": 512, "serve.queue_wait_us": 5,
      "serve.held_batches": 6, "serve.hold_us": 21000}, 64.0, 75.0),
], ids=["empty", "no_marker", "never_held", "held"])
def test_the_three_readings_from_the_counters(totals, mean, hold):
    got = hold_counters.metrics(totals)
    assert got["sched.batch_requests_mean"] == mean
    assert got["sched.hold_pct"] == hold
    assert got["sched.hold_ms_per_held_batch"] == (3.5 if hold else None)


def test_callers_that_wait_meet_the_hold_in_fill_serve():
    res, tel, n = _window_counters("fill.serve", 2**31 + 32)
    # what sched.hold_pct.qps and sched.batch_requests_mean.qps will read
    assert 1 <= n["serve.held_batches"] <= n["serve.batches"]
    assert n["serve.hold_us"] > 0
    got = hold_counters.metrics(n)
    assert got["sched.batch_requests_mean"] > 1.0
    assert 0.0 < got["sched.hold_pct"] <= 100.0
    # a hold is never an overlap: nothing is in flight while a worker holds
    assert n["serve.overlapped_batches"] == 0
    assert res["metrics"]["sched.overlap_pct.qps"]["value"] == 0.0
    assert "sched.hold_ms" in tel.snapshot()["timers"]


def test_callbacks_never_meet_it_in_share_serve():
    _, tel, n = _window_counters("share.serve", 2**31 + 33)
    assert n["serve.batches"] > 0
    assert hold_counters.metrics(n)["sched.hold_pct"] == 0.0  # the control
    assert "serve.held_batches" not in tel.counters       # no entry at all
    assert "serve.hold_us" not in tel.counters
    assert "sched.hold_ms" not in tel.snapshot()["timers"]
