"""The hold in the benchmark's cells (ISSUE 32; tier-1, CPU, debug geometry):
``fill.serve``'s closed loop of callers that wait is where the rule engages,
``share.serve``'s open loop of callbacks is the control and must never meet
it. What the hold did in a window is read by the manifest's own readers
(``benchmark/metrics/sched.hold_*``, ``sched.batch_requests_mean.qps``; in
the manifest since PR 34) — on registries made by hand and on the registry
the timed window of a debug run filled, beside the program's counters they
are computed from. (``scripts/hold_counters.py`` read the same counters for
PERF.md before those entries existed; it stays only because
``tests/benchmark/test_hold_metric.py``, a benchmark file, compares with it.)"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

MEAN, HOLD, CONTROL, LENGTH = (
    "sched.batch_requests_mean.qps", "sched.hold_pct.qps",
    "sched.hold_pct.lat", "sched.hold_p50_ms.qps")
COUNTERS = ("serve.requests", "serve.batches", "serve.held_batches",
            "serve.hold_us", "serve.overlapped_batches")


def _read(name, run):
    return harness.reader(name, ROOT)(run)


def _window(cell, seed):
    """The cell's traced debug run: its result, the run's registry as the
    window left it, and the manifest's readers' view of it."""
    seen = []
    res = harness.run_cell(cell, seed, 0.8, True, root=ROOT, debug=True,
                           sabotage=seen.append)      # breaks nothing
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    tel = seen[0].telemetry
    run = harness.Run({"name": cell, "chips": 1}, {}, {}, seed, 0.8, False,
                      ROOT)
    run.telemetry = tel
    return res, tel, run, {n: tel.counter_total(n) for n in COUNTERS}


@pytest.mark.parametrize("totals,mean,hold", [
    ({}, None, None),                                  # served nothing
    ({"serve.batches": 8, "serve.requests": 40}, 5.0, None),   # no marker
    ({"serve.batches": 8, "serve.requests": 260,
      "serve.queue_wait_us": 5}, 32.5, 0.0),           # the parent's counters
    ({"serve.batches": 8, "serve.requests": 512, "serve.queue_wait_us": 5,
      "serve.held_batches": 6, "serve.hold_us": 21000}, 64.0, 75.0),
], ids=["empty", "no_marker", "never_held", "held"])
def test_the_readings_from_the_counters(totals, mean, hold):
    run = harness.Run({"name": "fill.serve", "chips": 1}, {}, {}, 1, 1.0,
                      False, ROOT)
    run.telemetry = Telemetry()
    for name, n in totals.items():
        run.telemetry.bump(name, n)
    assert _read(MEAN, run) == mean
    assert _read(HOLD, run) == _read(CONTROL, run) == hold
    # the hold's length is the span's (a trace), never guessed from counters
    assert _read(LENGTH, run) is None


def test_callers_that_wait_meet_the_hold_in_fill_serve():
    res, tel, run, n = _window("fill.serve", 2**31 + 32)
    assert 1 <= n["serve.held_batches"] <= n["serve.batches"]
    assert n["serve.hold_us"] > 0
    # the manifest's readers on the window's registry are the counters' own
    # ratios, and what the traced run's line reports
    assert _read(MEAN, run) == n["serve.requests"] / n["serve.batches"] > 1.0
    assert _read(HOLD, run) \
        == 100.0 * n["serve.held_batches"] / n["serve.batches"]
    assert 0.0 < _read(HOLD, run) <= 100.0
    assert res["metrics"][MEAN]["value"] == _read(MEAN, run)
    assert res["metrics"][HOLD]["value"] == _read(HOLD, run)
    assert res["metrics"][LENGTH]["value"] > 0.0
    # a hold is never an overlap: nothing is in flight while a worker holds
    assert n["serve.overlapped_batches"] == 0
    assert res["metrics"]["sched.overlap_pct.qps"]["value"] == 0.0
    assert "sched.hold_ms" in tel.snapshot()["timers"]


def test_callbacks_never_meet_it_in_share_serve():
    res, tel, run, n = _window("share.serve", 2**31 + 33)
    assert n["serve.batches"] > 0
    assert _read(CONTROL, run) == 0.0 == res["metrics"][CONTROL]["value"]
    assert "serve.held_batches" not in tel.counters       # no entry at all
    assert "serve.hold_us" not in tel.counters
    assert "sched.hold_ms" not in tel.snapshot()["timers"]
