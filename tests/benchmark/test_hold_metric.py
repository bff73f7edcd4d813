"""The scheduler's hold in the manifest (PRs 32 and 34): requests a served
batch (``sched.batch_requests_mean.qps``), held / served batches in
``fill.serve``, where callers that wait meet the rule (``sched.hold_pct.qps``),
and in ``share.serve``, the control that has to read 0 (``sched.hold_pct.lat``),
and the hold's median length (``sched.hold_p50_ms.qps``, the ``lz.sched.hold``
span). Each entry against the manifest's contracts, each reader against
registries and a trace made by hand, both cells' traced debug runs, and the
readers against ``scripts/hold_counters.py``'s ``metrics()`` — which stood in
for them since PR 32 — on one set of totals. No number here is a device
number."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import files, harness  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

MEAN, HOLD, CONTROL, LENGTH = (
    "sched.batch_requests_mean.qps", "sched.hold_pct.qps",
    "sched.hold_pct.lat", "sched.hold_p50_ms.qps")
WINDOW = ("bench.window", 0.0, 1000.0)


@pytest.mark.parametrize("name", [MEAN, HOLD, CONTROL, LENGTH])
def test_entry_is_what_the_issue_names_and_passes_the_contracts(name):
    e = contracts.scheduler_entry(name, ROOT)      # as named, wherever it stands
    cells = {MEAN: "fill.serve", HOLD: "fill.serve", LENGTH: "fill.serve",
             CONTROL: "share.serve"}
    assert e["workloads"] == [cells[name]]
    # the control moves the latency cell's metric and is better LOWER
    assert (e["moves"], e["better"]) == (
        ("search_p50_ms", "lower") if name == CONTROL else
        ("search_qps", "lower" if name == LENGTH else "higher"))


def test_the_five_of_pr_34_are_appended_in_the_issue_s_order():
    names = [m["name"] for m in harness.manifest(ROOT)["per_layer"]]
    first = names.index(MEAN)
    assert first >= 46 and names[first:first + 5] == [
        MEAN, HOLD, CONTROL, LENGTH, "sched.overlap_pct.pod"]


def _run(counters=None, spans=None):
    run = harness.Run({"name": "fill.serve", "chips": 1}, {}, {}, 1, 1.0,
                      spans is not None, ROOT)
    if counters is not None:
        run.telemetry = Telemetry()
        for name, n in counters:
            run.telemetry.bump(name, n)
    if spans is not None:
        run.trace = {"devices": {}, "spans": [WINDOW] + spans}
    return run


MARKED = [("serve.batches", 8), ("serve.queue_wait_us", 5)]
TOTALS = {
    "no_registry": None,
    "empty": [],
    "no_marker": [("serve.batches", 8), ("serve.requests", 40)],
    "never_held": MARKED + [("serve.requests", 260)],
    "held": MARKED + [("serve.requests", 512), ("serve.held_batches", 6),
                      ("serve.hold_us", 21000)],
    "no_batches": [("serve.queue_wait_us", 5), ("serve.requests", 3),
                   ("serve.held_batches", 6)],
}
#            requests a batch, held / served batches in per cent
WANT = {"no_registry": (None, None), "empty": (None, None),
        "no_marker": (5.0, None), "never_held": (32.5, 0.0),
        "held": (64.0, 75.0), "no_batches": (None, None)}


@pytest.mark.parametrize("case", list(TOTALS))
def test_counter_readers_against_registries_made_by_hand(case):
    mean, hold = WANT[case]
    assert harness.reader(MEAN, ROOT)(_run(TOTALS[case])) == mean
    for name in (HOLD, CONTROL):
        assert harness.reader(name, ROOT)(_run(TOTALS[case])) == hold


@pytest.mark.parametrize("case", [c for c in TOTALS if TOTALS[c] is not None])
def test_readers_and_the_script_agree_on_one_set_of_totals(case):
    path = os.path.join("scripts", "hold_counters.py")
    if not os.path.exists(os.path.join(ROOT, path)):
        pytest.skip("scripts/hold_counters.py is gone: the manifest's "
                    "metrics stand alone")
    got = files.load_module(path, ROOT).metrics(dict(TOTALS[case]))
    run = _run(TOTALS[case])
    assert got["sched.batch_requests_mean"] == harness.reader(MEAN, ROOT)(run)
    assert got["sched.hold_pct"] == harness.reader(HOLD, ROOT)(run) \
        == harness.reader(CONTROL, ROOT)(run)


def test_hold_length_is_the_median_of_the_window_s_hold_spans():
    read = harness.reader(LENGTH, ROOT)
    holds = [("lz.sched.hold", 100.0, 30.0), ("lz.sched.hold", 300.0, 50.0),
             ("lz.sched.hold", 500.0, 40.0), ("lz.sched.hold", 700.0, 45.0),
             ("lz.sched.hold", 990.0, 500.0),        # cut to the window: 10
             ("lz.sched.hold", 2000.0, 70.0),        # after the window
             ("lz.sched.idle", 600.0, 300.0)]
    assert read(_run(spans=holds)) == pytest.approx(40e-6, rel=1e-12)
    assert read(_run(spans=holds[:1])) == pytest.approx(30e-6, rel=1e-12)
    # a program without the hold (the parent of PR 32), or no trace: nothing
    assert read(_run(spans=holds[-1:])) is None
    assert read(_run(MARKED)) is None


def test_callers_that_wait_meet_the_hold_in_fill_serve_s_traced_debug_run():
    res = contracts.debug_run("fill.serve", 2**31 + 34, ROOT, traced=True,
                              seconds=0.8)
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 1.0 < m[MEAN] <= 8.0                       # 8 clients, batches of 8
    assert 0.0 < m[HOLD] <= 100.0
    assert m[LENGTH] > 0.0 and res["metrics"][LENGTH]["unit"] == "ms"
    assert m["sched.overlap_pct.qps"] == 0.0          # a hold is no overlap
    assert CONTROL not in m


def test_callbacks_never_meet_it_in_share_serve_s_traced_debug_run():
    res = contracts.debug_run("share.serve", 2**31 + 35, ROOT, traced=True,
                              seconds=0.8)
    assert res["correct"] is True
    assert res["metrics"][CONTROL] == {"value": 0.0, "unit": "%"}
    assert not {MEAN, HOLD, LENGTH} & set(res["metrics"])
