"""A request's stages in the manifest (PR 41): the ten scheduler entries
that read the seven counters ``serve/scheduler.py`` sums over every served
request (``tests/test_request_stages.py`` holds the scheduler to them) —
the caller's wake-up and return in the two closed-loop cells the host paces,
what an answer waits in the demux, and the share of the HARNESS's own
latency that the program's stage sums leave unexplained. Each entry against
the manifest's contracts, each reader against registries made by hand (the
parent's, which lacks the counters, reads None and never 0), the arithmetic
on a made-up run, the three cells' traced debug runs, and a later PR's
checkout with the entries in it. No number here is a device number."""

import math
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness, stage_metrics  # noqa: E402
from test_benchmark_manifest import Later  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

CELLS = {"nbr": "graph.chat", "qps": "fill.serve", "lat": "share.serve"}
TEN = [f"sched.{family}.{suffix}" for family, suffixes in [
    ("wake_mean_ms", ("nbr", "qps")), ("return_mean_ms", ("nbr", "qps")),
    ("demux_wait_mean_ms", ("nbr", "qps", "lat")),
    ("unexplained_pct", ("nbr", "qps", "lat"))] for suffix in suffixes]

# a window's totals as the program's registry holds them: 1,000 requests in
# 20 batches whose stages add up to 15.5 ms a request
PARENT = [("serve.requests", 1000), ("serve.batches", 20),
          ("serve.queue_wait_us", 6_000_000)]
STAMPED = PARENT + [("serve.account_us", 200_000), ("serve.exec_us", 6_300_000),
                    ("serve.demux_wait_us", 500_000)]
WAITED = STAMPED + [("serve.wake_us", 2_500_000), ("serve.wakes", 1000),
                    ("serve.return_us", 270_000), ("serve.returns", 900)]


def _run(counters=None, latency_ms=(), late_ms=None):
    run = harness.Run({"name": "graph.chat", "chips": 1}, {}, {}, 1, 1.0,
                      True, ROOT)
    if counters is not None:
        run.telemetry = Telemetry()
        for name, n in counters:
            run.telemetry.bump(name, n)
    run.latency_ms = np.asarray(latency_ms, float)
    run.late_ms = (np.zeros(len(run.latency_ms)) if late_ms is None
                   else np.asarray(late_ms, float))
    return run


def _read(name, run):
    return harness.reader(name, ROOT)(run)


@pytest.mark.parametrize("name", TEN)
def test_entry_is_what_the_issue_names_and_passes_the_contracts(name):
    e = contracts.entry(ROOT, "per_layer", name)
    suffix = name.rpartition(".")[2]
    assert e == {"name": name, "unit": "%" if "pct" in name else "ms",
                 "better": "lower", "source": "program_counter",
                 "layer": "scheduler",
                 "moves": "search_p50_ms" if suffix == "lat" else "search_qps",
                 "workloads": [CELLS[suffix]]}
    contracts.per_layer_metric(e, ROOT)
    assert callable(harness.reader(name, ROOT))


def test_the_ten_are_appended_in_the_issue_s_order_after_pr_39_s_entries():
    names = [m["name"] for m in harness.manifest(ROOT)["per_layer"]]
    first = names.index(TEN[0])
    assert first >= 100 and names[first:first + 10] == TEN


@pytest.mark.parametrize("name", TEN)
def test_a_program_without_the_counters_reads_none_never_zero(name):
    # no registry, an empty one, and the parent's: served, queue wait
    # summed, nothing stamped
    for counters in (None, [], PARENT):
        assert _read(name, _run(counters, [15.0, 16.0])) is None


@pytest.mark.parametrize("suffix", ["nbr", "qps"])
def test_nobody_waited_or_nobody_came_back_reads_none(suffix):
    run = _run(STAMPED, [13.0])
    assert _read(f"sched.wake_mean_ms.{suffix}", run) is None
    assert _read(f"sched.return_mean_ms.{suffix}", run) is None
    assert _read(f"sched.demux_wait_mean_ms.{suffix}", run) == 0.5
    run = _run(STAMPED + [("serve.wake_us", 9000), ("serve.wakes", 3)], [13.0])
    assert _read(f"sched.wake_mean_ms.{suffix}", run) == 3.0
    assert _read(f"sched.return_mean_ms.{suffix}", run) is None


def test_the_means_divide_each_sum_by_its_own_count():
    run = _run(WAITED, [15.7] * 4)
    assert _read("sched.wake_mean_ms.nbr", run) == 2.5          # / wakes
    assert _read("sched.return_mean_ms.qps", run) == 0.3        # / returns
    assert _read("sched.demux_wait_mean_ms.lat", run) == 0.5    # / requests


def test_unexplained_is_the_harness_s_latency_less_the_stage_sums(capsys):
    # L: the mean latency LESS the mean lateness (an open loop's requests
    # are timed from when they were due); S = 6.0 + 0.2 + 6.3 + 0.5 + 2.5
    run = _run(WAITED, [15.0, 16.0, 17.0, 18.0], late_ms=[0.2, 0.4, 0.6, 0.8])
    parts = stage_metrics.parts_ms(run)
    assert parts["L"] == pytest.approx(16.0) and parts["S"] == 15.5
    assert parts["serve.wake_us"] == 2.5 and parts["serve.return_us"] == 0.3
    for suffix in CELLS:
        assert _read(f"sched.unexplained_pct.{suffix}", run) == \
            pytest.approx(100 * 0.5 / 16.0)
    assert "request stages, mean ms: L 16.0000" in capsys.readouterr().err
    # the sum OVER the latency reads the same distance: it is a share, and
    # a stamp in the wrong place shows whichever way it errs
    over = _run(WAITED, [15.0, 15.0])
    assert _read("sched.unexplained_pct.nbr", over) == \
        pytest.approx(100 * 0.5 / 15.0)
    # callbacks: no wake-up at all, the other four are the whole sum
    assert stage_metrics.parts_ms(_run(STAMPED, [13.0]))["S"] == 13.0
    # nothing finished, or nothing served: nothing to compare
    assert _read("sched.unexplained_pct.lat", _run(WAITED)) is None
    assert _read("sched.unexplained_pct.lat", _run(
        [("serve.exec_us", 5)], [1.0])) is None


@pytest.mark.parametrize("suffix", list(CELLS))
def test_the_cell_s_traced_debug_run_reports_its_entries(suffix):
    res = contracts.debug_run(CELLS[suffix], 2**31 + 41, ROOT, traced=True,
                              seconds=0.8)
    assert res["correct"] is True
    mine = [n for n in TEN if n.endswith("." + suffix)]
    assert len(mine) == (2 if suffix == "lat" else 4)
    for name in mine:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, name
    # the stamps are contiguous: what the harness's clock reads beyond
    # their sum is its own few lines around submit and result
    assert res["metrics"][f"sched.unexplained_pct.{suffix}"]["value"] < 25.0
    assert not [n for n in set(TEN) - set(mine) if n in res["metrics"]]


def test_a_later_pr_s_checkout_still_passes_with_the_ten_in_it(tmp_path):
    later = Later(str(tmp_path))
    contracts.manifest_wide(later.root)
    later.nothing_was_edited()
    names = [m["name"] for m in harness.manifest(later.root)["per_layer"]]
    assert names[names.index(TEN[0]):][:10] == TEN
    for name in TEN:
        assert harness.reader(name, later.root)(_run(PARENT, [1.0])) is None
