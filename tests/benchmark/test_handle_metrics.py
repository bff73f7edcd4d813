"""The hand-over in the manifest (PR 42): five scheduler entries beside PR
41's stage metrics — the demux loop's median in the two closed-loop cells
the host paces (``sched.demux_p50_ms.nbr`` / ``.qps``: the span
``lz.sched.demux``, as ``sched.demux_p50_ms`` reads it in ``share.serve``)
and how often an answer woke a thread that waited for it
(``sched.woken_pct.nbr`` / ``.qps`` / ``.lat``: ``serve.wakes`` over
``serve.requests`` under the marker ``serve.exec_us``), which says whether a
cell's traffic runs the handle's waiting path (a closed loop: ~100) or
bypasses it (an open loop with done-callbacks: 0). Each entry against the
manifest's contracts, each reader against registries and traces made by
hand (a program without the marker reads None, one that never wakes reads
0), the three cells' traced debug runs, and a later PR's checkout with the
entries in it. No number here is a device number."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness  # noqa: E402
from test_benchmark_manifest import Later  # noqa: E402
from test_stage_metrics import PARENT, STAMPED, WAITED, _read, _run  # noqa: E402

CELLS = {"nbr": "graph.chat", "qps": "fill.serve", "lat": "share.serve"}
DEMUX = [f"sched.demux_p50_ms.{s}" for s in ("nbr", "qps")]
WOKEN = [f"sched.woken_pct.{s}" for s in ("nbr", "qps", "lat")]
FIVE = DEMUX + WOKEN


@pytest.mark.parametrize("name", FIVE)
def test_entry_is_what_the_issue_names_and_passes_the_contracts(name):
    e = contracts.entry(ROOT, "per_layer", name)
    suffix = name.rpartition(".")[2]
    woken = name in WOKEN
    assert e == {"name": name, "unit": "%" if woken else "ms",
                 "better": ("higher" if woken and suffix != "lat"
                            else "lower"),
                 "source": "program_counter" if woken else "program_span",
                 "layer": "scheduler",
                 "moves": "search_p50_ms" if suffix == "lat" else "search_qps",
                 "workloads": [CELLS[suffix]]}
    contracts.per_layer_metric(e, ROOT)
    assert callable(harness.reader(name, ROOT))


def test_the_five_are_appended_in_the_issue_s_order_after_pr_41_s_entries():
    names = [m["name"] for m in harness.manifest(ROOT)["per_layer"]]
    first = names.index(FIVE[0])
    assert first >= 110 and names[first:first + 5] == FIVE
    assert names.index("sched.unexplained_pct.lat") < first


@pytest.mark.parametrize("name", WOKEN)
def test_woken_is_none_without_the_marker_and_zero_where_nobody_wakes(name):
    # no registry, an empty one, and PR 41's parent: served, nothing stamped
    for counters in (None, [], PARENT):
        assert _read(name, _run(counters, [15.0])) is None
    # stamped and answered through callbacks: nobody woke — 0, not None
    assert _read(name, _run(STAMPED, [13.0])) == 0.0
    # every answer woke its caller / three of a thousand did
    assert _read(name, _run(WAITED, [15.7])) == 100.0
    some = STAMPED + [("serve.wake_us", 9000), ("serve.wakes", 3)]
    assert _read(name, _run(some, [13.0])) == pytest.approx(0.3)
    # stamped but nothing served: nothing to divide by
    assert _read(name, _run([("serve.exec_us", 5)], [1.0])) is None


@pytest.mark.parametrize("name", DEMUX)
def test_demux_reads_the_span_s_median_inside_the_window_or_none(name):
    run = _run(WAITED, [15.7])
    assert _read(name, run) is None                 # no trace
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms),
             ("lz.sched.demux", 10 * ms, ms // 2),
             ("lz.sched.demux", 20 * ms, ms),
             ("lz.sched.demux", 30 * ms, 4 * ms),
             ("lz.sched.demux", 200 * ms, 9 * ms),  # after the window
             ("lz.sched.account", 40 * ms, 7 * ms)]
    run.trace = {"spans": spans, "devices": {}}
    assert _read(name, run) == 1.0
    # and it is the reader of the accepted entry, letter for letter
    assert _read("sched.demux_p50_ms", run) == 1.0
    run.trace = {"spans": [s for s in spans if s[0] != "lz.sched.demux"],
                 "devices": {}}
    assert _read(name, run) is None                 # a program without it


@pytest.mark.parametrize("suffix", list(CELLS))
def test_the_cell_s_traced_debug_run_reports_its_entries(suffix):
    res = contracts.debug_run(CELLS[suffix], 2**31 + 42, ROOT, traced=True,
                              seconds=0.8)
    assert res["correct"] is True
    mine = [n for n in FIVE if n.endswith("." + suffix)]
    assert len(mine) == (1 if suffix == "lat" else 2)
    woken = res["metrics"][f"sched.woken_pct.{suffix}"]["value"]
    if suffix == "lat":
        # callbacks on the worker: nobody waits — but for warm-up's few
        # ``result()`` callers, whose wake-ups the window's first batch bumps
        assert woken < 5.0
    else:
        # every answer wakes its caller; a wake-up is bumped with the NEXT
        # batch's counters, so a short window lends some to the one after
        # it and borrows some from warm-up
        assert 80.0 <= woken <= 105.0
        assert res["metrics"][f"sched.demux_p50_ms.{suffix}"]["value"] > 0.0
    assert not [n for n in set(FIVE) - set(mine) if n in res["metrics"]]


def test_a_later_pr_s_checkout_still_passes_with_the_five_in_it(tmp_path):
    later = Later(str(tmp_path))
    contracts.manifest_wide(later.root)
    later.nothing_was_edited()
