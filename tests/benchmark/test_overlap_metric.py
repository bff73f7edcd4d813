"""``sched.overlap_pct.qps`` (PR 30): the share of served batches the
scheduler admitted while another dispatch was in flight, read in
``fill.serve`` — the control: 64 clients and batches of up to 64 never leave
a FULL batch waiting behind a dispatch, so it has to read 0. The entry and
its reader file against the manifest's contracts, the reader against a
registry made by hand, and the cell's traced debug run. (The same reading
for ``pod.serve``, where the rule engages, waits for a ``benchmark`` PR:
``test_pod_cell.py`` pins that cell's metrics to thirteen. PERF.md §7.)"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

NAME = "sched.overlap_pct.qps"
CELL = "fill.serve"


def _entry():
    return [m for m in harness.manifest(ROOT)["per_layer"]
            if m["name"] == NAME][0]


def test_entry_is_what_the_issue_names_and_passes_the_contracts():
    e = _entry()
    assert e == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "scheduler",
                 "moves": "search_qps", "workloads": [CELL]}
    contracts.per_layer_metric(e, ROOT)
    assert harness.manifest(ROOT)["per_layer"][-1] == e      # appended
    assert e in contracts.span_metrics(ROOT)
    assert callable(harness.reader(NAME, ROOT))


def _run(counters=None):
    run = harness.Run({"name": CELL, "chips": 1}, {}, {}, 1, 1.0, False, ROOT)
    if counters is not None:
        run.telemetry = Telemetry()
        for name, n in counters:
            run.telemetry.bump(name, n)
    return run


@pytest.mark.parametrize("counters,want", [
    (None, None),                                  # no registry at all
    ([], None),                                    # a program that served nothing
    ([("serve.batches", 8)], None),                # no marker: cannot tell
    ([("serve.batches", 8), ("serve.queue_wait_us", 5)], 0.0),   # the parent
    ([("serve.batches", 8), ("serve.queue_wait_us", 5),
      ("serve.overlapped_batches", 6)], 75.0),
    ([("serve.queue_wait_us", 5), ("serve.overlapped_batches", 6)], None),
], ids=["no_registry", "empty", "no_marker", "never_overlapped", "overlapped",
        "no_batches"])
def test_reader_reads_zero_for_a_program_that_never_overlapped(counters, want):
    assert harness.reader(NAME, ROOT)(_run(counters)) == want


def test_control_cell_reads_zero_in_its_traced_debug_run():
    res = contracts.debug_run(CELL, 2**31 + 30, ROOT, traced=True)
    assert res["correct"] is True
    assert res["metrics"][NAME] == {"value": 0.0, "unit": "%"}
    assert res["metrics"]["sched.lone_dispatch_pct"]["value"] >= 0.0
