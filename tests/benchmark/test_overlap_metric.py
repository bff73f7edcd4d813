"""``sched.overlap_pct.qps`` and ``.pod`` (PRs 30 and 34): the share of
served batches the scheduler admitted while another dispatch was in flight.
In ``fill.serve`` it is the control: 64 clients and batches of up to 64 never
leave a FULL batch waiting behind a dispatch, so it has to read 0; in
``pod.serve`` (128 clients) the rule engages. Each entry and its reader file
against the manifest's contracts (``contracts.scheduler_entry``: the entry as
named and IN ``per_layer``, its place not held), the reader against a
registry made by hand, and the cell's traced debug run."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

METRICS = [("sched.overlap_pct.qps", "fill.serve"),
           ("sched.overlap_pct.pod", "pod.serve")]
both = pytest.mark.parametrize("name,cell", METRICS,
                               ids=[name for name, _ in METRICS])


@both
def test_entry_is_what_the_issue_names_and_passes_the_contracts(name, cell):
    e = contracts.scheduler_entry(name, ROOT)      # as named, wherever it stands
    assert (e["unit"], e["moves"], e["workloads"]) == ("%", "search_qps", [cell])


def _run(cell, counters=None):
    run = harness.Run({"name": cell, "chips": 1}, {}, {}, 1, 1.0, False, ROOT)
    if counters is not None:
        run.telemetry = Telemetry()
        for name, n in counters:
            run.telemetry.bump(name, n)
    return run


@both
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
def test_reader_reads_zero_for_a_program_that_never_overlapped(
        name, cell, counters, want):
    assert harness.reader(name, ROOT)(_run(cell, counters)) == want


def test_control_cell_reads_zero_in_its_traced_debug_run():
    name, cell = METRICS[0]
    res = contracts.debug_run(cell, 2**31 + 30, ROOT, traced=True)
    assert res["correct"] is True
    assert res["metrics"][name] == {"value": 0.0, "unit": "%"}
    assert res["metrics"]["sched.lone_dispatch_pct"]["value"] >= 0.0


def test_pod_cell_reads_a_share_in_its_traced_debug_run():
    # 16 clients on batches of 8 over four host devices: the rule may engage
    name, cell = METRICS[1]
    res = contracts.debug_run(cell, 2**31 + 34, ROOT, traced=True)
    assert res["correct"] is True
    assert res["metrics"][name]["unit"] == "%"
    assert 0.0 <= res["metrics"][name]["value"] <= 100.0
