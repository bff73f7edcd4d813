"""The per-layer metrics that read the program's own spans and counters
(PR 25), on a recorded trace with hand-computed values, and end to end on a
traced debug run of each cell. The accepted span metrics must read the same
with the new spans in the trace as without them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness, span_metrics, tracing  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

RAW = json.load(open(os.path.join(HERE, "data", "span_trace.json")))
TRACES = {k: {"devices": {p: [tuple(e) for e in v]
                          for p, v in RAW[k]["devices"].items()},
              "spans": [tuple(e) for e in RAW[k]["spans"]]}
          for k in ("serve", "ingest")}
# by hand from the trace's "_note"; ns -> ms
WANT = {
    "sched.worker_busy_pct": 85.0,
    "sched.queue_wait_mean_ms": 3.0,
    "sched.demux_p50_ms": 8e-6,
    "index.pack_p50_ms.lat": 30e-6,
    "index.stage_p50_ms.lat": 60e-6,
    "index.decode_p50_ms.lat": 55e-6,
    "dispatch.launch_p50_ms.lat": 50e-6,
    "dispatch.readback_p50_ms.lat": 100e-6,
    "sched.lone_dispatch_pct": 25.0,
    "api.end_conversation_p50_ms": 600e-6,
    "api.switch_user_p50_ms": 200e-6,
    "store.ms_per_conv": 290e-6,
    "journal.ms_per_conv": 102e-6,
    "store.file_ops_per_conv": 17.0,
}
NEW = [m for m in contracts.span_metrics(ROOT) if m["name"] in WANT]


def _run(trace_key, counters=True):
    cell = {"name": "t", "chips": 1}
    run = harness.Run(cell, {}, {}, 1, 1.0, True, ROOT)
    run.trace = TRACES[trace_key] if trace_key else None
    run.telemetry = Telemetry()
    if counters:
        run.telemetry.bump("serve.requests", 30)
        run.telemetry.bump("serve.batches", 8)
        run.telemetry.bump("serve.queue_wait_us", 90_000)
        run.telemetry.bump("serve.lone_batches", 2)
    return run


def _trace_of(metric):
    # by the cell the entry was accepted for (``contracts.FOURTEEN`` pins each
    # list in full: that cell, then the cells ``contracts.JOINED`` names)
    return ("ingest" if contracts.FOURTEEN[metric["name"]] == "share.ingest"
            else "serve")


def test_the_issue_s_fourteen_metrics_are_in_the_manifest():
    # each with its accepted cell and a hand-computed value below; a later
    # PR's span metrics, with other cells' lists, stand beside them
    assert set(WANT) == set(contracts.FOURTEEN)
    contracts.fourteen_in_manifest(ROOT)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_reader_against_hand_computed_value(metric):
    got = harness.reader(metric["name"], ROOT)(_run(_trace_of(metric)))
    assert got == pytest.approx(WANT[metric["name"]], rel=1e-12)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_reader_returns_none_without_trace_span_or_counter(metric):
    read = harness.reader(metric["name"], ROOT)
    # no trace and a registry without this PR's counters: the parent program
    assert read(_run(None, counters=False)) is None
    if metric["source"] == "program_span":
        assert read(_run(None)) is None
        # a trace of a program that has no such span (the other cell's)
        other = "serve" if _trace_of(metric) == "ingest" else "ingest"
        assert read(_run(other)) is None


def test_lone_dispatches_read_zero_when_counted_and_none_when_not():
    run = _run("serve", counters=False)
    run.telemetry.bump("serve.batches", 4)
    read = harness.reader("sched.lone_dispatch_pct", ROOT)
    assert read(run) is None                 # a program that counts none
    run.telemetry.bump("serve.queue_wait_us", 10)
    assert read(run) == 0.0                  # counted: there were none


def _without_new_spans(trace):
    old = ("bench.", "lz.serve.", "lz.ingest.")
    return {"devices": trace["devices"],
            "spans": [s for s in trace["spans"] if s[0].startswith(old)]}


@pytest.mark.parametrize("name,key,want", [
    ("index.host_p50_ms.lat", "serve", 160e-6),
    ("kernel.serve_dev_ms.lat", "serve", 70e-6),
    ("kernel.ingest_dev_ms", "ingest", 45e-6),
])
def test_accepted_span_metrics_read_the_same_with_the_new_spans(name, key, want):
    read = harness.reader(name, ROOT)
    run = _run(key)
    with_new = read(run)
    run.trace = _without_new_spans(TRACES[key])
    assert len(run.trace["spans"]) < len(TRACES[key]["spans"])
    assert with_new == read(run) == pytest.approx(want, rel=1e-12)


def test_new_span_names_cannot_redefine_an_accepted_metric():
    # index.host_p50_ms.lat subtracts every lz.serve.* child and
    # kernel.ingest_dev_ms averages over every lz.ingest.* span
    names = {s[0] for t in TRACES.values() for s in t["spans"]}
    fresh = names - {"bench.window", "bench.conversation", "lz.serve.batch",
                     "lz.serve.exact", "lz.ingest.dedup_fused"}
    assert fresh and not any(n.startswith(("lz.serve.", "lz.ingest."))
                             for n in fresh)


def test_idle_gaps_carry_the_new_names():
    gaps = dict(tracing.idle_gaps_by_span(TRACES["ingest"], n=50))
    # what is left to bench.conversation itself is what no program span
    # covers: 1000 - 200 - 30 - 7 - 600 and 1000 - 240 - 30 - 7 - 650 ns
    assert gaps["bench.conversation"] == pytest.approx((163 + 73) * 1e-9,
                                                       abs=1e-15)
    assert {"lz.store.io", "lz.journal.io", "lz.write.prepare",
            "lz.api.end_conversation"} <= set(gaps)


def test_span_helpers_on_the_empty_cases():
    run = _run("serve")
    assert span_metrics.span_p50_ms(run, "lz.nothing") is None
    assert span_metrics.span_ms_per(run, ("lz.index.pack",), "lz.nothing") is None
    assert span_metrics.span_count_per(run, ("lz.nothing",), "lz.index.pack") is None
    assert span_metrics.busy_pct(run, "lz.nothing") is None
    assert span_metrics.counter_ratio(run, "serve.lone_batches", "nothing") is None


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.manifest(ROOT)["workloads"]])
def test_traced_debug_run_reports_every_new_metric_of_its_cell(cell):
    mine = contracts.traced_debug_run_reports_span_metrics(cell, ROOT)
    assert set(mine) >= {n for n, c in contracts.FOURTEEN.items() if c == cell}
