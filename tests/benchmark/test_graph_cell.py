"""The graph deployment's cell (PR 39): ``graph.chat`` = ``graph131k`` x
``chat-closed-zipf``. The per-cell contracts, a traced debug run's counters
(5 served rows and some neighbours a request, no overlap, no CSR build in the
window, nothing compiled), the graph forgotten coming out NOT correct by
``state_errors`` alone, the FULL geometry's graph inside ``max_edges`` and
``serve_max_nbr``, and the demand file's arithmetic. CPU debug runs at tiny
sizes; no number read here is a device number."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
import faults  # noqa: E402
from benchmark import corpus, files, harness  # noqa: E402

CELL = "graph.chat"
OTHERS = ("score_gap", "rank_errors", "foreign_ids", "count_errors",
          "unanswered", "swallowed")
# what ISSUE 39 named of the manifest, by name: the cell's per-layer entries
NBR = ("sched.overlap_pct.nbr", "sched.batch_requests_mean.nbr",
       "sched.queue_wait_p50_ms.nbr", "sched.hold_pct.nbr",
       "index.puts_per_dispatch.nbr", "index.csr_builds.nbr",
       "dispatch.p50_ms.nbr", "dispatch.launch_p50_ms.nbr",
       "dispatch.readback_p50_ms.nbr", "dispatch.boost_rows_per_req.nbr",
       "dispatch.nbr_rows_per_req.nbr", "dispatch.copies.nbr",
       "kernel.serve_dev_ms.nbr", "kernel.boost_dev_ms.nbr",
       "kernel.serve_roofline.nbr", "device.idle_pct.nbr",
       "device.compiles.nbr")


def test_cell_line():
    contracts.cell_line(CELL, ROOT)


def test_traced_line():
    contracts.traced_line(CELL, ROOT)


def test_the_cell_is_the_traffic_and_the_deployment_the_issue_names():
    cell, cfg, mix = harness.cell_files(CELL, ROOT)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "graph131k", "chat-closed-zipf", 1)
    assert (mix["loop"], mix["clients"], mix["boost_share"]) == ("closed", 128, 1.0)
    assert (mix["query_pool"], mix["zipf_s"], mix["k"]) == (16384, 0.99, 5)
    assert (mix["check_tenants"], mix["check_per_tenant"]) == (96, 16)
    assert mix["limits"] == {"state_errors": 0}
    _, chat = harness.cell_files("share.chat", ROOT)[1:]
    assert {k: v for k, v in mix["boost"].items() if k != "why"} == \
        {k: v for k, v in chat["boost"].items() if k != "why"}
    # share131k field for field, but the graph, the edge arena that holds
    # it, the two fields the demand reads, and the file's own words
    _, share, _ = harness.cell_files("share.serve", ROOT)
    same = set(share) - {"name", "source", "deployment", "assumed", "demand",
                         "guarantees", "memory_config"}
    assert all(cfg[k] == share[k] for k in same)
    assert cfg["graph"] == {"chain_weight": 0.5, "nearest": 3, "gate": 0.5,
                            "weight_scale": 0.8}
    assert cfg["memory_config"] == {**share["memory_config"],
                                    "max_edges": 1048576, "retrieval_cap": 5,
                                    "serve_max_nbr": 32}
    assert cfg["guarantees"][:3] == share["guarantees"]
    assert cfg["reduced"] == ["rows", "tenants"]
    assert {"graph", "max_edges", "retrieval_cap", "serve_max_nbr"} <= \
        set(cfg["assumed"])
    # the mirrored fields are the program's defaults
    from lazzaro_tpu.config import MemoryConfig
    mc = MemoryConfig()
    assert (mc.chain_link_weight, mc.cross_link_top_k, mc.link_gate,
            mc.link_weight_scale) == (0.5, 3, 0.5, 0.8)
    assert (mc.retrieval_cap, mc.serve_max_nbr) == (5, 32)


@pytest.mark.parametrize("name", NBR)
def test_the_cell_s_per_layer_entries_are_appended_and_resolve(name):
    m = harness.manifest(ROOT)
    e = contracts.entry(ROOT, "per_layer", name)
    contracts.per_layer_metric(e, ROOT)
    assert e["workloads"] == [CELL]
    cell = harness.cell_files(CELL, ROOT)[0]
    reported = {x["name"] for x in harness.metrics_of(cell, "end_to_end", ROOT)}
    assert e["moves"] in reported - {"setup_s"}
    layers = {x["layer"] for x in m["per_layer"] if x.get("workloads") != [CELL]}
    assert e["layer"] in layers                      # no new layer name
    assert callable(harness.reader(name, ROOT))


@pytest.fixture(scope="module")
def traced():
    seen = {}

    def look(ms):
        seen.update(edges=len(ms.index.edge_slots), held=ms.config.max_edges,
                    arena=ms.index.edge_state.capacity)
    res = contracts.debug_run(CELL, 39, ROOT, traced=True, sabotage=look)
    return res, seen


def test_a_traced_debug_run_is_correct_and_counts_what_it_should(traced):
    res, seen = traced
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"]["state_errors"] == {"value": 0.0, "limit": 0.0}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["dispatch.boost_rows_per_req.nbr"] == 5.0
    assert m["dispatch.nbr_rows_per_req.nbr"] > 0
    assert m["sched.overlap_pct.nbr"] == 0.0      # what ROADMAP A4 is judged on
    assert m["index.csr_builds.nbr"] == 0.0       # a boosting read dirties nothing
    assert m["index.puts_per_dispatch.nbr"] == 1.0
    assert m["dispatch.copies.nbr"] == 0.0
    assert m["device.compiles.nbr"] == 0.0
    assert 0.0 <= m["sched.hold_pct.nbr"] <= 100.0
    # the edge arena holds the graph as configured: it never grew
    _, cfg, _ = harness.cell_files(CELL, ROOT, debug=True)
    assert cfg["rows"] - cfg["tenants"] < seen["edges"] <= seen["held"]
    assert seen["arena"] <= seen["held"] + 1


def test_the_span_metrics_the_cell_has_read_something(traced):
    res, _ = traced
    mine = [m["name"] for m in contracts.span_metrics(ROOT)
            if CELL in m["workloads"]]
    assert {"dispatch.nbr_rows_per_req.nbr", "sched.hold_pct.nbr"} <= set(mine)
    for name in mine + ["index.csr_builds.nbr"]:
        assert np.isfinite(res["metrics"][name]["value"])


def test_csr_builds_reads_none_on_a_program_without_the_counters():
    class Run:
        telemetry = None
        trace = None

        def counter(self, name):
            return 0
    for name in ("index.csr_builds.nbr", "dispatch.nbr_rows_per_req.nbr",
                 "sched.hold_pct.nbr"):
        assert harness.reader(name, ROOT)(Run()) is None


def test_the_graph_forgotten_is_not_correct_by_state_errors_alone():
    bad = contracts.debug_run(CELL, 39, ROOT, sabotage=faults.edges_dropped)
    assert bad["correct"] is False
    assert bad["compared"]["state_errors"]["value"] > 0
    assert all(bad["compared"][n]["value"] <= bad["compared"][n]["limit"]
               for n in OTHERS)


def test_the_full_geometry_s_graph_fits_its_edge_arena_and_its_lists():
    """Eight tenants' rows at the FULL geometry (the first block's, made as
    set-up makes them): at most 4 edges a row, no list over
    ``serve_max_nbr``, and 1,250 tenants' worth inside ``max_edges``."""
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    starts = corpus.tenant_starts(cfg["rows"], cfg["tenants"])
    most = 0.0
    for t in range(8):
        rows = harness.tenant_rows(cfg, 39, starts, 0, t)
        assert rows.shape == (int(starts[t + 1] - starts[t]), cfg["dim"])
        edges = corpus.tenant_edges(rows, cfg["graph"])
        assert len(set((a, b) for a, b, _ in edges)) == len(edges)
        assert rows.shape[0] - 1 < len(edges) <= 4 * rows.shape[0]
        most = max(most, len(edges) / rows.shape[0])
        lists = corpus.neighbour_lists(rows.shape[0], edges)
        assert max(len(x) for x in lists) <= cfg["memory_config"]["serve_max_nbr"]
        assert max(len(x) for x in lists) <= cfg["measured"]["longest_neighbour_list"]
    assert most * cfg["rows"] <= cfg["memory_config"]["max_edges"]
    assert cfg["measured"]["edges"] <= 4 * cfg["rows"] \
        <= cfg["memory_config"]["max_edges"]


@pytest.mark.parametrize("batch", [1, 44, 64])
def test_the_boosting_demand_is_the_scan_s_plus_the_stated_terms(batch):
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    scan = files.load_module("benchmark/demands/exact_scan.py", ROOT).need(cfg, batch)
    mod = files.load_module(cfg["demand"], ROOT)
    need = mod.need(cfg, batch)
    cap, reach = 5, 32
    tail = (cap * 2 * 4              # a served row's two indptr entries
            + cap * reach * 4        # its CSR slots
            + cap * reach * (4 + 1)  # the gathered rows' tenant and alive bytes
            + (cap + cap * reach) * 3 * 4 * 2)   # three columns, read + written
    assert mod.tail_bytes(cfg) == tail == 5440
    assert need["bytes"] == scan["bytes"] + batch * tail >= scan["bytes"]
    assert (need["ops"], need["ops_peak"]) == (scan["ops"], scan["ops_peak"])
    # HBM binds, with or without the tail
    from benchmark import peaks
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert least["seconds"] == pytest.approx(need["bytes"] / 819e9)
