"""Every cell end to end at a tiny geometry on the CPU: the plain reference
and the program agree on ids and scores for both configurations' corpus
geometry; the int8 control and a timed path broken underneath come out NOT
correct. These runs skip only the harness's look for a chip; no number they
read is a device number."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import harness  # noqa: E402

CELLS = [w["name"] for w in harness.manifest(ROOT)["workloads"]]


def run(cell, seed, **kw):
    return contracts.debug_run(cell, seed, ROOT, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_its_line_has_the_contracts_keys(cell):
    contracts.cell_line(cell, ROOT)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_it_can_read(cell):
    contracts.traced_line(cell, ROOT)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_int8_control_in_the_programs_place_is_not_correct(cell, seed):
    res = run(cell, seed=seed, control="int8", seconds=0.4)
    assert res["correct"] is False
    gap = res["compared"]["score_gap"]
    assert gap["value"] > 3 * gap["limit"]


def _wrong_tenant_mask(ms):
    contracts.wrong_tenant_mask(ms)


def _misrouted_answers(ms):
    real = ms.index.search_fused_requests

    def swapped(reqs, **kw):
        out = real(reqs, **kw)
        return out[1:] + out[:1] if len(out) > 1 else out[::-1]
    ms.index.search_fused_requests = swapped


def _altered_score(ms):
    real = ms.index.search_fused_requests

    def nudged(reqs, **kw):
        out = real(reqs, **kw)
        for r in out:
            r.scores = [s + 1e-3 for s in r.scores]
        return out
    ms.index.search_fused_requests = nudged


def _dropped_result(ms):
    real = ms.index.search_fused_requests

    def short(reqs, **kw):
        out = real(reqs, **kw)
        for r in out:
            r.ids, r.scores = r.ids[:-1], r.scores[:-1]
        return out
    ms.index.search_fused_requests = short


# the serving cells whose answers the exact reference decides (the two-stage
# int8 cell's own faults are in test_q8_cell.py)
def _served_exactly(cell):
    _, cfg, mix = harness.cell_files(cell, ROOT)
    return (mix["loop"] in ("open", "closed")
            and cfg["reference"] == "benchmark/reference.py")


SERVING = [c for c in CELLS if _served_exactly(c)]


@pytest.mark.parametrize("cell", SERVING)
@pytest.mark.parametrize("fault,number", [
    (_wrong_tenant_mask, "foreign_ids"), (_altered_score, "score_gap"),
    (_dropped_result, "count_errors")], ids=lambda f: getattr(f, "__name__", f))
def test_broken_timed_path_is_not_correct(cell, fault, number):
    res = run(cell, seed=21, sabotage=fault)
    assert res["correct"] is False
    v = res["compared"][number]
    assert v["value"] > v["limit"]


def test_misrouted_answers_are_not_correct():
    # closed loop, 8 clients: every dispatch carries several tenants
    res = run("fill.serve", seed=22, sabotage=_misrouted_answers)
    assert res["correct"] is False
    assert res["compared"]["foreign_ids"]["value"] > 0


def _dedup_off(ms):
    ms.config.dedup_similarity = 2.0       # nothing is ever a duplicate


def _ingest_wrong_tenant(ms):
    real = ms.index.search_fused_requests

    def other(reqs, **kw):
        import dataclasses
        names = sorted(ms.index.tenant_nodes)
        return real([dataclasses.replace(r, tenant=names[0]) for r in reqs], **kw)
    ms.index.search_fused_requests = other


@pytest.mark.parametrize("fault,number", [
    (_dedup_off, "rank_errors"), (_ingest_wrong_tenant, "foreign_ids")],
    ids=lambda f: getattr(f, "__name__", f))
def test_broken_write_path_is_not_correct(fault, number):
    res = run("share.ingest", seed=23, sabotage=fault)
    assert res["correct"] is False
    v = res["compared"][number]
    assert v["value"] > v["limit"]
