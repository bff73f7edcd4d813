"""Every cell end to end at a tiny geometry on the CPU: the plain reference
and the program agree on ids and scores for both configurations' corpus
geometry; the int8 control and a timed path broken underneath come out NOT
correct. These runs skip only the harness's look for a chip; no number they
read is a device number."""

import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELLS = [w["name"] for w in harness.manifest(ROOT)["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run(cell, seed, **kw):
    return harness.run_cell(cell, seed, kw.pop("seconds", 0.6),
                            kw.pop("traced", False), debug=True, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_its_line_has_the_contracts_keys(cell):
    res = run(cell, seed=2**31 + 7)
    assert list(res)[:5] == KEYS and list(res)[-1] == "compared"
    assert set(res) == set(KEYS) | {"compared"}
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    want = {m["name"] for m in harness.metrics_of(
        harness.cell_files(cell, ROOT)[0], "end_to_end")}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    gap = res["compared"]["score_gap"]
    assert gap["value"] <= 1e-6 < gap["limit"]       # bf16 products are exact
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_it_can_read(cell):
    res = run(cell, seed=11, traced=True)
    assert res["correct"] is True
    names = {m["name"] for m in harness.metrics_of(
        harness.cell_files(cell, ROOT)[0], "per_layer")}
    assert set(res["metrics"]) <= names
    # counters and spans read on any backend; device-trace metrics need a
    # device plane and are left out here, never reported as 0
    assert any(n.startswith("device.compiles") for n in res["metrics"])
    assert not any(n.startswith(("kernel.", "device.idle")) for n in res["metrics"])
    assert [v["value"] for n, v in res["metrics"].items()
            if n.startswith("device.compiles")] == [0.0]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_int8_control_in_the_programs_place_is_not_correct(cell, seed):
    res = run(cell, seed=seed, control="int8", seconds=0.4)
    assert res["correct"] is False
    gap = res["compared"]["score_gap"]
    assert gap["value"] > 3 * gap["limit"]


def _wrong_tenant_mask(ms):
    st = ms.index.state
    ms.index.state = st.replace(tenant_id=jnp.roll(st.tenant_id, 200))


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


@pytest.mark.parametrize("cell", [c for c in CELLS if "serve" in c])
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
