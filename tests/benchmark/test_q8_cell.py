"""The one-chip int8 cell ``fill.q8`` (PR 36): the REAL entries of
``BENCHMARK.json`` against the per-cell contracts in ``--cpu-debug`` runs, the
configuration against ``lme5m``'s, the demand's arithmetic at the published
sizes, the plain two-stage reference against the exact one, the nine readers
against a small trace made by hand and a registry made by hand, and the cell's
control: the coarse stage's answers served as they are come out NOT correct.
(``test_benchmark_cells.py`` runs every cell the manifest names, this one too:
its int8 control on three seeds and its timed path broken underneath.) No
number read here is a device number."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import files, harness, peaks  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

CELL, CONFIG = "fill.q8", "lme5m-int8"
M = harness.manifest(ROOT)
NINE = {
    "kernel.serve_dev_ms.q8": ("ms", "lower", "device_trace", "kernels"),
    "kernel.serve_roofline.q8": ("%", "higher", "device_trace", "kernels"),
    "kernel.rescore_dev_ms.q8": ("ms", "lower", "device_trace", "kernels"),
    "dispatch.quant_pct.q8": ("%", "higher", "program_counter", "dispatch"),
    "dispatch.p50_ms.q8": ("ms", "lower", "program_span", "dispatch"),
    "sched.overlap_pct.q8": ("%", "higher", "program_counter", "scheduler"),
    "sched.batch_requests_mean.q8": ("count", "higher", "program_counter",
                                     "scheduler"),
    "device.idle_pct.q8": ("%", "lower", "device_trace", "device"),
    "device.compiles.q8": ("count", "lower", "program_counter", "device"),
}


# ------------------------------------------------------- the entries, as named

def test_cell_is_named_as_the_issue_names_it():
    w = contracts.entry(ROOT, "workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "serve-closed-128", 1)
    cell, cfg, mix = harness.cell_files(CELL, ROOT)
    assert mix["loop"] == "closed" and mix["clients"] == 128 and mix["k"] == 5
    # what a caller waits, not requests a second: the driver holds a new
    # cell's spread to the bound in the PARENT's units, and 8% of the
    # parent's 380.8 req/s is 0.44% of this program's own level (PERF.md §6)
    # (later cells append themselves: the list starts as PR 36 left it)
    assert contracts.entry(ROOT, "end_to_end", "search_p50_ms")[
        "workloads"][:2] == ["share.serve", CELL]
    assert [m["name"] for m in harness.metrics_of(cell, "end_to_end", ROOT)] \
        == ["search_p50_ms", "setup_s"]
    # the tail spreads 6% over six seeds here, the median 1.4% (PERF.md §2)
    for name in ("search_qps", "search_p95_ms"):
        assert CELL not in contracts.entry(ROOT, "end_to_end",
                                           name)["workloads"]
    contracts.throughput_cells(ROOT)
    contracts.cell_resolves(w, ROOT)
    contracts.four_chip_quota(ROOT)
    assert sum(x["chips"] == 4 for x in M["workloads"]) == 1


@pytest.mark.parametrize("name", list(NINE))
def test_each_of_the_nine_metrics_is_named_and_has_its_reader(name):
    e = contracts.entry(ROOT, "per_layer", name)
    unit, better, source, layer = NINE[name]
    assert e == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": layer, "moves": "search_p50_ms",
                 "workloads": [CELL]}
    contracts.per_layer_metric(e, ROOT)
    assert callable(harness.reader(name, ROOT))
    # no new layer name: every layer is one an accepted cell's metric names
    assert layer in {x["layer"] for x in M["per_layer"]
                     if CELL not in x.get("workloads", [])}


def test_the_cell_reports_exactly_its_nine():
    cell = harness.cell_files(CELL, ROOT)[0]
    assert [m["name"] for m in harness.metrics_of(cell, "per_layer", ROOT)] \
        == list(NINE)


def test_configuration_is_lme5m_with_the_mode_switched_on():
    contracts.config_entry(contracts.entry(ROOT, "configs", CONFIG), ROOT)
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmark/configs/lme5m-int8.json"))
    one = harness.load_json(os.path.join(ROOT, "benchmark/configs/lme5m.json"))
    for key in ("rows", "tenants", "dim", "dtype", "fill_block_rows",
                "facts_per_tenant", "dup_every", "k", "limits", "reduced",
                "published", "debug"):
        assert cfg[key] == one[key], key
    mc = dict(cfg["memory_config"])
    assert mc.pop("int8_serving") is True and mc == one["memory_config"]
    # the coarse fetch is the program's two defaults, named where assumed
    from lazzaro_tpu.config import MemoryConfig
    d = MemoryConfig()
    assert cfg["coarse_fetch"] == d.serve_k_max + d.coarse_fetch_slack == 136
    assert "serve_k_max" in cfg["assumed"]["coarse_fetch"]
    assert "coarse_fetch_slack" in cfg["assumed"]["coarse_fetch"]
    # resident: master + codes + scales over the arena the program rounds to
    from lazzaro_tpu.core import state as S
    arena = -(-(mc["initial_capacity"] + 1) // S.TOPK_BLOCK) * S.TOPK_BLOCK
    assert arena == 5_001_216 == 1221 * 4096
    assert cfg["resident_bytes"] == arena * 768 * (2 + 1) + arena * 4
    assert 0.70 < cfg["resident_bytes"] / 16e9 < 0.75
    assert cfg["reference"] == "benchmark/reference_two_stage_q8.py"
    assert cfg["demand"] == "benchmark/demands/int8_two_stage_q8.py"
    assert any("136 best by int8 score" in g for g in cfg["guarantees"])


# ------------------------------------------------------------------ the demand

def test_demand_at_the_published_sizes():
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    need = files.load_module(cfg["demand"], ROOT).need(cfg, 64)
    rows, dim, fetch, k = 5_000_000, 768, 136, 5
    assert need["bytes"] == (rows * dim + rows * 4 + rows * 5
                             + 64 * fetch * dim * 2 + 64 * dim * 4 + 64 * k * 8)
    assert need["ops"] == 2.0 * 64 * rows * dim
    assert need["ops_peak"] == "int8_ops_per_s"
    least = peaks.least_seconds(need, peaks.peaks_for("TPU v5 lite"))
    assert least["bound"] == "hbm"
    assert least["seconds"] * 1e3 == pytest.approx(4.760, abs=0.005)
    assert least["ops_s"] * 1e3 == pytest.approx(1.25, abs=0.01)
    # half the exact scan's bytes, and the rescore's operations are nothing
    exact = files.load_module("benchmark/demands/exact_scan.py", ROOT).need(
        cfg, 64)
    assert 0.50 < need["bytes"] / exact["bytes"] < 0.51
    assert 2.0 * 64 * fetch * dim * 36_000 < need["ops"]
    # what PR 35's line read on the parent: 4.76 ms over 166.6 ms
    assert 100 * least["seconds"] * 1e3 / 166.6 == pytest.approx(2.857, abs=0.01)


# --------------------------------------------------------------- the reference

def test_reference_is_two_stage_and_reads_its_fetch_from_the_configuration():
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    ref = files.load_module(cfg["reference"], ROOT)
    exact = files.load_module("benchmark/reference.py", ROOT)
    assert ref.coarse_fetch() == cfg["coarse_fetch"] == 136
    assert "lazzaro" not in open(os.path.join(ROOT, cfg["reference"])).read()
    rng = np.random.default_rng(36)
    rows = exact.stored(rng.standard_normal((700, 64)), "bfloat16")
    live = rng.random(700) > 0.1
    q = rng.standard_normal((6, 64)).astype(np.float32)
    # codes by the documented rule
    codes, scale = ref.int8_codes(rows)
    assert np.abs(codes).max() == 127 and (codes == np.rint(codes)).all()
    np.testing.assert_allclose(scale[:, 0], np.abs(rows).max(1) / 127, rtol=1e-6)
    # a fetch as wide as the tenant is the exact answer
    wide = ref.topk_two_stage(rows, live, ref.unit(q), ref.unit(q), 5, 700)
    want = exact.topk_exact(rows, live, ref.unit(q), 5)
    np.testing.assert_array_equal(wide[1], want[1])
    np.testing.assert_array_equal(wide[0], want[0])
    # a fetch of k is the coarse stage's choice, exactly rescored
    bare = ref.topk_two_stage(rows, live, ref.unit(q), ref.unit(q), 5, 5)
    coarse = ref.coarse_scores(rows, live, ref.unit(q))
    assert (np.sort(bare[1], 1) == np.sort(
        np.argsort(-coarse, 1, kind="stable")[:, :5], 1)).all()
    assert live[bare[1]].all() and (np.diff(bare[0], axis=1) <= 0).all()
    np.testing.assert_array_equal(
        bare[0], np.take_along_axis(bare[2], bare[1], 1))
    # the control serves int8 scores: off by more than the limit somewhere
    ctl = ref.int8_answers(rows, live, q, 5)
    gap = max(abs(s - want[2][i][j]) for i, (idx, sc) in enumerate(ctl)
              for j, s in zip(idx, sc))
    assert gap > 3 * cfg["limits"]["score_gap"]


# ----------------------------------------------------------- its debug runs

def test_real_cell_passes_the_per_cell_contracts():
    contracts.cell_line(CELL, ROOT, seed=2**31 + 36)


def test_real_cell_reports_the_span_metrics_it_has():
    mine = contracts.traced_debug_run_reports_span_metrics(CELL, ROOT,
                                                           seed=3636)
    assert set(mine) == {"sched.overlap_pct.q8", "sched.batch_requests_mean.q8"}


def test_traced_debug_run_is_served_by_the_int8_program_alone():
    seen = {}

    def look(ms):
        idx = ms.index
        seen.update(int8=idx.int8_serving, shadow=idx._int8_shadow,
                    slack=idx.coarse_slack, k_max=idx.serve_k_max)
    res = contracts.debug_run(CELL, 3637, ROOT, traced=True, sabotage=look)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert seen["int8"] and seen["shadow"] is not None
    assert seen["k_max"] + seen["slack"] == 136
    got = {n: v["value"] for n, v in res["metrics"].items()}
    assert got["dispatch.quant_pct.q8"] == 100.0
    assert got["device.compiles.q8"] == 0.0
    assert got["sched.batch_requests_mean.q8"] > 1.0
    assert got["dispatch.p50_ms.q8"] > 0
    # device-trace metrics need a device plane: left out here, never 0
    for name in ("kernel.serve_dev_ms.q8", "kernel.serve_roofline.q8",
                 "kernel.rescore_dev_ms.q8", "device.idle_pct.q8"):
        assert name not in got


@pytest.mark.parametrize("seed", [36, 37, 38])
def test_int8_control_is_not_correct(seed):
    res = contracts.debug_run(CELL, seed, ROOT, control="int8", seconds=0.4)
    assert res["correct"] is False
    gap = res["compared"]["score_gap"]
    assert gap["value"] > 3 * gap["limit"]


def test_exact_cells_still_name_the_exact_reference():
    for cell in ("share.serve", "fill.serve", "share.ingest", "pod.serve"):
        _, cfg, _ = harness.cell_files(cell, ROOT)
        assert cfg["reference"] == "benchmark/reference.py"
        assert "int8_serving" not in cfg["memory_config"]


# ------------------------------------------------------------- the readers

TRACE = {
    # window 0..1000 ns, two lz.serve.batch spans; the scan 100..400 and
    # 500..800, outside it a gather 410..430 with a fusion 415..425 inside
    # it and a top_k 810..820: union 30 ns over 2 dispatches = 15 ns; busy
    # inside the spans' union 320 + 310 = 630 ns -> 315 ns a dispatch
    "devices": {"/device:TPU:0": [
        ("lz_select_scan_q8.1_f32_64_256_", 100.0, 300.0),
        ("fusion.7_bf16_8704_768_", 410.0, 20.0),
        ("fusion.8_f32_64_136_", 415.0, 10.0),
        ("lz_select_scan_q8.1_f32_64_256_", 500.0, 300.0),
        ("top_k.2_f32_64_128_", 810.0, 10.0),
        ("lz_select_scan_q8.1_f32_64_256_", 1100.0, 300.0)]},
    "spans": [("bench.window", 0.0, 1000.0), ("lz.serve.batch", 90.0, 350.0),
              ("lz.serve.quant", 95.0, 340.0), ("lz.serve.batch", 490.0, 340.0),
              ("lz.serve.quant", 495.0, 330.0)],
}


def _run(trace=TRACE, counters=(), cfg=None):
    run = harness.Run({"name": CELL, "chips": 1}, cfg or {}, {}, 1, 1.0, True,
                      ROOT)
    run.trace = trace
    run.telemetry = Telemetry()
    run.device_kind = "TPU v5 lite"
    for name, n, labels in counters:
        run.telemetry.bump(name, n, labels=labels)
    return run


def test_rescore_dev_ms_reads_the_operations_outside_the_int8_scan():
    read = harness.reader("kernel.rescore_dev_ms.q8", ROOT)
    assert read(_run()) == pytest.approx(15e-6, rel=1e-12)
    # a program without the kernel (the parent) does all its work outside it
    dense = {"devices": {"/device:TPU:0": [("sort.1_f32_64_5001216_", 100.0, 300.0),
                                           ("sort.1_f32_64_5001216_", 500.0, 300.0)]},
             "spans": TRACE["spans"]}
    assert read(_run(dense)) == pytest.approx(300e-6, rel=1e-12)
    assert read(_run(dense)) == harness.reader("kernel.serve_dev_ms.q8",
                                               ROOT)(_run(dense))
    only_scan = {"devices": {"/device:TPU:0": [
        e for e in TRACE["devices"]["/device:TPU:0"]
        if e[0].startswith("lz_select_scan_q8")]}, "spans": TRACE["spans"]}
    assert read(_run(only_scan)) is None
    assert read(_run({"devices": {}, "spans": TRACE["spans"]})) is None
    assert read(_run(None)) is None


def test_serve_dev_ms_and_roofline_read_the_whole_dispatch():
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    run = _run(cfg=cfg, counters=[("serve.batches", 2, None),
                                  ("serve.live_requests", 128, None)])
    dev = harness.reader("kernel.serve_dev_ms.q8", ROOT)(run)
    assert dev == pytest.approx(315e-6, rel=1e-12)
    share = harness.reader("kernel.serve_roofline.q8", ROOT)(run)
    least = peaks.least_seconds(
        files.load_module(cfg["demand"], ROOT).need(cfg, 64),
        peaks.peaks_for("TPU v5 lite"))["seconds"]
    assert share == pytest.approx(100 * least * 1e3 / dev, rel=1e-12)
    assert harness.reader("kernel.serve_roofline.q8", ROOT)(_run(None)) is None
    assert harness.reader("device.idle_pct.q8", ROOT)(run) == pytest.approx(
        100 * (1 - 630 / 1000), rel=1e-12)


@pytest.mark.parametrize("counters,want", [
    ([], None),                                          # counted nothing
    ([("serve.dispatches", 8, {"mode": "quant"})], 100.0),
    ([("serve.dispatches", 8, {"mode": "exact"})], 0.0),  # fell back to exact
    ([("serve.dispatches", 6, {"mode": "quant"}),
      ("serve.dispatches", 2, {"mode": "exact"})], 75.0),
    ([("serve.dispatches", 5, {"mode": "sharded_quant"})], 0.0),
    ([("serve.dispatches_other", 5, {"mode": "quant"})], None),
], ids=["none", "all_quant", "all_exact", "mixed", "another_mode",
        "another_counter"])
def test_quant_pct_reads_the_dispatches_label(counters, want):
    read = harness.reader("dispatch.quant_pct.q8", ROOT)
    assert read(_run(None, counters)) == want
    bare = _run(None)
    bare.telemetry = None
    assert read(bare) is None


def test_readers_copied_from_accepted_ones_read_the_same_run_alike():
    run = _run(counters=[("serve.batches", 2, None), ("serve.requests", 128, None),
                         ("serve.queue_wait_us", 5, None),
                         ("serve.overlapped_batches", 1, None)])
    for mine, accepted in [
            ("kernel.serve_dev_ms.q8", "kernel.serve_dev_ms.qps"),
            ("device.idle_pct.q8", "device.idle_pct.qps"),
            ("device.compiles.q8", "device.compiles.qps"),
            ("dispatch.p50_ms.q8", "dispatch.p50_ms.qps"),
            ("sched.overlap_pct.q8", "sched.overlap_pct.pod"),
            ("sched.batch_requests_mean.q8", "sched.batch_requests_mean.qps")]:
        assert (harness.reader(mine, ROOT)(run)
                == harness.reader(accepted, ROOT)(run)), mine
    assert harness.reader("sched.overlap_pct.q8", ROOT)(run) == 50.0
    assert harness.reader("sched.batch_requests_mean.q8", ROOT)(run) == 64.0
