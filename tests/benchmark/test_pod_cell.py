"""The four-chip cell ``pod.serve`` (PR 29): the REAL entries of
``BENCHMARK.json`` against the per-cell contracts in ``--cpu-debug`` runs on
four of conftest's host devices, its shard demand against the whole one, and
the three readers of what the mesh adds against a small recorded trace of four
device planes (``data/pod_trace.json``, values by hand in its ``_note``). The
cell's int8 control (three seeds) and its timed path broken underneath (wrong
tenant mask, altered score, dropped hit) come out NOT correct in
``test_benchmark_cells.py``, which runs every cell the manifest names. What
PR 29 named of the manifest is held as ``contracts.throughput_cells`` and
``contracts.pod_metrics`` hold it, "at least": later PRs append cells that
report ``search_qps`` and ``.pod`` metrics beside the thirteen. No number read
here is a device number."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import files, harness  # noqa: E402

from lazzaro_tpu.utils.telemetry import Telemetry  # noqa: E402

CELL = "pod.serve"
M = harness.manifest(ROOT)
RAW = json.load(open(os.path.join(HERE, "data", "pod_trace.json")))
TRACE = {"devices": {p: [tuple(e) for e in v]
                     for p, v in RAW["devices"].items()},
         "spans": [tuple(e) for e in RAW["spans"]]}
POD_METRICS = [m for m in M["per_layer"] if m.get("workloads") == [CELL]]


def _entry(kind, name):
    return contracts.entry(ROOT, kind, name)


# ------------------------------------------------------- the entries, as named

def test_cell_is_named_as_the_issue_names_it():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "lme20m-mesh4", "serve-closed-128", 4)
    cell, cfg, mix = harness.cell_files(CELL, ROOT)
    assert mix["loop"] == "closed" and mix["clients"] == 128
    old = harness.load_json(os.path.join(ROOT, "benchmark/mixes/serve-closed-64.json"))
    same = set(mix) - {"name", "why", "clients", "debug"}
    assert {k: mix[k] for k in same} == {k: old[k] for k in same}
    contracts.throughput_cells(ROOT)     # search_qps: AT LEAST fill.serve, CELL


def test_configuration_is_lme5m_on_every_chip():
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    one = harness.load_json(os.path.join(ROOT, "benchmark/configs/lme5m.json"))
    assert cfg["mesh"] == {"axes": ["data"], "shape": [4]}
    assert (cfg["rows"], cfg["tenants"]) == (4 * one["rows"], 4 * one["tenants"])
    assert cfg["rows"] // cfg["tenants"] == cfg["facts_per_tenant"] == 10000
    for key in ("dim", "dtype", "k", "limits", "reference", "fill_block_rows",
                "dup_every"):
        assert cfg[key] == one[key], key
    assert cfg["reference"] == "benchmark/reference.py"   # knows of no shard
    # each shard is lme5m's arena: 5,001,216 rows = 1,221 select blocks
    from lazzaro_tpu.core import state as S
    total = cfg["memory_config"]["initial_capacity"] + 1
    assert total % 4 == 0 and total // 4 == 1221 * S.TOPK_BLOCK
    assert total // 4 == -(-(one["memory_config"]["initial_capacity"] + 1)
                           // S.TOPK_BLOCK) * S.TOPK_BLOCK
    rest = {k: v for k, v in cfg["memory_config"].items()
            if k not in ("initial_capacity", "max_buffer_size")}
    assert rest == {k: one["memory_config"][k] for k in rest}
    tiny = harness._with_debug(cfg, True)
    assert tiny["mesh"]["shape"] == [4] and tiny["rows"] % 4 == 0
    assert (tiny["memory_config"]["initial_capacity"] + 1) % (4 * S.TOPK_BLOCK) == 0


def test_thirteen_pod_metrics_each_with_its_reader():
    # AT LEAST the thirteen PR 29 named, by name; later PRs append others
    assert contracts.pod_metrics(ROOT) == POD_METRICS
    assert len(contracts.POD_THIRTEEN) == 13 <= len(POD_METRICS)


def test_real_cell_passes_the_manifest_contracts():
    contracts.cell_resolves(_entry("workloads", CELL), ROOT)
    contracts.config_entry(_entry("configs", "lme20m-mesh4"), ROOT)
    contracts.four_chip_quota(ROOT)
    for m in POD_METRICS:
        contracts.per_layer_metric(m, ROOT)


# ----------------------------------------------------------- its debug runs

def test_real_cell_passes_the_per_cell_contracts_on_four_devices():
    contracts.cell_line(CELL, ROOT, seed=2**31 + 29)


def test_real_cell_reports_the_span_metrics_it_has():
    mine = contracts.traced_debug_run_reports_span_metrics(CELL, ROOT, seed=2929)
    assert set(mine) >= contracts.POD_SPAN_FIVE       # PR 29's five, at least


def test_traced_debug_run_counts_no_copy_and_no_compile_and_four_devices():
    res = contracts.debug_run(CELL, 2930, ROOT, traced=True)
    assert res["correct"] is True and res["device"]["count"] >= 4
    assert res["metrics"]["dispatch.copies.pod"]["value"] == 0.0
    assert res["metrics"]["device.compiles.pod"]["value"] == 0.0
    assert res["metrics"]["index.stage_p50_ms.pod"]["value"] > 0
    # device-trace metrics need device planes: left out here, never 0
    for name in ("kernel.merge_dev_ms.pod", "device.skew_pct.pod",
                 "kernel.serve_roofline.pod", "device.idle_pct.pod"):
        assert name not in res["metrics"]


def test_real_cell_is_served_from_four_shards_built_by_deploys_workaround():
    seen = {}

    def look(ms):
        emb = ms.index.state.emb
        seen.update(devices=len(emb.sharding.device_set),
                    shards={s.data.shape for s in emb.addressable_shards},
                    rows=emb.shape[0], capacity=ms.config.initial_capacity,
                    tenants=len(ms.index.tenant_nodes))
    res = contracts.debug_run(CELL, 2931, ROOT, sabotage=look)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert seen["devices"] == 4 and seen["rows"] == 16384
    assert seen["shards"] == {(4096, 64)} and seen["capacity"] == 16383
    assert seen["tenants"] == 30           # 477-478 rows each: they straddle


def test_run_on_fewer_than_four_chips_exits_2_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, cwd=ROOT, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
    assert "4 TPU chip(s)" in p.stderr


# ------------------------------------------------------------ the shard demand

@pytest.mark.parametrize("batch", [1, 16, 64])
def test_shard_demand_is_a_quarter_of_the_whole_plus_the_gather(batch):
    _, cfg, _ = harness.cell_files(CELL, ROOT)
    assert cfg["demand"] == "benchmark/demands/exact_scan_shard.py"
    shard = files.load_module(cfg["demand"], ROOT).need(cfg, batch)
    whole = files.load_module("benchmark/demands/exact_scan.py", ROOT).need(
        cfg, batch)
    assert shard["ops"] * 4 == whole["ops"]
    assert shard["ops_peak"] == whole["ops_peak"]
    per_batch = batch * cfg["dim"] * 2 + batch * cfg["k"] * 8
    rows_bytes = whole["bytes"] - per_batch          # rows + their columns
    gather = 4 * batch * cfg["k"] * 8
    assert shard["bytes"] == rows_bytes / 4 + per_batch + gather
    # the same arena as fill.serve's on every chip
    _, one, _ = harness.cell_files("fill.serve", ROOT)
    alone = files.load_module(one["demand"], ROOT).need(one, batch)
    assert shard["bytes"] - gather == alone["bytes"]


# ------------------------------------------ readers of what the mesh adds

def _run(trace=TRACE, counters=()):
    run = harness.Run({"name": CELL, "chips": 4}, {}, {}, 1, 1.0, True, ROOT)
    run.trace = trace
    run.telemetry = Telemetry()
    for name, n in counters:
        run.telemetry.bump(name, n, labels={"mode": "sharded_exact"})
    return run


def test_merge_dev_ms_reads_the_operations_outside_the_scan():
    read = harness.reader("kernel.merge_dev_ms.pod", ROOT)
    assert read(_run()) == pytest.approx(25e-6, rel=1e-12)
    only_scan = {"devices": {p: [e for e in v if e[0].startswith("lz_select")]
                             for p, v in TRACE["devices"].items()},
                 "spans": TRACE["spans"]}
    assert read(_run(only_scan)) is None
    assert read(_run({"devices": {}, "spans": TRACE["spans"]})) is None
    assert read(_run(None)) is None
    no_batch = {"devices": TRACE["devices"], "spans": TRACE["spans"][:1]}
    assert read(_run(no_batch)) is None


def test_skew_reads_every_device_plane():
    read = harness.reader("device.skew_pct.pod", ROOT)
    assert read(_run()) == pytest.approx(25.0, rel=1e-12)
    planes = list(TRACE["devices"].items())
    assert read(_run({"devices": dict(planes[:3]),
                      "spans": TRACE["spans"]})) == pytest.approx(
        100.0 * (650 / 600 - 1), rel=1e-12)       # the fourth plane counted
    assert read(_run({"devices": dict(planes[:1]),
                      "spans": TRACE["spans"]})) is None
    idle = dict(planes[:3] + [("/device:TPU:3", [("x", 5000.0, 10.0)])])
    assert read(_run({"devices": idle, "spans": TRACE["spans"]})) is None
    assert read(_run(None)) is None


def test_copies_read_zero_when_counted_and_none_when_not():
    read = harness.reader("dispatch.copies.pod", ROOT)
    assert read(_run(None)) is None            # the parent: counts neither
    assert read(_run(None, [("serve.merge_candidates", 4 * 64 * 128)])) == 0.0
    assert read(_run(None, [("serve.merge_candidates", 4 * 64 * 128),
                            ("serve.copy_dispatches", 3)])) == 3.0
    bare = _run(None)
    bare.telemetry = None
    assert read(bare) is None


def test_pod_readers_copied_from_accepted_ones_read_the_same_trace_alike():
    run = _run(counters=[("serve.batches", 2), ("serve.live_requests", 128),
                         ("serve.padded_slots", 128)])
    for pod, accepted in [
            ("dispatch.launch_p50_ms.pod", "dispatch.launch_p50_ms.lat"),
            ("dispatch.readback_p50_ms.pod", "dispatch.readback_p50_ms.lat"),
            ("index.stage_p50_ms.pod", "index.stage_p50_ms.lat"),
            ("kernel.serve_dev_ms.pod", "kernel.serve_dev_ms.qps"),
            ("device.idle_pct.pod", "device.idle_pct.qps"),
            ("sched.occupancy_pct.pod", "sched.occupancy_pct"),
            ("dispatch.p50_ms.pod", "dispatch.p50_ms.qps")]:
        assert (harness.reader(pod, ROOT)(run)
                == harness.reader(accepted, ROOT)(run)), pod
    assert harness.reader("index.stage_p50_ms.pod", ROOT)(run) == 5e-6
    assert harness.reader("kernel.serve_dev_ms.pod", ROOT)(run) == \
        pytest.approx((330 + 320) / 2 / 1e6)     # first plane, inside the spans
    assert harness.reader("sched.occupancy_pct.pod", ROOT)(run) == 100.0
