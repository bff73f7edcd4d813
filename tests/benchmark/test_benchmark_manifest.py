"""BENCHMARK.json against the contract it is checked by, and the harness's
promise that a later PR adds a cell with files and entries alone: every
assertion is a function of a checkout's root (``contracts.py``), called here
on the repository and again on a temporary checkout that three cells were
added to: one on four chips over a sharded configuration, and a one-chip
closed-loop cell of an int8 configuration that reports ``search_qps`` with
its metrics appended after the manifest's last entry — what the next
``model_config`` PR brings."""

import functools
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402
from benchmark import files, harness, peaks  # noqa: E402

M = harness.manifest(ROOT)


def test_top_level_keys_and_size():
    contracts.top_level(ROOT)


@pytest.mark.parametrize("c", M["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    contracts.config_entry(c, ROOT)


def test_config_files_and_names_are_distinct():
    contracts.names_distinct(ROOT)


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files(w):
    contracts.cell_resolves(w, ROOT)


def test_cells_on_four_chips_within_quota():
    contracts.four_chip_quota(ROOT)


@pytest.mark.parametrize("m", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    contracts.end_to_end_metric(m, ROOT)


def test_setup_s_is_reported_by_every_cell():
    contracts.setup_s(ROOT)


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    contracts.per_layer_metric(m, ROOT)


# ------------------------------------------------- what a later PR will meet

def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text)


def _add_int8_cell(root, m):
    """Another size, a serving mode of the program switched on by its field
    name, a reference, a demand, a mix and a metric of the new cell's own."""
    cfg = harness.load_json(os.path.join(root, "benchmark/configs/share131k.json"))
    cfg.update(name="share262k", rows=262144,
               reference="benchmark/reference_recall.py",
               demand="benchmark/demands/int8_scan.py")
    cfg["memory_config"].update(initial_capacity=262208, int8_serving=True)
    cfg["debug"]["memory_config"].update(semantic_cache=True)
    _write(root, "benchmark/configs/share262k.json", json.dumps(cfg))
    _write(root, "benchmark/reference_recall.py",
           "from benchmark.reference import *  # noqa: F401,F403\n"
           "MARK = 'recall'\n")
    _write(root, "benchmark/demands/int8_scan.py",
           "def need(cfg, batch):\n    return {'bytes': cfg['rows'] * "
           "cfg['dim'], 'ops': 1.0, 'ops_peak': 'int8_ops_per_s'}\n")
    mix = harness.load_json(os.path.join(root, "benchmark/mixes/serve-open-zipf.json"))
    mix.update(name="serve-open-burst", rate_rps=50)
    _write(root, "benchmark/mixes/serve-open-burst.json", json.dumps(mix))
    _write(root, "benchmark/metrics/sched.batch_p50.lat.py",
           "from benchmark.readers import timer_p50\n\n\n"
           "def read(run):\n    return timer_p50(run, 'serve.batch_requests')\n")
    m["configs"].append({"name": "share262k", "source": "s", "why": "w",
                         "file": "benchmark/configs/share262k.json",
                         "reduced": ["rows", "tenants"]})
    m["workloads"].append({"name": "share2.burst", "config": "share262k",
                           "traffic": "serve-open-burst", "chips": 1, "why": "w"})
    for e in m["end_to_end"]:
        if e["name"] in ("search_p50_ms", "search_p95_ms"):
            e["workloads"].append("share2.burst")
    m["per_layer"].append({"name": "sched.batch_p50.lat", "unit": "count",
                           "better": "higher", "source": "program_span",
                           "layer": "scheduler", "moves": "search_p50_ms",
                           "workloads": ["share2.burst"]})


def _add_pod_cell(root, m):
    """A configuration that names its layout, four chips, on a mix the
    benchmark has, with a span metric, a counter metric and a
    ``device.compiles.*`` of the cell's own: what ``pod.serve`` will be
    (under names no real cell will take)."""
    cfg = harness.load_json(os.path.join(root, "benchmark/configs/lme5m.json"))
    cfg.update(name="later-mesh4", rows=20000000, tenants=2000,
               mesh={"axes": ["data"], "shape": [4]},
               demand="benchmark/demands/later_shard.py")
    cfg["memory_config"].update(initial_capacity=20004863)
    cfg["debug"]["mesh"] = {"shape": [4]}
    _write(root, "benchmark/configs/later-mesh4.json", json.dumps(cfg))
    _write(root, "benchmark/demands/later_shard.py",
           "from benchmark.demands.exact_scan import need as whole\n\n\n"
           "def need(cfg, batch):\n"
           "    return whole(dict(cfg, rows=cfg['rows'] // 4), batch)\n")
    readers = {
        "dispatch.readback_p50_ms.later": (
            "program_span", "dispatch", "ms", "lower",
            "from benchmark.span_metrics import span_p50_ms\n\n\n"
            "def read(run):\n"
            "    return span_p50_ms(run, 'lz.dispatch.readback')\n"),
        "sched.lone_dispatch_pct.later": (
            "program_counter", "scheduler", "%", "lower",
            "from benchmark.span_metrics import counter_ratio\n\n\n"
            "def read(run):\n"
            "    return counter_ratio(run, 'serve.lone_batches', "
            "'serve.batches', 100.0, marker='serve.queue_wait_us')\n"),
        "device.compiles.later": (
            "program_counter", "device", "count", "lower",
            "from benchmark.readers import compiles\n\n\n"
            "def read(run):\n    return compiles(run)\n"),
    }
    for name, (source, layer, unit, better, text) in readers.items():
        _write(root, f"benchmark/metrics/{name}.py", text)
        m["per_layer"].append({"name": name, "unit": unit, "better": better,
                               "source": source, "layer": layer,
                               "moves": "search_qps", "workloads": ["later.pod"]})
    m["configs"].append({"name": "later-mesh4", "source": "s", "why": "w",
                         "file": "benchmark/configs/later-mesh4.json",
                         "reduced": ["tenants"]})
    m["workloads"].append({"name": "later.pod", "config": "later-mesh4",
                           "traffic": "serve-closed-64", "chips": 4, "why": "w"})
    for e in m["end_to_end"]:
        if e["name"] == "search_qps":
            e["workloads"].append("later.pod")


def _add_q8_cell(root, m):
    """What the next ``model_config`` PR appends: a ONE-chip closed-loop
    throughput cell of a configuration with ``int8_serving``, with a
    reference and a demand file of its own, reporting ``search_qps``, its
    kernel's roofline share and a counter metric — all appended AFTER the
    manifest's last entries."""
    cfg = harness.load_json(os.path.join(root, "benchmark/configs/lme5m.json"))
    cfg.update(name="later-int8", reference="benchmark/reference_two_stage.py",
               demand="benchmark/demands/int8_two_stage.py")
    cfg["memory_config"].update(int8_serving=True)
    _write(root, "benchmark/configs/later-int8.json", json.dumps(cfg))
    _write(root, "benchmark/reference_two_stage.py",
           "from benchmark.reference import *  # noqa: F401,F403\n"
           "MARK = 'two-stage'\n")
    _write(root, "benchmark/demands/int8_two_stage.py",
           "def need(cfg, batch):\n"
           "    rows = cfg['memory_config']['initial_capacity'] + 1\n"
           "    return {'bytes': rows * (cfg['dim'] + 9.0) + batch * 8 * "
           "cfg['dim'] * 2, 'ops': 2.0 * batch * rows * cfg['dim'],\n"
           "            'ops_peak': 'int8_ops_per_s'}\n")
    readers = {
        "kernel.serve_roofline.later": (
            "device_trace", "kernels", "%", "higher",
            "from benchmark.readers import serve_roofline_pct\n\n\n"
            "def read(run):\n    return serve_roofline_pct(run)\n"),
        "sched.batch_requests_mean.later": (
            "program_counter", "scheduler", "count", "higher",
            "from benchmark.span_metrics import counter_ratio\n\n\n"
            "def read(run):\n    return counter_ratio(run, 'serve.requests',"
            " 'serve.batches')\n"),
    }
    for name, (source, layer, unit, better, text) in readers.items():
        _write(root, f"benchmark/metrics/{name}.py", text)
        m["per_layer"].append({"name": name, "unit": unit, "better": better,
                               "source": source, "layer": layer,
                               "moves": "search_qps", "workloads": ["later.q8"]})
    m["configs"].append({"name": "later-int8", "source": "s", "why": "w",
                         "file": "benchmark/configs/later-int8.json",
                         "reduced": ["tenants"]})
    m["workloads"].append({"name": "later.q8", "config": "later-int8",
                           "traffic": "serve-closed-128", "chips": 1, "why": "w"})
    for e in m["end_to_end"]:
        if e["name"] == "search_qps":
            e["workloads"].append("later.q8")


def _add_graph_cell(root, m):
    """What the next ``model_config`` PR fills: a configuration that names
    its ``graph`` (edges installed after the rows), a closed-loop mix whose
    every request is a ``chat`` retrieval, and per-layer metrics that are
    two-line files of their families — files and appended entries alone."""
    cfg = harness.load_json(os.path.join(root, "benchmark/configs/share131k.json"))
    cfg.update(name="later-graph",
               graph={"chain_weight": 0.5, "nearest": 3, "gate": 0.5,
                      "weight_scale": 0.8})
    cfg["assumed"]["graph"] = "MemoryConfig's chain_link_weight, " \
        "cross_link_top_k, link_gate, link_weight_scale (the defaults)"
    cfg["memory_config"].update(max_edges=1048576)
    _write(root, "benchmark/configs/later-graph.json", json.dumps(cfg))
    mix = harness.load_json(os.path.join(root, "benchmark/mixes/serve-closed-128.json"))
    chat = harness.load_json(os.path.join(root, "benchmark/mixes/chat-open-zipf.json"))
    mix.update(name="chat-closed-128", boost_share=1.0, boost=chat["boost"],
               limits=chat["limits"])
    _write(root, "benchmark/mixes/chat-closed-128.json", json.dumps(mix))
    family = "from benchmark.families import reader_for\n\n" \
             "read = reader_for(__file__)\n"
    for name, unit, better, source, layer in [
            ("sched.overlap_pct.graph", "%", "higher", "program_counter",
             "scheduler"),
            ("dispatch.boost_rows_per_req.graph", "count", "higher",
             "program_counter", "dispatch"),
            ("device.compiles.graph", "count", "lower", "program_counter",
             "device")]:
        _write(root, f"benchmark/metrics/{name}.py", family)
        m["per_layer"].append({"name": name, "unit": unit, "better": better,
                               "source": source, "layer": layer,
                               "moves": "search_qps",
                               "workloads": ["later.graph"]})
    m["configs"].append({"name": "later-graph", "source": "s", "why": "w",
                         "file": "benchmark/configs/later-graph.json",
                         "reduced": ["rows", "tenants"]})
    m["workloads"].append({"name": "later.graph", "config": "later-graph",
                           "traffic": "chat-closed-128", "chips": 1, "why": "w"})
    for e in m["end_to_end"]:
        if e["name"] == "search_qps":
            e["workloads"].append("later.graph")


class Later:
    """A checkout as a later PR leaves it: the repository's manifest and
    paths, and the cells above added to them."""

    def __init__(self, root):
        self.root = root
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        for p in M["paths"]:
            shutil.copytree(os.path.join(ROOT, p), os.path.join(root, p),
                            ignore=shutil.ignore_patterns("__pycache__"))
        self.before = {}
        for d, _, names in os.walk(root):
            for f in names:
                p = os.path.join(d, f)
                if not p.endswith("BENCHMARK.json"):
                    self.before[p] = open(p, "rb").read()
        m = harness.manifest(root)
        _add_int8_cell(root, m)
        _add_pod_cell(root, m)
        _add_q8_cell(root, m)
        _add_graph_cell(root, m)
        _write(root, "BENCHMARK.json", json.dumps(m))

    def nothing_was_edited(self):
        for p, content in self.before.items():
            assert open(p, "rb").read() == content, f"{p} had to be edited"
        # entries were appended: every list of the manifest starts as it was
        m = harness.manifest(self.root)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            for old, new in zip(M[key], m[key]):
                lists = {k for k in old if isinstance(old[k], list)}
                assert {k: v for k, v in new.items() if k not in lists} == \
                    {k: v for k, v in old.items() if k not in lists}
                assert all(new[k][:len(old[k])] == old[k] for k in lists)
        assert all(m[k] == M[k] for k in ("command", "paths", "run_seconds"))


@pytest.fixture(scope="module")
def later(tmp_path_factory):
    return Later(str(tmp_path_factory.mktemp("checkout")))


def test_a_later_pr_adds_a_cell_with_files_and_entries_alone(later, tmp_path):
    root = later.root
    cell, cfg2, mix2 = harness.cell_files("share2.burst", root)
    assert cfg2["rows"] == 262144 and mix2["rate_rps"] == 50
    assert files.load_module(cfg2["reference"], root).MARK == "recall"
    run = harness.Run(cell, cfg2, mix2, 1, 1.0, True, root)
    assert files.load_module(cfg2["demand"], run.root).need(cfg2, 4)[
        "bytes"] == 262144 * 768
    # the field reaches the program as the file states it, here and in the
    # tiny geometry the tests run
    from benchmark import deploy
    _, tiny, _ = harness.cell_files("share2.burst", root, debug=True)
    assert tiny["memory_config"]["int8_serving"] is True
    assert tiny["memory_config"]["initial_capacity"] == 4160
    ms = deploy.build_system(tiny, str(tmp_path / "work"))
    try:
        assert ms.config.int8_serving and ms.config.semantic_cache
        assert not ms.config.enable_hierarchy and ms.config.embed_dim == 64
    finally:
        ms.close()
    names = [x["name"] for x in harness.metrics_of(cell, "per_layer", root)]
    assert names == ["sched.batch_p50.lat"]
    assert callable(harness.reader("sched.batch_p50.lat", root))
    e2e = [x["name"] for x in harness.metrics_of(cell, "end_to_end", root)]
    assert e2e == ["search_p50_ms", "search_p95_ms", "setup_s"]
    # the cell on four chips resolves to its own files as well
    pod, pcfg, pmix = harness.cell_files("later.pod", root)
    assert pod["chips"] == 4 == harness.mesh_chips(pcfg)
    assert pmix["name"] == "serve-closed-64" and pcfg["rows"] == 20000000
    assert files.load_module(pcfg["demand"], root).need(pcfg, 64) == \
        files.load_module("benchmark/demands/exact_scan.py", root).need(
            dict(pcfg, rows=5000000), 64)
    assert [x["name"] for x in harness.metrics_of(pod, "per_layer", root)] == [
        "dispatch.readback_p50_ms.later", "sched.lone_dispatch_pct.later",
        "device.compiles.later"]
    later.nothing_was_edited()


def test_a_later_pr_s_manifest_passes_every_manifest_wide_assertion(later):
    contracts.manifest_wide(later.root)
    later.nothing_was_edited()


def test_a_later_pr_s_four_chip_cell_passes_the_per_cell_contracts(later):
    contracts.cell_line("later.pod", later.root)
    contracts.traced_line("later.pod", later.root)
    later.nothing_was_edited()


def test_a_later_pr_s_four_chip_cell_reports_the_span_metrics_it_has(later):
    mine = contracts.traced_debug_run_reports_span_metrics("later.pod",
                                                           later.root)
    assert mine == ["dispatch.readback_p50_ms.later", "sched.lone_dispatch_pct.later"]
    later.nothing_was_edited()


def test_a_later_pr_s_one_chip_int8_cell_lands_after_the_last_entries(later):
    root = later.root
    m = harness.manifest(root)
    added = [e["name"] for e in m["per_layer"][len(M["per_layer"]):]]
    at = added.index("kernel.serve_roofline.later")
    assert added[at:at + 2] == ["kernel.serve_roofline.later",
                                "sched.batch_requests_mean.later"]
    assert m["per_layer"][:len(M["per_layer"])] == M["per_layer"]
    cell, cfg, mix = harness.cell_files("later.q8", root)
    assert cell["chips"] == 1 == harness.mesh_chips(cfg)
    assert mix["loop"] == "closed" and cfg["memory_config"]["int8_serving"]
    assert files.load_module(cfg["reference"], root).MARK == "two-stage"
    need = files.load_module(cfg["demand"], root).need(cfg, 64)
    assert need["ops_peak"] == "int8_ops_per_s"
    assert need["bytes"] < files.load_module(
        "benchmark/demands/exact_scan.py", root).need(cfg, 64)["bytes"]
    assert [x["name"] for x in harness.metrics_of(cell, "end_to_end", root)] == [
        "search_qps", "setup_s"]
    assert [x["name"] for x in harness.metrics_of(cell, "per_layer", root)] == [
        "kernel.serve_roofline.later", "sched.batch_requests_mean.later"]
    # the accepted cells report what they reported: no entry of theirs moved
    for w in M["workloads"]:
        for kind in ("end_to_end", "per_layer"):
            assert [x["name"] for x in harness.metrics_of(w, kind, root)] \
                == [x["name"] for x in harness.metrics_of(w, kind, ROOT)]
    later.nothing_was_edited()


def test_a_later_pr_s_one_chip_int8_cell_passes_the_per_cell_contracts(later):
    contracts.cell_line("later.q8", later.root)
    res = contracts.debug_run("later.q8", 34, later.root, traced=True)
    assert res["correct"] is True
    # the counter reads on any backend; the roofline share needs a device
    # plane and is left out here, never reported as 0
    assert set(res["metrics"]) == {"sched.batch_requests_mean.later"}
    assert res["metrics"]["sched.batch_requests_mean.later"]["value"] > 1.0
    later.nothing_was_edited()


# ------------------------------------------ a configuration names its graph

def test_a_later_pr_s_graph_cell_installs_its_edges_and_boosts_neighbours(later):
    import faults
    from benchmark import corpus
    root = later.root
    cell, cfg, mix = harness.cell_files("later.graph", root, debug=True)
    assert cfg["graph"]["nearest"] == 3 and mix["boost_share"] == 1.0
    seen = {}

    def look(ms):
        seen.update(edges=len(ms.index.edge_slots),
                    held=ms.config.max_edges)
    contracts.cell_line("later.graph", root)
    res = contracts.debug_run("later.graph", 35, root, traced=True, sabotage=look)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["compared"]["state_errors"] == {"value": 0.0, "limit": 0.0}
    # the chain alone is rows - tenants edges; the nearest facts add theirs
    assert cfg["rows"] - cfg["tenants"] < seen["edges"] <= seen["held"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["dispatch.boost_rows_per_req.graph"] == 5.0
    assert m["sched.overlap_pct.graph"] == 0.0        # what ROADMAP A4 is judged on
    assert m["device.compiles.graph"] == 0.0
    # the neighbour boosts were compared: without its edges the program
    # boosts the served rows alone, and only the state says so
    bad = contracts.debug_run("later.graph", 35, root,
                              sabotage=faults.edges_dropped)
    assert bad["correct"] is False
    assert bad["compared"]["state_errors"]["value"] > 0
    assert all(v["value"] <= v["limit"] for n, v in bad["compared"].items()
               if n != "state_errors")
    # and the graph is data: the same edges from the same rows, every time
    rows = harness.tenant_rows(cfg, 35, corpus.tenant_starts(
        cfg["rows"], cfg["tenants"]), 0, 3)
    assert corpus.tenant_edges(rows, cfg["graph"]) == \
        corpus.tenant_edges(rows, cfg["graph"])
    later.nothing_was_edited()


NAMED = {f"scheduler_entry:{name}": functools.partial(contracts.scheduler_entry, name)
         for name in contracts.SCHEDULER_ENTRIES}
NAMED.update(throughput_cells=contracts.throughput_cells,
             pod_metrics=contracts.pod_metrics)


@pytest.mark.parametrize("held", list(NAMED.values()), ids=list(NAMED))
def test_what_single_prs_named_holds_here_and_in_a_later_checkout(later, held):
    held(ROOT)
    held(later.root)
    later.nothing_was_edited()


def test_a_later_pr_s_pod_cell_keeps_its_thirteen_and_its_five_span_metrics(later):
    mine = contracts.traced_debug_run_reports_span_metrics("pod.serve",
                                                           later.root)
    assert set(mine) >= contracts.POD_SPAN_FIVE
    later.nothing_was_edited()


# ----------------------------------------- a configuration names its layout

def test_sharded_cell_fills_its_arena_over_four_devices_and_is_correct(later):
    seen = {}

    def look(ms):
        emb = ms.index.state.emb
        seen.update(devices=len(emb.sharding.device_set),
                    shards={s.data.shape for s in emb.addressable_shards},
                    rows=emb.shape[0], mesh=ms.mesh,
                    capacity=ms.config.initial_capacity)
    res = contracts.debug_run("later.pod", 31, later.root, sabotage=look)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert seen["devices"] == 4 and seen["mesh"].shape == {"data": 4}
    assert seen["shards"] == {(seen["rows"] // 4, 64)}
    assert seen["capacity"] == 4160          # as the file states it


def test_sharded_cell_with_a_wrong_tenant_mask_is_not_correct(later):
    res = contracts.debug_run("later.pod", 32, later.root,
                              sabotage=contracts.wrong_tenant_mask)
    assert res["correct"] is False
    v = res["compared"]["foreign_ids"]
    assert v["value"] > v["limit"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_sharded_cell_s_int8_control_is_not_correct(later, seed):
    res = contracts.debug_run("later.pod", seed, later.root, control="int8",
                              seconds=0.4)
    assert res["correct"] is False
    gap = res["compared"]["score_gap"]
    assert gap["value"] > 3 * gap["limit"]


def test_mesh_that_is_not_the_cell_s_chips_is_refused_with_a_sentence(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"))
    m = harness.manifest(ROOT)
    _add_pod_cell(root, m)
    m["workloads"][-1]["chips"] = 1
    _write(root, "BENCHMARK.json", json.dumps(m))
    with pytest.raises(ValueError, match=r"'later.pod' asks for 1 chip\(s\), "
                       r"but its configuration 'later-mesh4' is laid out over 4"):
        harness.run_cell("later.pod", 1, 0.4, False, root=root, debug=True)
    with pytest.raises(ValueError, match="laid out over 4"):
        harness.cell_files("later.pod", root)


def test_debug_mesh_larger_than_the_devices_is_refused_with_a_sentence():
    with pytest.raises(harness.NoAccelerator, match="needs 64 device"):
        harness.require_chips(64, debug=True)
    harness.require_chips(8, debug=True)         # conftest's eight


def test_configuration_without_mesh_builds_today_s_system(tmp_path, monkeypatch):
    from benchmark import deploy
    _, cfg, _ = harness.cell_files("fill.serve", ROOT, debug=True)
    assert "mesh" not in cfg and deploy.mesh_of(cfg) is None
    calls = []
    real = deploy.MemorySystem

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)
    monkeypatch.setattr(deploy, "MemorySystem", spy)
    ms = deploy.build_system(cfg, str(tmp_path / "work"))
    try:
        assert set(calls[0]) == {"config", "verbose"}     # no mesh argument
        assert ms.mesh is None and ms.index.mesh is None
        assert ms.config.initial_capacity == 4160
        assert len(ms.index.state.emb.sharding.device_set) == 1
    finally:
        ms.close()


def test_peaks_table_known_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "share.serve", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        cwd=ROOT, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
    assert "TPU" in p.stderr
