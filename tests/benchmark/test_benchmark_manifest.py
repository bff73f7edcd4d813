"""BENCHMARK.json against the contract it is checked by, and the harness's
promise that a later PR adds a cell with files and entries alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import files, harness, peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = harness.manifest(ROOT)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(_line(w) for w in M["command"])
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("c", M["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
    assert any(c["file"].startswith(p + "/") for p in M["paths"])
    cfg = harness.load_json(os.path.join(ROOT, c["file"]))
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    assert all(NAME.match(k) and k in cfg for k in c["reduced"])
    assert any(w["config"] == c["name"] for w in M["workloads"])
    assert cfg["guarantees"] and cfg["assumed"]
    # its reference and its demand are files of the benchmark, found by path
    for key in ("reference", "demand"):
        assert any(cfg[key].startswith(p + "/") for p in M["paths"])
    ref = files.load_module(cfg["reference"], ROOT)
    ref.Comparison(cfg["limits"])                # every compared number has one
    need = files.load_module(cfg["demand"], ROOT).need(cfg, 1)
    assert need["bytes"] > 0 and need["ops"] > 0
    # what the program is configured with is the program's own field names
    from lazzaro_tpu.config import MemoryConfig
    mc = MemoryConfig(**cfg["memory_config"])
    assert (mc.embed_dim, mc.dtype) == (cfg["dim"], cfg["dtype"])
    assert mc.initial_capacity >= cfg["rows"]


def test_config_files_and_names_are_distinct():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in M[key]]
        assert len(set(names)) == len(names)
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    metric_names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert w["chips"] in (1, 4) and _line(w["why"])
    cell, cfg, mix = harness.cell_files(w["name"], ROOT)
    assert mix["loop"] in ("open", "closed", "conversations")
    assert mix["name"] == w["traffic"] and cfg["name"] == w["config"]
    e2e = [m["name"] for m in harness.metrics_of(cell, "end_to_end", ROOT)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(cell, "per_layer", ROOT)
    for kind in ("end_to_end", "per_layer"):
        for m in harness.metrics_of(cell, kind, ROOT):
            assert callable(harness.reader(m["name"], ROOT))


def test_cells_on_four_chips_within_quota():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("m", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                     "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in M["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


def test_setup_s_is_reported_by_every_cell():
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                     "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert _line(m["layer"])
    moved = [e for e in M["end_to_end"] if e["name"] == m["moves"]]
    assert len(moved) == 1
    cells = [w["name"] for w in M["workloads"]]
    reporting = set(moved[0].get("workloads", cells))
    assert set(m.get("workloads", reporting)) <= reporting
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_a_later_pr_adds_a_cell_with_files_and_entries_alone(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"))
    before = {}
    for d, _, names in os.walk(root):
        for f in names:
            p = os.path.join(d, f)
            if not p.endswith("BENCHMARK.json"):
                before[p] = open(p, "rb").read()
    cfg = harness.load_json(os.path.join(root, "benchmark/configs/share131k.json"))
    # another size, a serving mode of the program switched on by its field
    # name, and a reference and a demand of the new configuration's own
    cfg.update(name="share262k", rows=262144,
               reference="benchmark/reference_recall.py",
               demand="benchmark/demands/int8_scan.py")
    cfg["memory_config"].update(initial_capacity=262208, int8_serving=True)
    cfg["debug"]["memory_config"].update(semantic_cache=True)
    json.dump(cfg, open(os.path.join(root, "benchmark/configs/share262k.json"), "w"))
    with open(os.path.join(root, "benchmark/reference_recall.py"), "w") as f:
        f.write("from benchmark.reference import *  # noqa: F401,F403\n"
                "MARK = 'recall'\n")
    with open(os.path.join(root, "benchmark/demands/int8_scan.py"), "w") as f:
        f.write("def need(cfg, batch):\n    return {'bytes': cfg['rows'] * "
                "cfg['dim'], 'ops': 1.0, 'ops_peak': 'int8_ops_per_s'}\n")
    mix = harness.load_json(os.path.join(root, "benchmark/mixes/serve-open-zipf.json"))
    mix.update(name="serve-open-burst", rate_rps=50)
    json.dump(mix, open(os.path.join(root, "benchmark/mixes/serve-open-burst.json"), "w"))
    with open(os.path.join(root, "benchmark/metrics/sched.batch_p50.lat.py"), "w") as f:
        f.write("from benchmark.readers import timer_p50\n\n\n"
                "def read(run):\n    return timer_p50(run, 'serve.batch_requests')\n")
    m = harness.manifest(root)
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
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
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
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} had to be edited"


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
