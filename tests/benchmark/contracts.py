"""What ``tests/benchmark`` holds a manifest, its cells and its metrics to, as
functions of a checkout's root. The test files call them on the repository;
``test_benchmark_manifest.py`` calls every one again on a temporary checkout
to which three cells were added with new files and appended entries alone — a
four-chip cell of a sharded configuration and a one-chip throughput cell of an
int8 configuration among them — so "files and entries alone" is held to every
test a later PR's cell will meet. What a single PR named of the manifest is
here too (``named_entries``), with "at least" where the contract is "at
least": an assertion written against the repository alone, with ``==`` on a
list or a place in it, is what stopped PRs 30 and 32 from appending. The
benchmark's code is the repository's in both cases; only ``BENCHMARK.json``
and the files it names are found under ``root``."""

import math
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import files, harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]

# PR 25's fourteen span metrics, each with the one cell it was accepted for
FOURTEEN = {
    "sched.worker_busy_pct": "share.serve",
    "sched.queue_wait_mean_ms": "share.serve",
    "sched.demux_p50_ms": "share.serve",
    "index.pack_p50_ms.lat": "share.serve",
    "index.stage_p50_ms.lat": "share.serve",
    "index.decode_p50_ms.lat": "share.serve",
    "dispatch.launch_p50_ms.lat": "share.serve",
    "dispatch.readback_p50_ms.lat": "share.serve",
    "sched.lone_dispatch_pct": "fill.serve",
    "api.end_conversation_p50_ms": "share.ingest",
    "api.switch_user_p50_ms": "share.ingest",
    "store.ms_per_conv": "share.ingest",
    "journal.ms_per_conv": "share.ingest",
    "store.file_ops_per_conv": "share.ingest",
}

# The driver's contract for BENCHMARK.json — the instructions every PR of this
# repository is built to, which give ``top_level`` its other ceilings (64 KiB,
# 16 paths, 32 words of command) — lets ``per_layer`` hold 1 to 128 entries
# and refuses a longer file before any run. Accepted cells hold 115; a
# suffixed entry per cell and quantity (24 cells x ~15) cannot fit.
PER_LAYER_MAX = 128

# So a cell whose run reads the same quantity JOINS the accepted entry's list
# (as cells join an end-to-end metric's) and brings a suffixed entry only for
# what no entry reads. The cells that joined, in the order they came; every
# other accepted entry lists the one cell it was accepted for. PR 43:
# ``share.mixed``.
JOINED = {name: ["share.mixed"] for name in (
    "loadgen.late_p95_ms", "sched.queue_wait_p50_ms", "sched.worker_busy_pct",
    "api.conversation_p50_ms", "api.end_conversation_p50_ms",
    "api.switch_user_p50_ms", "store.ms_per_conv", "store.file_ops_per_conv",
    "journal.ms_per_conv", "kernel.ingest_dev_ms", "dispatch.p50_ms.ing",
    "device.idle_pct.ing", "device.compiles.ing")}


def line(s) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


# ------------------------------------------------------------- the manifest

def top_level(root):
    m = harness.manifest(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["command"]) <= 32 and all(line(w) for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(root, p))


def config_entry(c, root):
    m = harness.manifest(root)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
    assert any(c["file"].startswith(p + "/") for p in m["paths"])
    cfg = harness.load_json(os.path.join(root, c["file"]))
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    assert all(NAME.match(k) and k in cfg for k in c["reduced"])
    assert any(w["config"] == c["name"] for w in m["workloads"])
    assert cfg["guarantees"] and cfg["assumed"]
    # its reference and its demand are files of the benchmark, found by path
    for key in ("reference", "demand"):
        assert any(cfg[key].startswith(p + "/") for p in m["paths"])
    ref = files.load_module(cfg["reference"], root)
    ref.Comparison(cfg["limits"])                # every compared number has one
    need = files.load_module(cfg["demand"], root).need(cfg, 1)
    assert need["bytes"] > 0 and need["ops"] > 0
    # what the program is configured with is the program's own field names
    from lazzaro_tpu.config import MemoryConfig
    mc = MemoryConfig(**cfg["memory_config"])
    assert (mc.embed_dim, mc.dtype) == (cfg["dim"], cfg["dtype"])
    assert mc.initial_capacity >= cfg["rows"]
    # a layout is one named axis list and one shape, here and in the tiny
    # geometry; every cell of the configuration asks for the chips it spans
    for layout in (cfg, harness._with_debug(cfg, True)):
        if "mesh" in layout:
            mesh = layout["mesh"]
            assert set(mesh) == {"axes", "shape"}
            assert len(mesh["axes"]) == len(mesh["shape"]) >= 1
            assert all(isinstance(n, int) and n >= 1 for n in mesh["shape"])
    assert harness.mesh_chips(cfg) in (1, 4)
    assert all(w["chips"] == harness.mesh_chips(cfg)
               for w in m["workloads"] if w["config"] == c["name"])


def names_distinct(root):
    m = harness.manifest(root)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[key]]
        assert len(set(names)) == len(names)
    assert len({c["file"] for c in m["configs"]}) == len(m["configs"])
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def cell_resolves(w, root):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert w["chips"] in (1, 4) and line(w["why"])
    cell, cfg, mix = harness.cell_files(w["name"], root)
    assert mix["loop"] in harness.LOOPS
    assert mix["name"] == w["traffic"] and cfg["name"] == w["config"]
    e2e = [m["name"] for m in harness.metrics_of(cell, "end_to_end", root)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(cell, "per_layer", root)
    for kind in ("end_to_end", "per_layer"):
        for m in harness.metrics_of(cell, kind, root):
            assert callable(harness.reader(m["name"], root))


def four_chip_quota(root):
    m = harness.manifest(root)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)


def end_to_end_metric(e, root):
    m = harness.manifest(root)
    assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                     "source"}
    assert NAME.match(e["name"]) and UNIT.match(e["unit"])
    assert e["better"] in ("lower", "higher")
    assert e["source"] in ("host_clock", "device_trace")
    assert 0.01 <= e["bound"] <= 0.25
    cells = {w["name"] for w in m["workloads"]}
    assert set(e.get("workloads", cells)) <= cells


def setup_s(root):
    m = harness.manifest(root)
    setup = [e for e in m["end_to_end"] if e["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def per_layer_metric(e, root):
    m = harness.manifest(root)
    assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                     "layer", "moves"}
    assert NAME.match(e["name"]) and UNIT.match(e["unit"])
    assert e["better"] in ("lower", "higher") and e["source"] in SOURCES
    assert line(e["layer"])
    moved = [x for x in m["end_to_end"] if x["name"] == e["moves"]]
    assert len(moved) == 1
    cells = [w["name"] for w in m["workloads"]]
    reporting = set(moved[0].get("workloads", cells))
    assert set(e.get("workloads", reporting)) <= reporting
    if e["name"].endswith("_roofline") or "mfu" in e["name"]:
        assert e["unit"] == "%"


# --------------------------- what PRs 29, 30 and 34 named: "at least", by name

# the scheduler's entries, each as the PR that asked for it named it; where
# in ``per_layer`` an entry stands is nobody's contract
SCHEDULER_ENTRIES = {
    name: {"name": name, "unit": unit, "better": better, "source": source,
           "layer": "scheduler", "moves": moves, "workloads": [cell]}
    for name, unit, better, source, moves, cell in [
        ("sched.overlap_pct.qps", "%", "higher", "program_counter",
         "search_qps", "fill.serve"),
        ("sched.overlap_pct.pod", "%", "higher", "program_counter",
         "search_qps", "pod.serve"),
        ("sched.batch_requests_mean.qps", "count", "higher",
         "program_counter", "search_qps", "fill.serve"),
        ("sched.hold_pct.qps", "%", "higher", "program_counter",
         "search_qps", "fill.serve"),
        ("sched.hold_pct.lat", "%", "lower", "program_counter",
         "search_p50_ms", "share.serve"),
        ("sched.hold_p50_ms.qps", "ms", "lower", "program_span",
         "search_qps", "fill.serve"),
    ]}

# PR 29's thirteen metrics of ``pod.serve`` and the five of them that read
# the program's spans and counters; later PRs append others beside them
POD_THIRTEEN = (
    "sched.occupancy_pct.pod", "sched.lone_dispatch_pct.pod",
    "dispatch.p50_ms.pod", "dispatch.launch_p50_ms.pod",
    "dispatch.readback_p50_ms.pod", "index.stage_p50_ms.pod",
    "kernel.serve_dev_ms.pod", "kernel.serve_roofline.pod",
    "device.idle_pct.pod", "device.compiles.pod", "kernel.merge_dev_ms.pod",
    "device.skew_pct.pod", "dispatch.copies.pod")
POD_SPAN_FIVE = {
    "sched.lone_dispatch_pct.pod", "dispatch.launch_p50_ms.pod",
    "dispatch.readback_p50_ms.pod", "index.stage_p50_ms.pod",
    "dispatch.copies.pod"}


def entry(root, kind, name):
    found = [e for e in harness.manifest(root)[kind] if e["name"] == name]
    assert len(found) == 1, f"{kind} holds {len(found)} entries {name!r}"
    return found[0]


def scheduler_entry(name, root):
    """The entry is what its PR named, is IN ``per_layer`` (anywhere), reads
    the program's counters or spans and has its reader file."""
    e = entry(root, "per_layer", name)
    assert e == SCHEDULER_ENTRIES[name]
    per_layer_metric(e, root)
    assert e in span_metrics(root)
    assert callable(harness.reader(name, root))
    return e


def throughput_cells(root):
    """``search_qps`` is reported by AT LEAST ``fill.serve`` and
    ``pod.serve`` (a later throughput cell appends itself), and by
    ``pod.serve`` beside ``setup_s`` alone."""
    cells = entry(root, "end_to_end", "search_qps")["workloads"]
    assert {"fill.serve", "pod.serve"} <= set(cells)
    assert len(set(cells)) == len(cells)
    pod = harness.cell_files("pod.serve", root)[0]
    assert [m["name"] for m in harness.metrics_of(pod, "end_to_end", root)] \
        == ["search_qps", "setup_s"]


def pod_metrics(root):
    """``pod.serve``'s own per-layer metrics: AT LEAST PR 29's thirteen, by
    name; every one ends in ``.pod``, moves ``search_qps``, has a reader and
    names a layer another cell's metrics name too."""
    m = harness.manifest(root)
    mine = [e for e in m["per_layer"] if e.get("workloads") == ["pod.serve"]]
    assert set(POD_THIRTEEN) <= {e["name"] for e in mine}
    assert {e["moves"] for e in mine} == {"search_qps"}
    assert all(e["name"].endswith(".pod") for e in mine)
    layers = {e["layer"] for e in m["per_layer"]
              if e.get("workloads") != ["pod.serve"]}
    assert {e["layer"] for e in mine} <= layers          # no new layer name
    for e in mine:
        per_layer_metric(e, root)
        assert callable(harness.reader(e["name"], root))
    return mine


def named_entries(root):
    """What single PRs named of the manifest, held for every checkout."""
    for name in SCHEDULER_ENTRIES:
        scheduler_entry(name, root)
    throughput_cells(root)
    pod_metrics(root)


def manifest_wide(root):
    """Every assertion above, on every entry of the root's manifest."""
    m = harness.manifest(root)
    top_level(root)
    names_distinct(root)
    four_chip_quota(root)
    setup_s(root)
    for c in m["configs"]:
        config_entry(c, root)
    for w in m["workloads"]:
        cell_resolves(w, root)
    for e in m["end_to_end"]:
        end_to_end_metric(e, root)
    for e in m["per_layer"]:
        per_layer_metric(e, root)
    fourteen_in_manifest(root)
    lists_as_accepted(root)
    named_entries(root)


# ------------------------------------------------------ a cell's debug runs

def debug_run(cell, seed, root=ROOT, **kw):
    return harness.run_cell(cell, seed, kw.pop("seconds", 0.6),
                            kw.pop("traced", False), root=root, debug=True,
                            **kw)


def cell_line(cell, root, seed=2**31 + 7):
    res = debug_run(cell, seed, root)
    assert list(res)[:5] == KEYS and list(res)[-1] == "compared"
    assert set(res) == set(KEYS) | {"compared"}
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    want = {m["name"] for m in harness.metrics_of(
        harness.cell_files(cell, root)[0], "end_to_end", root)}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    gap = res["compared"]["score_gap"]
    assert gap["value"] <= 1e-6 < gap["limit"]       # bf16 products are exact
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())


def traced_line(cell, root, seed=11):
    res = debug_run(cell, seed, root, traced=True)
    assert res["correct"] is True
    names = {m["name"] for m in harness.metrics_of(
        harness.cell_files(cell, root)[0], "per_layer", root)}
    assert set(res["metrics"]) <= names
    # counters and spans read on any backend; device-trace metrics need a
    # device plane and are left out here, never reported as 0
    assert any(n.startswith("device.compiles") for n in res["metrics"])
    assert not any(n.startswith(("kernel.", "device.idle")) for n in res["metrics"])
    assert [v["value"] for n, v in res["metrics"].items()
            if n.startswith("device.compiles")] == [0.0]


def wrong_tenant_mask(ms):
    """A timed path broken underneath: rows answer to other tenants."""
    import jax.numpy as jnp
    st = ms.index.state
    ms.index.state = st.replace(tenant_id=jnp.roll(st.tenant_id, 200))


# ------------------------------------------------------------- span metrics

def span_metrics(root):
    """The per-layer metrics whose reader reads the program's spans and
    counters through ``benchmark/span_metrics.py``."""
    out = []
    for m in harness.manifest(root)["per_layer"]:
        path = os.path.join(root, "benchmark", "metrics", m["name"] + ".py")
        if os.path.exists(path) and "span_metrics" in open(path).read():
            out.append(m)
    return out


def lists_as_accepted(root):
    """Every per-layer entry lists its own cell, then the cells ``JOINED``
    names for it, letter for letter; in the repository's own manifest nothing
    else (a later PR's checkout appends behind them)."""
    per_layer = harness.manifest(root)["per_layer"]
    own = os.path.samefile(root, ROOT)     # not a test's later checkout
    assert 1 <= len(per_layer) <= PER_LAYER_MAX or not own
    assert set(JOINED) <= {e["name"] for e in per_layer}
    for e in per_layer:
        joined, cells = JOINED.get(e["name"], []), e.get("workloads")
        if cells is None:                  # reported wherever ``moves`` is
            assert not joined
            continue
        assert cells[1:1 + len(joined)] == joined, e["name"]
        assert cells[0] not in joined
        if own:
            assert len(cells) == 1 + len(joined), e["name"]


def fourteen_in_manifest(root):
    """PR 25's fourteen stay, each with the cell it was accepted for; a later
    span metric with another cell's list is none of this test's business."""
    have = {m["name"]: m for m in span_metrics(root)}
    assert set(FOURTEEN) <= set(have)
    for name, cell in FOURTEEN.items():
        want = [cell] + JOINED.get(name, [])
        assert have[name]["workloads"][:len(want)] == want
    cells = {w["name"] for w in harness.manifest(root)["workloads"]}
    assert all(set(m["workloads"]) <= cells for m in have.values())


def traced_debug_run_reports_span_metrics(cell, root, seed=2**31 + 25):
    """The span metrics the cell HAS read something in its traced run."""
    res = debug_run(cell, seed, root, traced=True)
    assert res["correct"] is True
    mine = [m["name"] for m in span_metrics(root) if cell in m["workloads"]]
    for name in mine:
        value = res["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, name
    if "store.file_ops_per_conv" in mine:
        ops = res["metrics"]["store.file_ops_per_conv"]["value"]
        assert ops == int(ops) > 0        # the same work every conversation
    return mine
