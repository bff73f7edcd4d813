#!/usr/bin/env python3
"""Stand a meshed deployment up as a USER of the library does, with no help
from the benchmark: ``MemorySystem(config=MemoryConfig(**memory_config),
mesh=make_mesh(...))`` at the configuration's full ``initial_capacity``, then
one ingest, one retrieval through the scheduler and one boosting dispatch.

    chiprun --chips 4 -- python3 scripts/check_pod_construct.py \\
        --config benchmark/configs/lme20m-mesh4.json

Prints every chip's ``bytes_in_use`` / ``peak_bytes_in_use`` after each step
(also to ``chiprun_out/pod_construct.json``): an arena created in its shards
leaves each chip its 1/n and no chip the whole; a boosting dispatch that
donates the state leaves the peak where it was. Exit 2 without the TPU chips
the configuration's mesh asks for; ``--cpu-debug`` takes the file's ``debug``
sizes on whatever devices jax has (exit 3, nothing it prints is a device
number)."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--cpu-debug", action="store_true")
    args = ap.parse_args(argv)
    sys.path[0:1] = [ROOT]
    with open(os.path.join(ROOT, args.config)) as f:
        cfg = json.load(f)
    mc, layout = dict(cfg["memory_config"]), dict(cfg["mesh"])
    if args.cpu_debug:
        mc.update(cfg["debug"]["memory_config"])
        layout.update(cfg["debug"].get("mesh", {}))
    chips = math.prod(layout["shape"])

    import jax
    import numpy as np

    devs = jax.devices()
    if len(devs) < chips or not (args.cpu_debug or devs[0].platform == "tpu"):
        print(f"needs {chips} TPU chip(s); jax sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 2

    from lazzaro_tpu import MemorySystem
    from lazzaro_tpu.config import MemoryConfig
    from lazzaro_tpu.parallel.mesh import make_mesh
    from lazzaro_tpu.serve.scheduler import RetrievalRequest

    mesh = make_mesh(tuple(layout["axes"]), tuple(layout["shape"]),
                     devices=devs[:chips])
    steps = []

    def note(step: str, t0: float) -> None:
        stats = [d.memory_stats() or {} for d in devs[:chips]]
        steps.append({
            "step": step, "seconds": time.perf_counter() - t0,
            "bytes_in_use": [int(s.get("bytes_in_use", 0)) for s in stats],
            "peak_bytes_in_use": [int(s.get("peak_bytes_in_use", 0))
                                  for s in stats]})
        print(json.dumps(steps[-1]), flush=True)

    with tempfile.TemporaryDirectory(prefix="lzpod-") as work:
        t0 = time.perf_counter()
        ms = MemorySystem(config=MemoryConfig(**mc, db_dir=work),
                          verbose=False, mesh=mesh)
        jax.block_until_ready(ms.index.state.emb)
        note("construct", t0)
        emb = ms.index.state.emb
        shards = sorted({s.data.shape for s in emb.addressable_shards})
        dim = mc["embed_dim"]
        rng = np.random.default_rng(29)
        rows = rng.standard_normal((64, dim)).astype(np.float32)
        t0 = time.perf_counter()
        ms.index.add([f"u:f{j}" for j in range(64)], rows, [0.6] * 64,
                     [0.0] * 64, ["semantic"] * 64, ["default"] * 64, "u")
        note("ingest of 64 rows", t0)
        t0 = time.perf_counter()
        sched = ms._ensure_scheduler()
        hit = sched.submit(RetrievalRequest(query=rows[7], tenant="u",
                                            k=5)).result(timeout=600)
        note("retrieval through the scheduler", t0)
        copies = []
        for turn in range(2):       # the first compiles; both must donate
            t0 = time.perf_counter()
            boosted = ms.index.search_fused_requests(
                [RetrievalRequest(query=rows[9], tenant="u", k=5, boost=True)],
                cap_take=3, max_nbr=8, super_gate=0.4, acc_boost=0.05,
                nbr_boost=0.02)
            copies.append(int(ms.telemetry.counter_total(
                "serve.copy_dispatches")))
            note(f"boosting dispatch {turn + 1}", t0)
        out = {
            "config": cfg["name"], "rows": int(emb.shape[0]),
            "shard_shapes": [list(s) for s in shards],
            "devices": {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)},
            "retrieved": hit.ids[:1], "boosted": boosted[0].ids[:1],
            "copy_dispatches": copies, "steps": steps,
            "ok": (hit.ids[:1] == ["u:f7"] and boosted[0].ids[:1] == ["u:f9"]
                   and copies[-1] == 0
                   and shards == [(emb.shape[0] // chips, dim)])}
        ms.close()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "pod_construct.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "steps"}))
    if not out["ok"]:
        return 1
    return 3 if args.cpu_debug else 0


if __name__ == "__main__":
    sys.exit(main())
