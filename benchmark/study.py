#!/usr/bin/env python3
"""Study tool: many runs of one cell in ONE process, for what the benchmark's
own runs do not do — the rate sweep, the tail study, the readings a limit of
``correct`` is set from, and the control.

    python3 benchmark/study.py --workload share.serve --seeds 11,12 \\
        --seconds 10 --set rate_rps=400,800 --out chiprun_out/study.jsonl
    python3 benchmark/study.py --workload fill.serve --seeds 1,2,3 \\
        --seconds 5 --control int8
    python3 benchmark/study.py --workload share.chat --seeds 4,5,6 \\
        --seconds 10 --sabotage tests/benchmark/faults.py:boosts_off

Each run appends one JSON line (the result object with the window's
distributions under ``detail``) to ``--out`` and prints a one-line summary.
Set-up is paid per run, compilation once per process. Needs the chips the
cell asks for, like ``run.py``; its numbers are study readings, never a
cell's result.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def _sweep_row(d: dict, res: dict) -> dict:
    """What the rate sweep reads of one run: whether the rate was sustained
    (the generator on time, every request completed, the last quarter's p95
    no worse than the first's), and how the scheduler served it."""
    lat, due = d.get("latency_ms_all") or [], d.get("due_s_all") or []
    if not lat:
        return {}
    order = sorted(range(len(lat)), key=due.__getitem__)
    q = len(order) // 4
    p95 = [sorted(lat[i] for i in part)[int(0.95 * q)]
           for part in (order[:q], order[-q:])] if q >= 20 else [None, None]
    c = d.get("counters", {})
    batches = c.get("serve.batches") or 0
    return {
        "p95_first_quarter": p95[0], "p95_last_quarter": p95[1],
        "completed_share": (res["attempted"] - res["failed"]) / res["attempted"],
        "overlap_pct": 100.0 * c.get("serve.overlapped_batches", 0) / batches
        if batches else None,
        "batch_mean": c.get("serve.requests", 0) / batches if batches else None,
        "copies": c.get("serve.copy_dispatches"),
        "boost_rows_per_req": c.get("device.boost_rows", 0)
        / c["serve.requests"] if c.get("serve.requests") else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="mix parameter sweep: name=v1,v2,...")
    ap.add_argument("--control", default=None, choices=(None, "int8"))
    ap.add_argument("--sabotage", default=None, metavar="FILE.py:FUNCTION",
                    help="break the timed path before the window: "
                         "FUNCTION(ms) of FILE, a path inside the checkout")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu-debug", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [root]
    from benchmark import files, harness

    sabotage = None
    if args.sabotage:
        path, _, name = args.sabotage.partition(":")
        sabotage = getattr(files.load_module(path, root), name)
    sweeps = []
    for item in args.set:
        name, _, values = item.partition("=")
        sweeps.append([(name, _value(v)) for v in values.split(",")])
    combos = [dict(c) for c in itertools.product(*sweeps)] or [{}]
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for override in combos:
        for seed in seeds:
            t0 = time.perf_counter()
            res = harness.run_cell(
                args.workload, seed, args.seconds, bool(args.trace),
                debug=args.cpu_debug, t_start=t0, control=args.control,
                sabotage=sabotage, mix_override=override, detail=True)
            res.update(workload=args.workload, seed=seed, override=override,
                       control=args.control, sabotage=args.sabotage,
                       seconds=args.seconds, wall_s=time.perf_counter() - t0)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(res) + "\n")
            d = res.get("detail") or {}
            lat = d.get("latency_ms", {})
            print(json.dumps({
                "set": override, "seed": seed, "correct": res["correct"],
                "control": args.control, "sabotage": args.sabotage,
                "sweep": _sweep_row(d, res),
                "metrics": {k: round(v["value"], 4)
                            for k, v in res["metrics"].items()},
                "lat_p50_p95_p99": [round(lat.get(k, 0), 3)
                                    for k in ("p50", "p95", "p99")],
                "late_p95": round(d.get("late_ms", {}).get("p95", 0), 3),
                "batch_p50": d.get("batch_requests", {}).get("p50"),
                "dispatch_p50": round(d.get("dispatch_ms", {}).get("p50", 0), 3),
                "dispatch_device_ms": d.get("dispatch_device_ms"),
                "compared": {k: v["value"] for k, v in res["compared"].items()},
                "failed": res["failed"], "attempted": res["attempted"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
