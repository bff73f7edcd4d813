#!/usr/bin/env python3
"""Run one benchmark cell of a checkout through that checkout's OWN
``benchmark/run.py`` and print, beside its result line, what the scheduler's
hold did in the timed window (ISSUE 32):

    python3 scripts/hold_counters.py <checkout> --workload fill.serve \\
        --seed 3200011 --seconds 40 --trace 0

The three per-layer metrics ISSUE 32 names ARE in the manifest since PR 34
(``sched.batch_requests_mean.qps``, ``sched.hold_pct.qps`` / ``.lat``, beside
``sched.hold_p50_ms.qps``), and ``tests/benchmark/test_hold_metric.py`` holds
:func:`metrics` below and their readers to the same numbers. Before that, the
numbers PERF.md quotes for them were read this way, and a checkout older than
PR 34 — or one whose manifest lacks them — still is: one ``HOLD_COUNTERS``
line on stderr with the program's counters as they stand when the window
ends (``serve.requests``, ``serve.batches``, ``serve.held_batches``,
``serve.hold_us``, ``serve.lone_batches``, ``serve.overlapped_batches``),
the metrics computed from them as the issue defines them (:func:`metrics`:
requests a dispatch for ``sched.batch_requests_mean.qps``, held / served
batches for ``sched.hold_pct.qps`` and ``.lat``), the ``sched.hold_ms`` timer, the scheduler's own estimate
of a dispatch and the sizes of its last batches. A checkout without the
hold (the parent) has no ``serve.held_batches`` entry and reads 0.0, its
marker ``serve.queue_wait_us`` being there. stdout is the checkout's own:
its last line is the benchmark's result line, untouched.

It hooks the one place where the harness reads the program's counters
after the window (``benchmark.deploy.counters``); it edits nothing."""

from __future__ import annotations

import collections
import json
import os
import runpy
import sys
from typing import Dict, Optional

COUNTERS = ("serve.requests", "serve.batches", "serve.held_batches",
            "serve.hold_us", "serve.lone_batches", "serve.overlapped_batches",
            "serve.queue_wait_us")


def metrics(totals: Dict[str, float]) -> Dict[str, Optional[float]]:
    """ISSUE 32's three readings from the counters' totals (a counter that
    was never bumped is absent or 0: a bump of 0 leaves no entry):
    requests a dispatch, held / served batches in per cent, and the mean
    hold — None where the program served nothing or does not mark its
    batches (``serve.queue_wait_us``), 0.0 for a program that does and
    never held one."""
    n = {name: totals.get(name, 0) for name in COUNTERS}
    batches = n["serve.batches"]
    marked = batches and n["serve.queue_wait_us"]
    return {
        "sched.batch_requests_mean": (n["serve.requests"] / batches
                                      if batches else None),
        "sched.hold_pct": (100.0 * n["serve.held_batches"] / batches
                           if marked else None),
        "sched.hold_ms_per_held_batch": (
            n["serve.hold_us"] / 1e3 / n["serve.held_batches"]
            if n["serve.held_batches"] else None),
    }


def report(ms) -> dict:
    tel = ms.telemetry
    out = {n: tel.counter_total(n) for n in COUNTERS}
    out.update(metrics(out))
    out["sched.hold_ms"] = tel.snapshot()["timers"].get("sched.hold_ms")
    sched = getattr(ms, "query_scheduler", None)
    if sched is not None:
        out["dispatch_estimate_ms"] = 1e3 * getattr(sched, "_dispatch_s", 0.0)
        out["returners"] = len(getattr(sched, "_returners", ()))
        out["last_batch_sizes"] = sorted(
            collections.Counter(sched.batch_sizes).items())
    return out


def main(argv) -> None:
    root = os.path.abspath(argv[1])
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + argv[2:]
    sys.path.insert(0, root)
    os.chdir(root)
    from benchmark import deploy

    read = deploy.counters

    def counters(ms):
        sys.stderr.write("HOLD_COUNTERS " + json.dumps(report(ms)) + "\n")
        return read(ms)

    deploy.counters = counters
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main(sys.argv)
