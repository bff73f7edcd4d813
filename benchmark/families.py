"""Metric families: one reader per quantity, whatever cell reports it.

A cell's per-layer entries carry the cell's suffix (``sched.overlap_pct.chat``
beside ``sched.overlap_pct.qps``), because an entry lists the cells it was
accepted for and later PRs append entries and edit none. What such entries
read is the same, so a metric's file need not restate it:

    from benchmark.families import reader_for

    read = reader_for(__file__)

``reader_for`` takes the file's name (``<family>.<suffix>.py``), drops the
suffix and returns the family's reader. A family reads the run as
``readers.py`` and ``span_metrics.py`` prescribe: None for what the run does
not hold, never 0 for what it could not measure.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional

from benchmark import stats, tracing
from benchmark.readers import (compiles, device_ms_per_span, idle_pct,
                               serve_roofline_pct, timer_p50)
from benchmark.span_metrics import counter_ratio, has_counter, span_p50_ms

SCAN = "lz_select_scan"


def device_ms_outside(run, kernel: str) -> Optional[float]:
    """Device milliseconds a dispatch spends OUTSIDE the kernels named
    ``kernel*``: first device plane, the window's other operations by name,
    over the window's ``lz.serve.batch`` spans. None where the trace holds
    no such operation."""
    if run.trace is None or not run.trace["devices"]:
        return None
    window = tracing.window_of(run.trace)
    ops = next(iter(run.trace["devices"].values()))
    other = [e for e in tracing.clip(ops, window) if not e[0].startswith(kernel)]
    n = len(tracing.spans_named(run.trace, "lz.serve.batch"))
    if not other or not n:
        return None
    return tracing.total(tracing.union(other)) / n / 1e6


def counted(run, name: str, marker: str) -> Optional[float]:
    """The program's counter ``name``, whole; 0 where it was never bumped by
    a program that bumps ``marker`` wherever it could have been, None for a
    program that counts neither."""
    return float(run.counter(name)) if has_counter(run, marker) else None


FAMILIES: Dict[str, Callable] = {
    # load generator
    "loadgen.late_p95_ms": lambda run: (
        stats.percentile(run.late_ms, 95) if len(run.late_ms) else None),
    # scheduler
    "sched.queue_wait_p50_ms": lambda run: timer_p50(run, "serve.queue_wait_ms"),
    "sched.overlap_pct": lambda run: counter_ratio(
        run, "serve.overlapped_batches", "serve.batches", 100.0,
        marker="serve.queue_wait_us"),
    "sched.batch_requests_mean": lambda run: counter_ratio(
        run, "serve.requests", "serve.batches"),
    # index host path
    "index.puts_per_dispatch": lambda run: counter_ratio(
        run, "serve.h2d_puts", "serve.dispatches", 1.0),
    # dispatch
    "dispatch.p50_ms": lambda run: timer_p50(run, "serve.dispatch_ms"),
    "dispatch.launch_p50_ms": lambda run: span_p50_ms(run, "lz.dispatch.launch"),
    "dispatch.readback_p50_ms": lambda run: span_p50_ms(
        run, "lz.dispatch.readback"),
    # rows the device's boost scatter touched, a request: retrieval_cap where
    # every request boosts; a program that drops the boost reads 0
    "dispatch.boost_rows_per_req": lambda run: counter_ratio(
        run, "device.boost_rows", "serve.requests", 1.0,
        marker="serve.requests"),
    # boosting dispatches that took the COPYING twin (the whole arena copied)
    # where the donated one would do: has to read 0
    "dispatch.copies": lambda run: counted(
        run, "serve.copy_dispatches", "serve.dispatches"),
    # kernels
    "kernel.serve_dev_ms": lambda run: device_ms_per_span(run, "lz.serve.batch"),
    "kernel.serve_roofline": serve_roofline_pct,
    # what a dispatch costs the device outside the scan: the tail (tier
    # columns, gate, neighbour gather, pack) and the boost's scatter
    "kernel.boost_dev_ms": lambda run: device_ms_outside(run, SCAN),
    # device
    "device.idle_pct": idle_pct,
    "device.compiles": compiles,
}


def reader_for(path: str) -> Callable:
    """The reader of the family that the metric file ``path`` belongs to:
    its name without ``.py`` and without the cell's suffix."""
    family = os.path.basename(path)[:-len(".py")].rpartition(".")[0]
    if family not in FAMILIES:
        raise KeyError(f"{os.path.basename(path)} names no metric family: "
                       f"{family!r} is none of {sorted(FAMILIES)}")
    return FAMILIES[family]
