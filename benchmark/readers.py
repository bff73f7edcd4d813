"""Arithmetic the metric readers share. A reader is a file
``benchmark/metrics/<metric>.py`` with one function ``read(run)``; it returns
None when the run holds nothing for it to read (no trace, no such timer), and
the harness then leaves the metric out of the line. It never returns 0 for a
share of a roofline or of a peak that it could not measure."""

from __future__ import annotations

from typing import List, Optional

from benchmark import files, peaks, stats, tracing


def timer_p50(run, name: str) -> Optional[float]:
    values = run.timer(name)
    return stats.median(values) if values else None


def latency_percentile(run, p: float) -> Optional[float]:
    """Of ALL requests the window finished, from the time each was due."""
    return stats.percentile(run.latency_ms, p) if len(run.latency_ms) else None


def idle_pct(run) -> Optional[float]:
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * tracing.device_busy(run.trace)["idle_share"]


def compiles(run) -> float:
    return float(run.compiles_in_window)


def device_ms_per_span(run, prefix: str) -> Optional[float]:
    """Device-busy milliseconds inside the union of the host spans
    ``prefix*``, over their number: what one dispatch keeps the device busy,
    read once per dispatch whether dispatches overlap or not."""
    if run.trace is None:
        return None
    ns = tracing.device_ns_per_dispatch(run.trace, prefix)
    return None if ns is None else ns / 1e6


def span_minus_child_p50_ms(run, outer: str, inner_prefix: str
                            ) -> Optional[float]:
    """Median, over the ``outer`` spans, of their length less the length of
    the ``inner_prefix*`` spans that start inside them."""
    if run.trace is None:
        return None
    outs = sorted((s, s + d) for _, s, d in tracing.spans_named(run.trace, outer))
    inner = sorted((s, d) for n, s, d in tracing.clip(
        run.trace["spans"], tracing.window_of(run.trace))
        if n.startswith(inner_prefix) and n != outer)
    self_ms: List[float] = []
    i = 0
    for a, b in outs:
        while i < len(inner) and inner[i][0] < a:
            i += 1
        child = 0.0
        while i < len(inner) and inner[i][0] < b:
            child += inner[i][1]
            i += 1
        self_ms.append((b - a - child) / 1e6)
    return stats.median(self_ms) if self_ms else None


def serve_roofline_pct(run) -> Optional[float]:
    """The least time the configuration's demand file allows one
    dispatch of the window's mean batch, over the device time a dispatch
    took. Above 100% would mean the demand is counted too high."""
    dev_ms = device_ms_per_span(run, "lz.serve.batch")
    live, batches = run.counter("serve.live_requests"), run.counter("serve.batches")
    if not dev_ms or not batches:
        return None
    need = files.load_module(run.cfg["demand"], run.root).need(
        run.cfg, live / batches)
    least = peaks.least_seconds(need, peaks.peaks_for(run.device_kind))
    return 100.0 * least["seconds"] * 1e3 / dev_ms
