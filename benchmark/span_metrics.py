"""Readers of the program's own spans and counters (PR 25): what the
per-layer metrics added with them share. The spans are the ``lz.<name>``
annotations ``lazzaro_tpu.utils.telemetry.Span`` writes into the profiler's
trace, cut to the measured window as ``tracing.spans_named`` cuts them; the
counters are the program registry's. As ``benchmark/readers.py`` prescribes,
a reader returns None when the run holds nothing for it to read: no trace,
or a program without that span or counter (the parent of the PR that added
them) — never 0 for what it could not measure."""

from __future__ import annotations

from typing import Optional, Sequence

from benchmark import stats, tracing


def _durations_ns(run, name: str) -> list:
    if run.trace is None:
        return []
    return [d for _, _, d in tracing.spans_named(run.trace, name)]


def span_p50_ms(run, name: str) -> Optional[float]:
    """Median length of the spans called ``name``."""
    d = _durations_ns(run, name)
    return stats.median(d) / 1e6 if d else None


def span_ms_per(run, names: Sequence[str], per: str) -> Optional[float]:
    """Summed length of the spans called any of ``names``, over the number
    of ``per`` spans (milliseconds of store work per conversation)."""
    n = len(_durations_ns(run, per))
    parts = [_durations_ns(run, name) for name in names]
    if not n or not any(parts):
        return None
    return sum(sum(p) for p in parts) / n / 1e6


def span_count_per(run, names: Sequence[str], per: str) -> Optional[float]:
    """Number of spans called any of ``names`` over the number of ``per``
    spans (file operations per conversation)."""
    n = len(_durations_ns(run, per))
    total = sum(len(_durations_ns(run, name)) for name in names)
    return total / n if n and total else None


def busy_pct(run, idle: str) -> Optional[float]:
    """100 x (1 - the ``idle`` spans' share of the window): how much of the
    window a worker that records its waits as ``idle`` spans was at work."""
    d = _durations_ns(run, idle)
    if not d:
        return None
    lo, hi = tracing.window_of(run.trace)
    return 100.0 * (1.0 - sum(d) / (hi - lo))


def has_counter(run, name: str) -> bool:
    """Whether the program's registry holds a counter of this name at all
    (a counter that was never bumped has no entry)."""
    tel = run.telemetry
    return tel is not None and any(
        key == name or key.startswith(name + "{") for key in tel.counters)


def counter_ratio(run, num: str, den: str, scale: float = 1.0,
                  marker: Optional[str] = None) -> Optional[float]:
    """``scale * num / den`` of two program counters. ``marker`` names a
    counter the program bumps wherever it could bump ``num``: with it
    present a missing ``num`` reads 0 (nothing to count), without it None
    (a program that does not count this)."""
    if not has_counter(run, marker or num):
        return None
    d = run.counter(den)
    return scale * run.counter(num) / d if d else None
