"""Readers of a mixed window (PR 43): what the harness's own clock says of
readers beside a writer — which share of the window lay inside a
conversation, and the median latency of the reads that were DUE while one
was in progress. ``run.due_s`` and ``run.conversation_spans`` are seconds
from the window's one ``t0``. As ``readers.py`` prescribes, a reader returns
None for what the run cannot show: no writer, no readers, or too few such
reads for a median."""

from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark import stats

MIN_READS = 200         # fewer and their median is not reported


def writer_active_pct(run) -> Optional[float]:
    """100 x the part of the window's ``seconds`` inside a conversation."""
    spans = np.clip(run.conversation_spans, 0.0, run.seconds)
    if not len(spans):
        return None
    return 100.0 * float((spans[:, 1] - spans[:, 0]).sum()) / run.seconds


def inside(spans: np.ndarray, at: np.ndarray) -> np.ndarray:
    """Per time of ``at``: whether it lies inside one of the ``[n, 2]``
    start-sorted, disjoint ``spans`` (a span's start is inside, its end
    outside)."""
    i = np.searchsorted(spans[:, 0], at, side="right") - 1
    return (i >= 0) & (at < spans[np.maximum(i, 0), 1])


def in_write(run) -> Optional[np.ndarray]:
    """Per finished read: whether it fell due inside a conversation."""
    if not len(run.conversation_spans) or not len(run.due_s):
        return None
    return inside(run.conversation_spans, run.due_s)


def read_p50_in_write_ms(run) -> Optional[float]:
    """Median latency of the reads due inside a conversation."""
    mask = in_write(run)
    if mask is None or int(mask.sum()) < MIN_READS:
        return None
    return stats.median(run.latency_ms[mask])
