"""Readers of a request's stages (PR 41): what the scheduler counts of every
served request on one clock — queue, account, executor call, demux wait, the
caller's wake-up and its return — as sums over ALL the window's requests
(``serve.*_us`` over ``serve.requests`` / ``serve.wakes`` / ``serve.returns``),
and how much of the latency the HARNESS measured those sums leave over. As
``span_metrics.py`` prescribes, a reader returns None for a program that
does not count this (no ``serve.exec_us``: the parent of the PR that added
the counters) or a run with nothing to divide by — never 0 for what it
could not measure."""

from __future__ import annotations

import sys
from typing import Dict, Optional

from benchmark.span_metrics import counter_ratio, has_counter

MARKER = "serve.exec_us"        # bumped on every served batch that is stamped
# the stages between a request's submission and its caller running again;
# a callback request has no wake-up
SUMMED = ("serve.queue_wait_us", "serve.account_us", "serve.exec_us",
          "serve.demux_wait_us", "serve.wake_us")


def stage_mean_ms(run, total_us: str, count: str) -> Optional[float]:
    """Mean milliseconds of one stage: its summed microseconds over the
    number of times it was counted."""
    return counter_ratio(run, total_us, count, 1e-3, marker=MARKER)


def parts_ms(run) -> Optional[Dict[str, float]]:
    """What ``unexplained_pct`` compares, in mean milliseconds a request:
    ``L``, the harness's own latency less its lateness (sent → done, on the
    harness's clock), each summed stage over the served requests, ``S``
    their sum, and the callers' return beside them."""
    n = run.counter("serve.requests")
    if not has_counter(run, MARKER) or not n or not len(run.latency_ms):
        return None
    out = {"L": float(run.latency_ms.mean() - run.late_ms.mean())}
    for name in SUMMED:
        out[name] = run.counter(name) / n / 1e3
    out["S"] = sum(out[name] for name in SUMMED)
    out["serve.return_us"] = stage_mean_ms(run, "serve.return_us",
                                           "serve.returns") or 0.0
    return out


def unexplained_pct(run) -> Optional[float]:
    """100 x |L - S| / L: the share of the latency the user feels that the
    program's stamps do not add up to. Says the signed parts on standard
    error, for PERF.md's breakdown."""
    parts = parts_ms(run)
    if parts is None or parts["L"] <= 0.0:
        return None
    print("request stages, mean ms: " + ", ".join(
        f"{name} {value:.4f}" for name, value in parts.items()),
        file=sys.stderr, flush=True)
    return 100.0 * abs(parts["L"] - parts["S"]) / parts["L"]
