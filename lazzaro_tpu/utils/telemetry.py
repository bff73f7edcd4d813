"""Serving telemetry: the process-wide metrics registry.

The reference tracks metrics in an ad-hoc dict on MemorySystem with inline
emoji prints (SURVEY §5: retrieval_times[], consolidation_times[], tiered
⚡/✓/⏱ latency prints, no structured logging). Since ISSUE 6 this module is
the one sink every serving-path measurement flows into:

- **timers** — ring-buffered latency samples with percentile summaries
  (``record`` / ``span``): queue wait per request, device dispatch wall
  time per mega-batch, readback decode, chat retrieval, consolidation;
- **spans** — ``Telemetry.span(name)`` is the ONE way the program times a
  region: it enters ``jax.profiler.TraceAnnotation("lz." + name)`` (so the
  region lies on the profiler's clock beside the device's ``XLA Ops``
  whenever a trace is being taken, and costs one cheap check when none
  is: ~2 us a span all told), names its enclosing span on a thread-local stack, and records
  its length under a timer. "Off" is the profiler not started;
- **counters** — monotonic totals (``bump``): requests, dispatches per
  mode, the device-side counters decoded from the packed readback tail
  (gate hits, top-k shortfall, dedup hits, boost-scatter rows, link-pool
  occupancy/overflow);
- **gauges** — last-value observations (``gauge``): batch occupancy,
  compile-cache entries, ``memory_analysis()`` peak-HBM per
  (mode × geometry × mesh) kernel.

Every metric name may carry labels (``labels={"tenant": ...}``); the
(name, labels) pair canonicalizes to one key in Prometheus sample syntax,
so ``prometheus()`` can render the whole registry as a text exposition and
``snapshot()`` as a JSON-able dict (bench artifacts embed it; the
dashboard serves both). Label cardinality is clamped per metric so a
million distinct tenants cannot grow the registry without bound — excess
label values collapse into ``"~other"``.

Instances are thread-safe and cheap (a deque append / int add under one
lock). ``REGISTRY`` is the process-wide default used by components
constructed standalone; ``MemorySystem`` owns a private instance so two
systems in one process (tests, multi-user benches) never mix samples.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict, deque
from typing import Deque, Dict, List, Optional

import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation

SPAN_PREFIX = "lz."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# Per-metric bound on distinct label COMBINATIONS. Overflowing values are
# folded into one "~other" series, so a tenant explosion degrades to a
# coarse aggregate instead of unbounded memory.
MAX_LABEL_SETS = 256


def _fmt_labels(labels: Dict[str, object]) -> str:
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return "{" + inner + "}"


def split_key(key: str):
    """``name{k="v",...}`` → (name, label_str) — the inverse of the
    canonical key the registry stores under."""
    i = key.find("{")
    if i < 0:
        return key, ""
    return key[:i], key[i:]


# The spans open on each thread, innermost last. One stack for the whole
# process (not one per registry): the compile listener below has to find
# the span of the thread that compiled, whichever registry opened it.
_OPEN = threading.local()


def _open_spans() -> List["Span"]:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


def current_span() -> Optional["Span"]:
    """The innermost span open on the calling thread, or None."""
    stack = _open_spans()
    return stack[-1] if stack else None


class Span(TraceAnnotation):
    """One timed region of the program (``Telemetry.span`` makes them).

    Entering it starts the profiler annotation ``lz.<name>`` and pushes the
    span on the calling thread's stack; ``parent`` is then the name of the
    span that was innermost before — the one that caused this one. All
    spans of one dispatch lie inside one ``lz.serve.batch`` step, all spans
    of one conversation inside one ``lz.api.end_conversation``. Leaving it
    records its length in milliseconds under ``timer`` unless the registry
    is disabled; the annotation is kept either way."""

    def __init__(self, tel: "Telemetry", name: str, timer: str,
                 labels: Optional[Dict]):
        TraceAnnotation.__init__(self, SPAN_PREFIX + name)
        self.tel = tel
        self.name = name
        self.timer = timer
        self.labels = labels
        self.parent: Optional[str] = None
        self._t0 = 0.0

    def __enter__(self) -> "Span":
        stack = _open_spans()
        if stack:
            self.parent = stack[-1].name
        stack.append(self)
        self._t0 = time.perf_counter()
        TraceAnnotation.__enter__(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        TraceAnnotation.__exit__(self, exc_type, exc, tb)
        ms = (time.perf_counter() - self._t0) * 1e3
        _OPEN.stack.pop()
        tel = self.tel
        if tel.enabled:
            if self.labels is None:
                # what ``record`` does for an unlabelled timer, without its
                # key lookup: a dozen spans ride every dispatch
                tel.timers[self.timer].append(ms)
            else:
                tel.record(self.timer, ms, self.labels)


class Telemetry:
    def __init__(self, window: int = 10_000, enabled: bool = True):
        # ``enabled=False`` turns every writer into a cheap no-op (the
        # MemoryConfig.serve_telemetry switch) — readers keep working on
        # whatever was recorded before the flip.
        self.enabled = bool(enabled)
        self.window = window
        self._lock = threading.Lock()
        self.timers: Dict[str, Deque[float]] = defaultdict(
            lambda: deque(maxlen=window))
        self.counters: Dict[str, int] = defaultdict(int)
        self.gauges: Dict[str, float] = {}
        self._series_per_name: Dict[str, int] = defaultdict(int)
        self._known_keys = set()

    # ------------------------------------------------------------------ keys
    def _key(self, name: str, labels: Optional[Dict] = None) -> str:
        if not labels:
            return name
        key = name + _fmt_labels(labels)
        # cardinality clamp: past the per-name budget, new label sets fold
        # into one "~other" series (existing keys keep recording)
        with self._lock:
            if key not in self._known_keys:
                if self._series_per_name[name] >= MAX_LABEL_SETS:
                    return name + _fmt_labels(
                        {k: "~other" for k in labels})
                self._series_per_name[name] += 1
                self._known_keys.add(key)
        return key

    # --------------------------------------------------------------- writers
    def record(self, name: str, value_ms: float,
               labels: Optional[Dict] = None) -> None:
        if not self.enabled:
            return
        self.timers[self._key(name, labels)].append(float(value_ms))

    def bump(self, name: str, n: int = 1,
             labels: Optional[Dict] = None) -> None:
        if n == 0 or not self.enabled:
            return
        key = self._key(name, labels)
        with self._lock:
            self.counters[key] += int(n)

    def gauge(self, name: str, value: float,
              labels: Optional[Dict] = None) -> None:
        if not self.enabled:
            return
        self.gauges[self._key(name, labels)] = float(value)

    def span(self, name: str, *, timer: Optional[str] = None,
             labels: Optional[Dict] = None) -> Span:
        """``with tel.span("index.pack"):`` — the profiler annotation
        ``lz.index.pack`` and the timer ``index.pack_ms`` (or ``timer``)."""
        return Span(self, name, timer or name + "_ms", labels)

    # --------------------------------------------------------------- readers
    def counter_total(self, name: str) -> int:
        """Sum of a counter across every label set (e.g. all modes)."""
        with self._lock:
            return sum(v for k, v in self.counters.items()
                       if split_key(k)[0] == name)

    def timer_count(self, name: str) -> int:
        """Sample count of a timer across every label set."""
        return sum(len(v) for k, v in self.timers.items()
                   if split_key(k)[0] == name)

    def timer_values(self, name: str) -> list:
        """All ring-buffered samples of a timer across every label set."""
        out: list = []
        for k, v in list(self.timers.items()):
            if split_key(k)[0] == name:
                out.extend(v)
        return out

    def snapshot(self) -> Dict[str, Dict]:
        """One JSON-able view of the whole registry — embedded in bench
        artifacts and served by the dashboard's ``/api/metrics``."""
        timers: Dict[str, Dict[str, float]] = {}
        for name, values in list(self.timers.items()):
            arr = np.asarray(values)
            if arr.size:
                timers[name] = {
                    "count": int(arr.size),
                    "avg_ms": float(arr.mean()),
                    "p50_ms": float(np.percentile(arr, 50)),
                    "p95_ms": float(np.percentile(arr, 95)),
                    "max_ms": float(arr.max()),
                }
        with self._lock:
            counters = dict(self.counters)
        return {"timers": timers, "counters": counters,
                "gauges": dict(self.gauges)}

    def prometheus(self, prefix: str = "lazzaro") -> str:
        """Prometheus text exposition (v0.0.4) of the registry. Metric
        names sanitize ``.`` → ``_``; timers expose ``_count`` /
        ``_avg_ms`` / ``_p50_ms`` / ``_p95_ms`` gauges, counters expose
        ``_total``, gauges expose their value as-is — all with the
        original label sets preserved."""
        def san(name: str) -> str:
            return f"{prefix}_{name}".replace(".", "_").replace("-", "_")

        lines = []
        typed = set()

        def emit(full_name: str, label_str: str, kind: str, value) -> None:
            if full_name not in typed:
                typed.add(full_name)
                lines.append(f"# TYPE {full_name} {kind}")
            lines.append(f"{full_name}{label_str} {value}")

        snap = self.snapshot()
        for key, stats in sorted(snap["timers"].items()):
            base, label_str = split_key(key)
            for suffix, val in (("count", stats["count"]),
                                ("avg_ms", stats["avg_ms"]),
                                ("p50_ms", stats["p50_ms"]),
                                ("p95_ms", stats["p95_ms"])):
                emit(f"{san(base)}_{suffix}", label_str, "gauge", val)
        for key, val in sorted(snap["counters"].items()):
            base, label_str = split_key(key)
            emit(f"{san(base)}_total", label_str, "counter", val)
        for key, val in sorted(snap["gauges"].items()):
            base, label_str = split_key(key)
            emit(san(base), label_str, "gauge", val)
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self.timers.clear()
            self.counters.clear()
            self.gauges.clear()
            self._series_per_name.clear()
            self._known_keys.clear()

    @staticmethod
    def tier(latency_ms: float) -> str:
        """The reference's emoji latency tiers (memory_system.py:332-337)."""
        return "⚡" if latency_ms < 100 else ("✓" if latency_ms < 200 else "⏱")


# The process-wide default registry: components constructed standalone
# (a bare MemoryIndex, a QueryScheduler in a test harness) record here;
# MemorySystem threads its own instance through everything it owns.
REGISTRY = Telemetry()


def default_registry() -> Telemetry:
    return REGISTRY


def file_op(tel: Telemetry, layer: str, op: str) -> Span:
    """One file operation at one of the funnels of the store (``layer``
    "store") or of a write-ahead log ("journal"): the span
    ``lz.<layer>.io`` around it and one ``store.file_ops{op}`` count."""
    tel.bump("store.file_ops", labels={"op": op})
    return tel.span(layer + ".io")


def _on_compile(event: str, duration_s: float, **_) -> None:
    """jax calls this on the thread that compiled, so that thread's
    innermost span says which part of the program paid: an operator alerts
    on ``lazzaro_compile_events_total`` after warm-up, a builder reads the
    ``span`` label. Outside any span the default registry counts it."""
    if event != COMPILE_EVENT:
        return
    span = current_span()
    tel = span.tel if span is not None else REGISTRY
    tel.bump("compile.events",
             labels={"span": span.name if span is not None else "none"})
    tel.record("compile.ms", duration_s * 1e3)


# One listener for the process, whatever number of registries it holds.
jax.monitoring.register_event_duration_secs_listener(_on_compile)


def record_device_counters(tel: Telemetry, counters, fast, gate_on, valid,
                           k_req, sem_active: bool = False) -> None:
    """Fold one fused readback's device-counter tail into the registry —
    shared by the single-chip (``core.index``) and pod
    (``parallel.index``) decoders. ``counters`` is the
    ``utils.batching.unpack_retrieval`` tail ([Q, 5] int32: live, dup,
    acc-boost rows, nbr-boost rows, semantic verdict), ``fast`` the
    device gate verdicts, ``gate_on``/``valid`` the per-query flags,
    ``k_req`` each request's asked-for k (shortfall counts against THAT,
    not the padded kernel bucket). ``sem_active`` marks dispatches that
    actually carried the semantic ring — without it a cache-off turn
    would count every query as a semantic miss (the column is always
    present, just all-zero)."""
    v = np.asarray(valid, bool)
    if not v.any():
        return
    live = np.asarray(counters[:, 0])[v]
    want = np.asarray(k_req)[v]
    g_on = np.asarray(gate_on, bool)[v]
    f = np.asarray(fast, bool)[v]
    tel.bump("device.gate_hit", int((g_on & f).sum()))
    tel.bump("device.gate_miss", int((g_on & ~f).sum()))
    tel.bump("device.topk_shortfall", int(np.maximum(want - live, 0).sum()))
    tel.bump("device.dedup_hits", int(counters[:, 1][v].sum()))
    tel.bump("device.boost_rows", int(counters[:, 2][v].sum()))
    tel.bump("device.nbr_boost_rows", int(counters[:, 3][v].sum()))
    if sem_active and counters.shape[1] > 4:
        n_hit = int((counters[:, 4][v] > 0).sum())
        tel.bump("serve.semantic_hits", n_hit)
        tel.bump("serve.semantic_misses", int(v.sum()) - n_hit)


def peak_bytes(memory_stats) -> Optional[float]:
    """Peak live bytes of one compiled fused program, from
    ``compiled.memory_analysis()`` ("Memory Safe Computations with XLA" —
    compile-time introspection is cheap). None when the backend doesn't
    report (some TPU runtimes return None pre-execution)."""
    if memory_stats is None:
        return None
    try:
        return float(memory_stats.argument_size_in_bytes
                     + memory_stats.output_size_in_bytes
                     + memory_stats.temp_size_in_bytes
                     - memory_stats.alias_size_in_bytes)
    except AttributeError:
        return None
