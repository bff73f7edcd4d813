"""One run of one cell: set-up, window, trace, comparison, result.

Driven by data: a cell is an entry of ``BENCHMARK.json``'s ``workloads``
naming a configuration (its ``file``) and a traffic mix
(``benchmark/mixes/<traffic>.json``, whose ``loop`` picks one of ``LOOPS``); a metric is an entry naming its reader
(``benchmark/metrics/<name>.py``, one function ``read(run)``). The
configuration names its plain reference and its demand function by path and
carries the program's ``MemoryConfig`` fields verbatim. Nothing below
branches on a cell's, a configuration's or a metric's name.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import ProfileOptions, TraceAnnotation

from benchmark import corpus, loadgen, tracing
from benchmark.files import ROOT, load_json, load_module

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _with_debug(d: dict, debug: bool) -> dict:
    """``d`` without its ``debug`` key; in a debug run (tests) with that
    key's tiny sizes laid over it, groups merged key by key."""
    out = {k: v for k, v in d.items() if k != "debug"}
    for k, v in (d.get("debug", {}) if debug else {}).items():
        both = isinstance(v, dict) and isinstance(out.get(k), dict)
        out[k] = {**out[k], **v} if both else v
    return out


def mesh_chips(cfg: dict) -> int:
    """Devices the layout a configuration names spans: 1 without ``mesh``."""
    return math.prod(cfg["mesh"]["shape"]) if "mesh" in cfg else 1


def cell_files(workload: str, root: str = ROOT, debug: bool = False
               ) -> Tuple[dict, dict, dict]:
    """(cell, configuration, mix) of a workload, found by name. The cell's
    ``chips`` have to be what the configuration's layout spans (a debug run
    then lays the ``debug`` block's own small mesh over it)."""
    m = manifest(root)
    cells = [w for w in m["workloads"] if w["name"] == workload]
    if len(cells) != 1:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
    cell = cells[0]
    conf = [c for c in m["configs"] if c["name"] == cell["config"]][0]
    cfg = load_json(os.path.join(root, conf["file"]))
    if mesh_chips(cfg) != cell["chips"]:
        raise ValueError(
            f"workload {workload!r} asks for {cell['chips']} chip(s), but its "
            f"configuration {conf['name']!r} is laid out over "
            f"{mesh_chips(cfg)} ({cfg.get('mesh', 'no mesh')})")
    mix = load_json(os.path.join(root, "benchmark", "mixes",
                                 cell["traffic"] + ".json"))
    return cell, _with_debug(cfg, debug), _with_debug(mix, debug)


def metrics_of(cell: dict, kind: str, root: str = ROOT) -> List[dict]:
    """The manifest's metrics of ``kind`` that this cell reports."""
    return [m for m in manifest(root)[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def reader(name: str, root: str = ROOT) -> Callable:
    return load_module(os.path.join("benchmark", "metrics", name + ".py"),
                       root).read


class Compiles:
    """Backend compilations of this process, as jax reports them."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class Run:
    """Everything a metric reader may read. Readers return None for what
    this run cannot show (no trace, another loop)."""

    def __init__(self, cell, cfg, mix, seed, seconds, traced, root=ROOT):
        self.cell, self.cfg, self.mix, self.root = cell, cfg, mix, root
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.setup_s = 0.0
        # real length of the window; where a writer runs, the writer's: the
        # window's start to the end of its last conversation
        self.window_s = 0.0
        self.latency_ms = np.zeros(0)       # due -> done, finished requests
        self.late_ms = np.zeros(0)          # due -> sent (open loop)
        self.due_s = np.zeros(0)            # window start -> due, the same
        self.completed_in_window = 0        # finished inside ``seconds``
        self.conversation_s = np.zeros(0)
        # [n, 2] window start -> a conversation's start and end (mixed loop)
        self.conversation_spans = np.zeros((0, 2))
        self.memories_acked = 0
        self.telemetry = None               # the system's Telemetry
        self.trace: Optional[dict] = None
        self.compiles_in_window = 0
        self.device_kind = ""
        self.attempted = self.failed = 0
        self.want_detail = False
        self.detail: Optional[dict] = None

    def timer(self, name: str) -> list:
        return self.telemetry.timer_values(name) if self.telemetry else []

    def counter(self, name: str) -> int:
        return self.telemetry.counter_total(name) if self.telemetry else 0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Phases:
    """Seconds of each set-up phase, on standard error: set-up is most of
    what a check costs, so what it is made of is always shown."""

    def __init__(self, t_start: float):
        self.at, self.parts = t_start, []

    def done(self, name: str) -> None:
        now = time.perf_counter()
        self.parts.append(f"{name} {now - self.at:.1f}s")
        self.at = now

    def report(self) -> None:
        say("set-up: " + ", ".join(self.parts))


# --------------------------------------------------------------------------
# Plans: everything a window sends, made before it.
# --------------------------------------------------------------------------

class ServePlan:
    def __init__(self, cfg, mix, seed, seconds, starts, tenant_first,
                 make_requests):
        rng = np.random.default_rng([int(seed), 0x5E17E])
        tenants = cfg["tenants"]
        if mix["loop"] == "open":
            n = max(1, int(round(mix["rate_rps"] * seconds)))
            self.due = loadgen.poisson_schedule(n, mix["rate_rps"], rng)
        else:
            n = int(mix["query_pool"])
            self.due = None
        self.tenant = loadgen.tenant_sequence(n, tenants, mix["zipf_s"], rng)
        size = (starts[1:] - starts[:-1]).astype(np.int64)
        self.fact = (rng.random(n) * size[self.tenant]).astype(np.int32)
        seed2 = jnp.asarray(corpus.seed_words(seed))
        q = []
        for a in range(0, n, 8192):
            t = self.tenant[a:a + 8192]
            q.append(np.asarray(corpus.query_vectors(
                seed2, jnp.asarray(t + tenant_first),
                jnp.asarray(self.fact[a:a + 8192]),
                jnp.asarray(size[t].astype(np.int32)),
                jnp.arange(a, a + len(t), dtype=jnp.int32),
                dim=cfg["dim"])))
        self.queries = np.concatenate(q)
        self.k = int(mix["k"])
        # a mix may say what share of its requests are ``chat`` retrievals,
        # reads that write: that many of them, marked from a stream of their
        # own. A mix without the key makes the plan it made before the key.
        self.boost: Optional[np.ndarray] = None
        if "boost_share" in mix:
            share = float(mix["boost_share"])
            if not 0.0 <= share <= 1.0:
                raise ValueError(f"boost_share {share} is no share")
            self.boost = np.arange(n) < int(round(share * n))
            np.random.default_rng([int(seed), 0xB0057]).shuffle(self.boost)
        self.requests = make_requests(self.queries, self.tenant + tenant_first,
                                      self.k, self.boost)
        # the answers compared after the window, drawn from the seed now:
        # up to check_per_tenant requests of check_tenants tenants
        self.keep = np.zeros(n, bool)
        picked: Dict[int, int] = {}
        want = mix["check_tenants"] * mix["check_per_tenant"]
        for i in np.random.default_rng([int(seed), 0xC0FFEE]).permutation(n):
            t = int(self.tenant[i])
            if t not in picked and len(picked) >= mix["check_tenants"]:
                continue
            if picked.get(t, 0) < mix["check_per_tenant"]:
                picked[t] = picked.get(t, 0) + 1
                self.keep[i] = True
                want -= 1
                if not want:
                    break
        self.check_tenants = sorted(picked)


# --------------------------------------------------------------------------
# Comparison with the reference, after the window.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("size",))
def _cut(block, start, *, size: int):
    return jax.lax.dynamic_slice(block, (start, jnp.int32(0)),
                                 (size, block.shape[1]))


def tenant_rows(cfg, seed, starts, tenant_first, t) -> np.ndarray:
    """[n, dim] f32: tenant ``t``'s rows as set-up installed them, made again
    by the same compiled generator call on the same device — nothing is
    read from the program. One fixed-size cut per block keeps it to two
    compiled programs whatever the tenant."""
    block = cfg["fill_block_rows"]
    size = int((starts[1:] - starts[:-1]).max())
    if size > block:
        raise ValueError("a tenant's rows must fit one fill block")
    lo, hi = int(starts[t]), int(starts[t + 1])
    seed2 = jnp.asarray(corpus.seed_words(seed))
    starts_d = jnp.asarray(starts)
    parts = []
    for row0 in range(lo - lo % block, hi, block):
        blk, _, _ = corpus.block_rows(
            seed2, starts_d, jnp.int32(tenant_first), jnp.int32(row0),
            block=block, dim=cfg["dim"], dtype=cfg["dtype"])
        a, b = max(lo, row0) - row0, min(hi, row0 + block) - row0
        c = min(a, block - size)
        cut = np.asarray(_cut(blk, jnp.int32(c), size=size))
        parts.append(cut[a - c:b - c].astype(np.float32))
    return np.concatenate(parts)


def boost_constants(cfg: dict, mix: dict, salience0: float) -> dict:
    """What a boost adds, as the replay assumes it: the mix's ``boost`` group
    restates the program's documented defaults, a field the configuration's
    ``memory_config`` names wins, and ``salience0`` is what set-up installed."""
    names = ("retrieval_cap", "access_salience_boost",
             "neighbor_salience_boost", "serve_max_nbr")
    return {"salience0": salience0,
            **{n: cfg["memory_config"].get(n, mix["boost"][n]) for n in names}}


def check_state(reference, cmp, cfg, plan, state, rows, t, lo, hi):
    """Tenant ``t``'s rows ``[lo, hi)`` of the state read back after the
    window against the replay of EVERY boosting request of ``t`` the window
    completed (``state["done"]``; a closed loop sends one more than once)."""
    done = state["done"]
    reqs, times = np.unique(done[plan.tenant[done] == t], return_counts=True)
    boost = state["boost"]
    near = None
    if "graph" in cfg:
        near = corpus.neighbour_lists(rows.shape[0],
                                      corpus.tenant_edges(rows, cfg["graph"]))
    want = reference.boost_bounds(
        rows, np.ones(rows.shape[0], bool), plan.queries[reqs], times,
        min(plan.k, boost["retrieval_cap"]), cfg["dtype"],
        cfg["limits"]["score_gap"], near, boost["serve_max_nbr"])
    cmp.state(f"tenant {t}", {n: c[lo:hi] for n, c in state["columns"].items()},
              want, boost, state["window"])


def check_serving(reference, cmp, cfg, plan, samples, seed, starts,
                  tenant_first, control: Optional[str],
                  state: Optional[dict] = None):
    """The kept answers of the timed requests against the reference, tenant
    by tenant; under a mix that boosts also the ``state`` the window left
    (``columns``, ``window``, ``boost``), for the tenants drawn for the
    check."""
    from benchmark.deploy import parse_node_id

    by_tenant: Dict[int, List[int]] = {}
    for i in sorted(samples.answers):
        by_tenant.setdefault(int(plan.tenant[i]), []).append(i)
    if state is not None:
        for t in plan.check_tenants:
            by_tenant.setdefault(t, [])
        done = samples.request_of[samples.ok]
        state = {**state, "done": done[plan.boost[done]]}
    k = plan.k
    for t, reqs in sorted(by_tenant.items()):
        rows = tenant_rows(cfg, seed, starts, tenant_first, t)
        if state is not None:
            check_state(reference, cmp, cfg, plan, state, rows, t,
                        int(starts[t]), int(starts[t + 1]))
        if not reqs:
            continue
        live = np.ones(rows.shape[0], bool)
        q = plan.queries[reqs]
        variants = reference.query_variants(rows, live, q, k, cfg["dtype"])
        ctl = (reference.int8_answers(rows, live, reference.unit(q), k)
               if control is not None else None)
        for n, i in enumerate(reqs):
            label = f"request {i} tenant {t}"
            if ctl is not None:
                idx, sc = ctl[n]
            else:
                res = samples.answers[i]
                idx, sc = [], []
                for nid, score in zip(res.ids, res.scores):
                    who, fact = parse_node_id(nid)
                    if who != tenant_first + t:
                        cmp.foreign(label, repr(nid))
                    else:
                        idx.append(fact); sc.append(score)
            cmp.answer(label, idx, sc, [tuple(v[n] for v in var)
                                        for var in variants], live)


# --------------------------------------------------------------------------
# The run.
# --------------------------------------------------------------------------

def require_chips(chips: int, debug: bool) -> None:
    """``chips`` TPU chips; a debug run takes as many devices of any backend
    (its configuration's ``debug`` mesh)."""
    devs = jax.devices()
    if len(devs) >= chips and (debug or devs[0].platform == "tpu"):
        return
    what = (f"{chips} device(s) for the configuration's debug mesh (XLA_FLAGS="
            f"--xla_force_host_platform_device_count={chips} gives the CPU "
            f"backend as many)" if debug else f"{chips} TPU chip(s)")
    raise NoAccelerator(f"needs {what}; jax sees {len(devs)} "
                        f"{devs[0].platform} device(s)")


def _trace_options() -> ProfileOptions:
    o = ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: str = ROOT, debug: bool = False, t_start: float = None,
             control: Optional[str] = None, sabotage: Optional[Callable] = None,
             mix_override: Optional[dict] = None, detail: bool = False
             ) -> dict:
    """Run one cell once and return the result line's object. ``control``
    puts the lower-precision reference in the program's place for the
    comparison; ``sabotage(ms)`` breaks the timed path before the window
    (both are for the tests and ``study.py`` only, as are ``mix_override``,
    which changes parameters of the mix, and ``detail``, which adds the
    window's distributions to the returned object under ``detail``). The
    configuration's ``reference`` file decides ``correct``."""
    from benchmark import deploy

    t_start = time.perf_counter() if t_start is None else t_start
    cell, cfg, mix = cell_files(workload, root, debug)
    mix.update(mix_override or {})
    # the cell's chips; a debug run's are its configuration's debug mesh
    require_chips(mesh_chips(cfg), debug)
    # a debug run (tests) leaves the process's cache policy alone
    cache_dir = None if debug else deploy.place_cache()
    compiles = Compiles()
    run = Run(cell, cfg, mix, seed, seconds, traced, root)
    run.want_detail = detail
    run.device_kind = jax.devices()[0].device_kind
    work = tempfile.mkdtemp(prefix="lzbench-")
    trace_dir = os.path.join(work, "trace")
    reference = load_module(cfg["reference"], root)
    limits = dict(cfg["limits"])
    reads = mix.get("readers") or mix       # a mixed mix's readers: an open mix
    if "boost_share" in reads:
        # the state its reads leave is compared under a mix that boosts, and
        # only there: the mix brings that number's limit
        limits["state_errors"] = reads["limits"]["state_errors"]
    cmp = reference.Comparison(limits)
    try:
        if mix["loop"] not in LOOPS:
            raise ValueError(
                f"mix {mix['name']!r} names no loop the generator has: "
                f"{mix['loop']!r} is none of {', '.join(LOOPS)}")
        result = LOOPS[mix["loop"]](run, deploy, work, compiles, trace_dir,
                                    reference, cmp, t_start, control, sabotage)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(f"compile cache: {cache_dir}; compilations this process: "
        f"{compiles.count} ({compiles.seconds:.1f}s)")
    return result


def _start_trace(run: Run, trace_dir: str) -> None:
    if run.traced:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())


def _stop_trace(run: Run, trace_dir: str) -> None:
    if run.traced:
        jax.profiler.stop_trace()
        run.trace = tracing.read_xplane(tracing.newest_xplane(trace_dir))


def _freeze() -> None:
    """As a long-lived server does: set-up's million host objects are
    collected once and frozen, so no full collection of them falls into a
    window."""
    gc.collect()
    gc.freeze()


def _note_reads(run: Run, samples) -> np.ndarray:
    """What the window's requests leave on ``run``; returns which finished."""
    ok = samples.ok
    run.attempted += len(ok)
    run.failed += int((~ok).sum())
    run.latency_ms = (samples.done - samples.due)[ok] * 1e3
    run.late_ms = (samples.sent - samples.due)[ok] * 1e3
    run.due_s = (samples.due - samples.t0)[ok]
    run.window_s = samples.t1 - samples.t0
    inside = ok & (samples.done <= samples.t0 + run.seconds)
    run.completed_in_window = int(inside.sum())
    return ok


def _serve_run(run, deploy, work, compiles, trace_dir, reference, cmp,
               t_start, control, sabotage) -> dict:
    cfg, mix, seed = run.cfg, run.mix, run.seed
    phases = Phases(t_start)
    ms = deploy.build_system(cfg, work)
    phases.done("import+system")
    starts = deploy.install_rows(ms, cfg, seed, cfg["rows"], 0, cfg["tenants"])
    phases.done("rows")
    if "graph" in cfg:
        edges = deploy.install_graph(
            ms, cfg, starts, 0,
            lambda t: tenant_rows(cfg, seed, starts, 0, t))
        phases.done(f"graph of {edges} edges")
    deploy.warm_serving(ms, cfg)
    phases.done("warm-up")
    plan = ServePlan(cfg, mix, seed, run.seconds, starts, 0,
                     deploy.make_requests)
    phases.done("requests")
    if sabotage is not None:
        sabotage(ms)
    submit = ms.query_scheduler.submit
    run.telemetry = ms.telemetry
    _freeze()
    phases.done("collect+freeze")
    phases.report()
    ms.telemetry.reset()
    mark = compiles.count
    gc_before = [g["collections"] for g in gc.get_stats()]
    pauses = _watch_gc() if run.want_detail else None
    run.setup_s = time.perf_counter() - t_start
    opened = deploy.index_clock(ms)
    _start_trace(run, trace_dir)
    if mix["loop"] == "open":
        samples = loadgen.run_open(submit, plan.requests, plan.due,
                                   plan.keep, mix["drain_s"], TraceAnnotation)
    else:
        samples = loadgen.run_closed(submit, plan.requests, mix["clients"],
                                     plan.keep, run.seconds, mix["drain_s"],
                                     TraceAnnotation)
    _stop_trace(run, trace_dir)
    run.compiles_in_window = compiles.count - mark
    device = deploy.device_info(run.cell["chips"])
    swallowed = deploy.counters(ms)
    arena_rows = int(ms.index.state.salience.shape[0])
    state = None
    if plan.boost is not None:
        state = {"columns": deploy.read_state(ms),
                 "window": (opened, deploy.index_clock(ms)),
                 "boost": boost_constants(cfg, mix, deploy.SALIENCE)}
    gc.unfreeze()

    ok = _note_reads(run, samples)
    if run.want_detail:
        gc.callbacks.remove(pauses.hook)
        run.detail = _serve_detail(run, samples, ok, gc_before)
        run.detail["gc_pauses_ms"] = pauses.longest()
        if run.trace is not None:
            run.detail["spans"] = tracing.span_study(run.trace,
                                                     "lz.serve.batch")
            run.detail["dispatch_device_ms"] = tracing.dispatch_study(
                run.trace, "lz.serve.batch", "lz_select_scan")
    ms.close()
    del ms, submit, plan.requests
    gc.collect()

    cmp.swallowed = sum(swallowed.values())
    cmp.unanswered = run.failed
    check_serving(reference, cmp, cfg, plan, samples, seed, starts, 0,
                  control, state)
    say(f"arena {arena_rows} rows x {cfg['dim']} {cfg['dtype']}; "
        f"{run.attempted} requests, {run.failed} failed, "
        f"{cmp.answers} compared; swallowed {swallowed}")
    return finish(run, cmp, device)


class _watch_gc:
    """Study aid: how long each collection of the window stopped the world."""

    def __init__(self):
        self.t = 0.0
        self.seen: List[Tuple[int, float]] = []
        gc.callbacks.append(self.hook)

    def hook(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        else:
            self.seen.append((info["generation"],
                              (time.perf_counter() - self.t) * 1e3))

    def longest(self, n: int = 8) -> list:
        return sorted(self.seen, key=lambda x: -x[1])[:n]


_PCTS = (1, 5, 10, 25, 50, 75, 90, 95, 99, 99.9, 100)


def _pcts(values) -> dict:
    v = np.asarray(values, float)
    if not len(v):
        return {}
    return {"n": int(len(v)), **{f"p{p}": float(np.percentile(v, p))
                                 for p in _PCTS}}


def _serve_detail(run: Run, samples, ok, gc_before) -> dict:
    """The window's distributions, for the tail study (``study.py``)."""
    lat = run.latency_ms
    due_rel = (samples.due - samples.t0)[ok]
    slices = []
    for a in range(int(np.ceil(run.seconds))):
        m = (due_rel >= a) & (due_rel < a + 1)
        if m.sum() >= 20:
            slices.append([float(np.percentile(lat[m], 50)),
                           float(np.percentile(lat[m], 95))])
    hist, _ = np.histogram(lat, bins=np.arange(0, 60.5, 0.5))
    sizes = np.asarray(run.timer("serve.batch_requests"), int)
    return {
        "latency_ms": _pcts(lat), "late_ms": _pcts(run.late_ms),
        "latency_hist_half_ms": [int(h) for h in hist],
        "p50_p95_by_second": slices,
        "dispatch_ms": _pcts(run.timer("serve.dispatch_ms")),
        "queue_wait_ms": _pcts(run.timer("serve.queue_wait_ms")),
        "decode_ms": _pcts(run.timer("serve.decode_ms")),
        "batch_requests": _pcts(sizes),
        "batch_size_counts": np.bincount(sizes).tolist() if len(sizes) else [],
        "latency_ms_all": [round(float(x), 4) for x in lat],
        "due_s_all": [round(float(x), 5) for x in due_rel],
        "counters": {name: run.counter(name) for name in (
            "serve.requests", "serve.batches", "serve.dispatches",
            "serve.overlapped_batches", "serve.held_batches",
            "serve.copy_dispatches", "serve.h2d_puts", "device.boost_rows",
            "device.nbr_boost_rows")},
        "gc_collections_in_window": [
            g["collections"] - b for g, b in zip(gc.get_stats(), gc_before)],
        "window_s": run.window_s,
    }


class Writer:
    """The one writer's side of a window, from a group of parameters (an
    ``ingest-conv100`` mix, or a mixed mix's ``writer``): its providers, its
    tenants in the seed's order, and after the window the read-back of
    acknowledged memories and its comparison with the reference."""

    def __init__(self, cfg: dict, params: dict, seed: int, other=None):
        self.cfg, self.p, self.seed = cfg, params, seed
        self.facts = params["facts_per_conversation"]
        self.first = params["first_fact"]
        # (first fact, facts, corpus size) of a tenant's conversation: the
        # window's and warm-up's own, or what ``other(t)`` says of a tenant
        # stocked through the API
        own = (self.first, self.facts, cfg["facts_per_tenant"])
        self.shape_of = lambda t: (other(t) if other else None) or own
        self.emb = corpus.SeededEmbedder(seed, cfg["dim"], cfg["dup_every"],
                                         lambda t: self.shape_of(t)[2])
        self.llm = corpus.SeededLLM(self.shape_of)
        t0, tn = params["window_tenants"]
        self.order = np.random.default_rng([int(seed), 0x1A6E57]).permutation(
            np.arange(t0, t0 + tn))

    def warm(self, deploy, ms, last_inside=contextlib.nullcontext) -> None:
        """The warm-up conversations; the last of them inside the context
        ``last_inside()`` makes."""
        w0, wn = self.p["warm_tenants"]
        for t in range(w0, w0 + wn - 1):
            deploy.run_conversation(ms, t)
        with last_inside():
            deploy.run_conversation(ms, w0 + wn - 1)

    def note(self, run: Run, log) -> List[int]:
        """What the writer's log leaves on ``run``; returns the tenants
        whose conversation was acknowledged."""
        for e in log.errors:
            say(f"write failed: {e}")
        if log.ran_out:
            say(f"the writer filled the deployment's free rows "
                f"({len(self.order)} conversations) in "
                f"{log.t1 - log.t0:.2f}s of {run.seconds}s: it stopped there")
        secs = np.asarray(log.seconds)
        good = np.isfinite(secs)
        run.attempted += len(secs)
        run.failed += int((~good).sum())
        run.conversation_s = secs[good]
        run.memories_acked = int(good.sum()) * self.facts
        # what the writer's rate divides by, whatever the loop: ALL its time,
        # from the window's start to the end of its last conversation
        run.window_s = log.t1 - log.t0
        return [t for t, g in zip(log.tenants, good) if g]

    def read_back(self, deploy, ms, done: List[int]) -> list:
        """Acknowledged memories, through the program's own API."""
        rng = np.random.default_rng([int(self.seed), 0xC0FFEE])
        chosen = list(dict.fromkeys(
            done[-1:] + [int(t) for t in rng.permutation(done)]
        ))[:self.p["check_tenants"]]
        js_all = np.arange(self.first, self.first + self.facts)
        dups = [int(j) for j in
                js_all[corpus.is_dup(js_all, self.cfg["dup_every"])]]
        out = []
        for t in chosen:
            probes = list(dict.fromkeys(
                dups + [j - 1 for j in dups if j - 1 >= self.first]
                + [int(j) for j in rng.permutation(js_all)]
            ))[:self.p["check_facts"]]
            out.append((t, probes) + deploy.read_back(
                ms, t, probes, self.emb.corpus(t)[probes], int(self.p["k"])))
        return out

    def compare(self, reference, cmp, readback: list,
                control: Optional[str]) -> None:
        cfg, emb, k = self.cfg, self.emb, int(self.p["k"])
        first, facts = self.first, self.facts
        js_all = np.arange(first, first + facts)
        live = np.zeros(cfg["facts_per_tenant"], bool)
        live[first:first + facts] = ~corpus.is_dup(js_all, cfg["dup_every"])
        for t, probes, nodes, by_text, by_vec in readback:
            rows = reference.stored(emb.corpus(t), cfg["dtype"])
            q = emb.corpus(t)[probes]
            # as in check_serving: the single-request programs keep the f32
            # query, the batched ones round it to the arena's dtype
            variants = reference.query_variants(rows, live, q, k, cfg["dtype"])
            if nodes != int(live.sum()):
                cmp.count_errors += 1
                cmp._fault(f"tenant {t} holds {nodes} nodes after {facts} "
                           f"facts, the reference {int(live.sum())}")
            ctl = (reference.int8_answers(rows, live, q, k)
                   if control is not None else None)
            for n, j in enumerate(probes):
                label = f"tenant {t} fact {j}"
                for hits, scores in ((by_text[n], None), by_vec[n]):
                    if ctl is not None:
                        if scores is None:
                            continue
                        idx, sc = ctl[n]
                    else:
                        idx, sc = [], ([] if scores is not None else None)
                        for r, (who, fact) in enumerate(hits):
                            if who != t:
                                cmp.foreign(label, f"fact {who}.{fact}")
                                continue
                            idx.append(fact)
                            if scores is not None:
                                sc.append(scores[r])
                    cmp.answer(label, idx, sc, [tuple(v[n] for v in var)
                                                for var in variants], live)


def _ingest_run(run, deploy, work, compiles, trace_dir, reference, cmp,
                t_start, control, sabotage) -> dict:
    cfg, mix, seed = run.cfg, run.mix, run.seed
    pre = mix["prefill"]
    stock = range(pre["tenant_first"], pre["tenant_first"] + pre["tenants"])
    writer = Writer(cfg, mix, seed, lambda t: (
        (0, pre["facts"], pre["facts"]) if t in stock else None))
    phases = Phases(t_start)
    ms = deploy.build_system(cfg, work, writer.emb, writer.llm)
    phases.done("import+system")
    # the deployment's stock: large conversations through the API itself,
    # so that index, buffer and store all hold it when the window opens
    for t in stock:
        deploy.run_conversation(ms, t)
    phases.done(f"stock of {pre['tenants']} x {pre['facts']} facts")
    writer.warm(deploy, ms)
    phases.done("warm-up conversations")
    deploy.warm_serving(ms, cfg)
    phases.done("warm-up serving")
    if sabotage is not None:
        sabotage(ms)
    capacity = ms.index.capacity
    run.telemetry = ms.telemetry
    _freeze()
    phases.done("collect+freeze")
    phases.report()
    ms.telemetry.reset()
    mark = compiles.count
    run.setup_s = time.perf_counter() - t_start
    _start_trace(run, trace_dir)
    log = loadgen.run_conversations(
        lambda t: deploy.run_conversation(ms, t), writer.order, run.seconds,
        TraceAnnotation)
    _stop_trace(run, trace_dir)
    run.compiles_in_window = compiles.count - mark
    device = deploy.device_info(run.cell["chips"])
    gc.unfreeze()
    _same_capacity(ms, capacity)
    done = writer.note(run, log)
    readback = writer.read_back(deploy, ms, done)
    swallowed = deploy.counters(ms)
    ms.close()
    del ms
    gc.collect()

    cmp.swallowed = sum(swallowed.values())
    cmp.unanswered = run.failed
    writer.compare(reference, cmp, readback, control)
    _say_writes(run, writer, done, readback, cmp, swallowed)
    return finish(run, cmp, device)


def _same_capacity(ms, capacity: int) -> None:
    if ms.index.capacity != capacity:
        raise RuntimeError("the arena grew inside the window: the cell is "
                           "sized so that it never has to")


def _say_writes(run, writer, done, readback, cmp, swallowed) -> None:
    drift = [round(1e3 * float(np.median(part)), 1)
             for part in np.array_split(run.conversation_s, 4) if len(part)]
    say(f"{len(done)} conversations x {writer.facts} facts in "
        f"{run.window_s:.2f}s (median ms by quarter of the window: {drift}); "
        f"{len(readback)} tenants read back, {cmp.answers} answers compared; "
        f"swallowed {swallowed}")


def mixed_groups(cfg: dict, mix: dict) -> Tuple[dict, Optional[dict],
                                                 Optional[dict]]:
    """(stock, readers, writer) of a mixed mix: ``stock`` ``{"rows",
    "tenants"}`` installed from the device, ``readers`` an open mix's
    parameters over the stock's tenants, ``writer`` an ingest mix's over
    tenants that are not of the stock. Either of the two may be absent (or
    None), not both."""
    stock, readers, writer = mix["stock"], mix.get("readers"), mix.get("writer")
    if readers is None and writer is None:
        raise ValueError(f"mixed mix {mix['name']!r} has neither 'readers' "
                         "nor 'writer': a window needs one of the two")
    block = cfg["fill_block_rows"]
    if stock["rows"] % block or not 0 < stock["rows"] <= cfg["rows"]:
        raise ValueError(
            f"mixed mix {mix['name']!r}: stock.rows {stock['rows']} is no "
            f"multiple of the configuration's fill_block_rows {block} inside "
            f"its {cfg['rows']} rows")
    if writer is not None:
        (w0, wn), (a0, an) = writer["window_tenants"], writer["warm_tenants"]
        mine = set(range(w0, w0 + wn)) | set(range(a0, a0 + an))
        if min(mine) < stock["tenants"] or len(mine) != wn + an:
            raise ValueError(
                f"mixed mix {mix['name']!r}: the writer's window_tenants and "
                f"warm_tenants have to be distinct tenants outside the "
                f"stock's {stock['tenants']}")
    return stock, readers, writer


def _mixed_run(run, deploy, work, compiles, trace_dir, reference, cmp,
               t_start, control, sabotage) -> dict:
    """Readers beside a writer over an installed stock: ``_serve_run``'s
    set-up, plan and comparison over the stock's tenants, ``_ingest_run``'s
    writer and read-back over tenants of its own, in one window."""
    cfg, mix, seed = run.cfg, run.mix, run.seed
    stock, readers, wparams = mixed_groups(cfg, mix)
    # with a writer the system has the seeded providers, as ``_ingest_run``
    # builds it; without one nothing ever calls a provider
    writer = Writer(cfg, wparams, seed) if wparams is not None else None
    phases = Phases(t_start)
    ms = deploy.build_system(cfg, work, *((writer.emb, writer.llm)
                                          if writer else ()))
    phases.done("import+system")
    starts = deploy.install_rows(ms, cfg, seed, stock["rows"], 0,
                                 stock["tenants"])
    phases.done(f"stock of {stock['rows']} rows")
    if "graph" in cfg:
        edges = deploy.install_graph(
            ms, cfg, starts, 0,
            lambda t: tenant_rows(cfg, seed, starts, 0, t))
        phases.done(f"graph of {edges} edges")
    if writer is not None:
        # the last warm conversation runs as the window's will, beside a
        # reader: the writer's copying twins are compiled here too, and the
        # serving programs below over a CSR pad that edges cannot outgrow
        writer.warm(deploy, ms, lambda: deploy.beside_a_reader(ms))
        deploy.presize_csr(ms)
        phases.done("warm-up conversations")
    deploy.warm_serving(ms, cfg)
    phases.done("warm-up serving")
    plan = None
    if readers is not None:
        plan = ServePlan(dict(cfg, tenants=stock["tenants"]),
                         dict(readers, loop="open"), seed, run.seconds, starts,
                         0, deploy.make_requests)
        phases.done("requests")
    if sabotage is not None:
        sabotage(ms)
    capacity = ms.index.capacity
    _freeze()
    phases.done("collect+freeze")
    phases.report()
    ms.telemetry.reset()
    mark = compiles.count
    pauses = _watch_gc() if run.want_detail else None
    gc_before = [g["collections"] for g in gc.get_stats()]
    run.setup_s = time.perf_counter() - t_start
    opened = deploy.index_clock(ms)
    _start_trace(run, trace_dir)
    samples, log = loadgen.run_mixed(
        None if plan is None else (ms.query_scheduler.submit, plan.requests,
                                   plan.due, plan.keep, readers["drain_s"]),
        None if writer is None else (
            lambda t: deploy.run_conversation(ms, t), writer.order),
        run.seconds, TraceAnnotation)
    _stop_trace(run, trace_dir)
    run.compiles_in_window = compiles.count - mark
    run.telemetry = deploy.telemetry_copy(ms)     # before the read-back's reads
    device = deploy.device_info(run.cell["chips"])
    state = None
    if plan is not None and plan.boost is not None:
        state = {"columns": deploy.read_state(ms),
                 "window": (opened, deploy.index_clock(ms)),
                 "boost": boost_constants(cfg, readers, deploy.SALIENCE)}
    gc.unfreeze()
    _same_capacity(ms, capacity)

    if samples is not None:
        ok = _note_reads(run, samples)
        if run.want_detail:
            run.detail = _serve_detail(run, samples, ok, gc_before)
    done, readback = [], []
    if log is not None:
        done = writer.note(run, log)
        at = np.asarray(log.starts) - log.t0
        run.conversation_spans = np.stack(
            [at, at + np.nan_to_num(np.asarray(log.seconds))], axis=1)
        readback = writer.read_back(deploy, ms, done)
    if run.want_detail:
        gc.callbacks.remove(pauses.hook)
        run.detail = dict(run.detail or {}, gc_pauses_ms=pauses.longest(),
                          **_mixed_detail(run))
    swallowed = deploy.counters(ms)
    arena_rows = int(ms.index.state.salience.shape[0])
    ms.close()
    del ms
    if plan is not None:
        del plan.requests
    gc.collect()

    cmp.swallowed = sum(swallowed.values())
    cmp.unanswered = run.failed
    if samples is not None:
        check_serving(reference, cmp, cfg, plan, samples, seed, starts, 0,
                      control, state)
        say(f"arena {arena_rows} rows x {cfg['dim']} {cfg['dtype']}, "
            f"{stock['rows']} of them the stock's; {len(samples.ok)} requests, "
            f"{int((~samples.ok).sum())} failed")
    if log is not None:
        writer.compare(reference, cmp, readback, control)
        _say_writes(run, writer, done, readback, cmp, swallowed)
    return finish(run, cmp, device)


def _mixed_detail(run: Run) -> dict:
    """Study aid: where the writer's conversations lay in the window."""
    return {"conversation_spans_s": run.conversation_spans.round(5).tolist()}


LOOPS: Dict[str, Callable] = {
    "open": _serve_run, "closed": _serve_run,
    "conversations": _ingest_run, "mixed": _mixed_run}


def finish(run: Run, cmp, device: dict) -> dict:
    """The result line's object: end-to-end metrics without a trace,
    per-layer metrics with one; the numbers compared come last."""
    kind = "per_layer" if run.traced else "end_to_end"
    metrics = {}
    for m in metrics_of(run.cell, kind, run.root):
        value = reader(m["name"], run.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": bool(cmp.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if run.traced and run.trace is not None and run.trace["devices"]:
        busy = tracing.device_busy(run.trace)
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = busy["window_s"]
        out["breakdown"] = {
            "device_ops": tracing.top_ops(run.trace),
            "idle_gaps": tracing.idle_gaps_by_span(run.trace)}
    if run.detail is not None:
        out["detail"] = run.detail
    out["compared"] = cmp.numbers()
    if cmp.first_fault:
        say(f"first fault: {cmp.first_fault}")
    return out
