"""Serving telemetry (ISSUE 6; tier-1 smoke, CPU, tiny arenas).

The observability layer must be free-riding by construction: host spans are
perf_counter bookkeeping around dispatches that already happen, and the
device-side counters are an int32 tail on the packed readback that already
exists. These tests pin the three claims that make it trustworthy:

- span accounting composes with coalescing — N requests flushed as ONE
  mega-batch yield N queue-wait samples and exactly 1 dispatch sample;
- the device counters decoded from the readback tail match host-computed
  truth on gate-hit / gate-miss / multi-tenant fixtures;
- telemetry adds ZERO device dispatches (the jit counter still reads 1 per
  chat turn, and cached turns stay zero-RTT) while visibly recording;

plus the exposure surfaces: the dashboard's Prometheus ``/metrics`` and
JSON ``/api/metrics`` must agree with ``MemorySystem.metrics_summary()``,
and fused-path counters must survive a checkpoint round trip.
"""

import json
import tempfile
import threading
import urllib.request

import numpy as np
import pytest

from lazzaro_tpu.core.index import MemoryIndex
from lazzaro_tpu.serve import QueryScheduler, RetrievalRequest
from lazzaro_tpu.utils.telemetry import Telemetry, split_key
from tests.test_fused_retrieval import (_count_dispatches, _ingest,
                                        _system)

D = 16


# ------------------------------------------------------------ registry unit
def test_registry_labels_snapshot_prometheus():
    tel = Telemetry()
    tel.bump("serve.dispatches", labels={"mode": "exact"})
    tel.bump("serve.dispatches", 2, labels={"mode": "quant"})
    tel.record("serve.queue_wait_ms", 1.5, labels={"tenant": "a"})
    tel.record("serve.queue_wait_ms", 2.5, labels={"tenant": "a"})
    tel.gauge("serve.batch_occupancy", 0.75)

    assert tel.counter_total("serve.dispatches") == 3
    assert tel.timer_count("serve.queue_wait_ms") == 2
    assert sorted(tel.timer_values("serve.queue_wait_ms")) == [1.5, 2.5]

    snap = tel.snapshot()
    key = 'serve.queue_wait_ms{tenant="a"}'
    assert snap["timers"][key]["count"] == 2
    assert snap["timers"][key]["max_ms"] == 2.5
    assert snap["counters"]['serve.dispatches{mode="quant"}'] == 2
    assert snap["gauges"]["serve.batch_occupancy"] == 0.75
    json.dumps(snap)                       # the bench-artifact contract

    text = tel.prometheus()
    assert '# TYPE lazzaro_serve_dispatches_total counter' in text
    assert 'lazzaro_serve_dispatches_total{mode="exact"} 1' in text
    assert 'lazzaro_serve_dispatches_total{mode="quant"} 2' in text
    assert 'lazzaro_serve_queue_wait_ms_count{tenant="a"} 2' in text
    assert 'lazzaro_serve_batch_occupancy 0.75' in text

    name, label = split_key(key)
    assert (name, label) == ("serve.queue_wait_ms", '{tenant="a"}')


def test_label_cardinality_clamp():
    """A tenant explosion folds into one '~other' series instead of
    growing the registry without bound."""
    from lazzaro_tpu.utils import telemetry as T
    tel = Telemetry()
    for i in range(T.MAX_LABEL_SETS + 50):
        tel.bump("serve.requests", labels={"tenant": f"u{i}"})
    series = [k for k in tel.counters if k.startswith("serve.requests")]
    assert len(series) == T.MAX_LABEL_SETS + 1
    assert tel.counters['serve.requests{tenant="~other"}'] == 50
    assert tel.counter_total("serve.requests") == T.MAX_LABEL_SETS + 50


def test_disabled_registry_is_a_noop():
    tel = Telemetry(enabled=False)
    tel.bump("c")
    tel.record("t", 1.0)
    tel.gauge("g", 2.0)
    assert tel.snapshot() == {"timers": {}, "counters": {}, "gauges": {}}


# ----------------------------------------------------- fixtures (tiny arena)
def _index(tel=None, **kw):
    idx = MemoryIndex(dim=D, capacity=64, edge_capacity=255,
                      telemetry=tel if tel is not None else Telemetry(),
                      **kw)
    return idx


def _basis(i):
    v = np.zeros(D, np.float32)
    v[i] = 1.0
    return v


def _fill_two_tenants(idx):
    """Tenant 'a': rows a0..a7 on basis vectors + one super row on e0;
    tenant 'b': rows b0..b7 + one super row on e15 (orthogonal to every
    test query, so its gate can never fire). Edges a0—a1 and a0—a2."""
    for t, base, sup_axis in (("a", 0, 0), ("b", 8, 15)):
        emb = np.stack([_basis((base + i) % D) for i in range(8)])
        idx.add([f"{t}{i}" for i in range(8)], emb, [0.5] * 8, [0.0] * 8,
                ["semantic"] * 8, ["default"] * 8, t)
        idx.add([f"s{t}"], _basis(sup_axis)[None, :], [0.9], [0.0],
                ["semantic"], ["default"], t, is_super=[True])
    idx.add_edges([("a0", "a1", 0.7), ("a0", "a2", 0.7)], "a")
    return idx


_KW = dict(cap_take=2, max_nbr=4, super_gate=0.4, acc_boost=0.05,
           nbr_boost=0.02)


# ------------------------------------------- scheduler span accounting
def test_coalesced_batch_yields_n_queue_waits_one_dispatch():
    """The ISSUE 6 accounting contract: N requests coalesced into ONE
    mega-batch must yield N queue-wait samples (per-tenant labelled) and
    exactly 1 dispatch sample / 1 dispatch counter bump."""
    tel = Telemetry()
    idx = _fill_two_tenants(_index(tel))
    release = threading.Event()
    in_first = threading.Event()
    batches = []

    def executor(reqs):
        batches.append(len(reqs))
        if len(batches) == 1:
            in_first.set()
            release.wait(timeout=10)
        return idx.search_fused_requests(reqs, **_KW)

    s = QueryScheduler(executor, max_batch=64, telemetry=tel)
    try:
        first = s.submit(RetrievalRequest(query=_basis(0), tenant="a"))
        assert in_first.wait(timeout=10)   # worker is now blocked mid-flush
        rest = s.submit_many(
            [RetrievalRequest(query=_basis(i % 8), tenant="a")
             for i in range(5)]
            + [RetrievalRequest(query=_basis(8 + i % 8), tenant="b")
               for i in range(5)])
        release.set()
        first.result(timeout=10)
        for f in rest:
            f.result(timeout=10)
    finally:
        s.close()

    assert batches == [1, 10]              # the 10 coalesced into ONE flush
    # 11 requests total → 11 queue-wait samples, split by tenant label
    assert tel.timer_count("serve.queue_wait_ms") == 11
    snap = tel.snapshot()
    assert snap["timers"]['serve.queue_wait_ms{tenant="a"}']["count"] == 6
    assert snap["timers"]['serve.queue_wait_ms{tenant="b"}']["count"] == 5
    # 2 flushes → 2 dispatch samples / bumps (1 for the coalesced batch)
    assert tel.counter_total("serve.dispatches") == 2
    assert tel.timer_count("serve.dispatch_ms") == 2
    assert tel.counter_total("serve.batches") == 2
    assert sorted(tel.timer_values("serve.batch_requests")) == [1, 10]
    assert tel.counter_total("serve.requests") == 11
    # pad-inflation accounting: 11 live requests, pow2-padded slots
    assert tel.counter_total("serve.live_requests") == 11
    assert tel.counter_total("serve.padded_slots") == 1 + 16


# ------------------------------------------------- device-counter parity
@pytest.mark.parametrize("mode", ["exact", "quant", "ivf"])
def test_device_counters_match_host_truth(mode):
    """Gate hit / gate miss / boost-row counts decoded from the readback
    tail must equal host-computed truth on a multi-tenant fixture, on
    every single-chip fused serving path."""
    tel = Telemetry()
    idx = _fill_two_tenants(_index(
        tel, int8_serving=(mode == "quant"),
        ivf_nprobe=4 if mode == "ivf" else 0))
    if mode == "ivf":
        idx._IVF_MIN_ROWS = 1
        assert idx.ivf_maintenance()
    qa = 0.8 * _basis(0) + 0.6 * _basis(1)   # top-2 = a0, a1; gate sa=0.8
    reqs = [
        # gate HIT for tenant a (sa is e0): fast path, boosts suppressed
        RetrievalRequest(query=qa, tenant="a", k=4, gate_enabled=True,
                         boost=True),
        # gate MISS for tenant b (sb is e15, orthogonal): boosts applied
        RetrievalRequest(query=_basis(8), tenant="b", k=4,
                         gate_enabled=True, boost=True),
        # no gate, boosts applied: acc = top-2 {a0, a1}; a0's neighbors
        # {a1, a2} minus the retrieved set → ONE neighbor boost row (a2)
        RetrievalRequest(query=qa, tenant="a", k=4, boost=True),
        # pure read: contributes nothing to any boost counter
        RetrievalRequest(query=_basis(9), tenant="b", k=4),
    ]
    res = idx.search_fused_requests(reqs, **_KW)

    assert res[0].fast and not res[1].fast and not res[2].fast
    assert tel.counter_total("device.gate_hit") == 1
    assert tel.counter_total("device.gate_miss") == 1
    # host truth for access-boost rows: every valid boosted non-fast query
    # scatters min(cap_take, live) rows — queries 1 and 2, 2 rows each
    assert tel.counter_total("device.boost_rows") == 4
    assert tel.counter_total("device.nbr_boost_rows") == 1
    # 8 live rows per tenant ≥ k=4 → no shortfall anywhere
    assert tel.counter_total("device.topk_shortfall") == 0
    assert tel.counter_total("device.dedup_hits") == 0
    assert tel.counter_total(f"serve.dispatches") == 1
    snap = tel.snapshot()
    assert snap["counters"][f'serve.dispatches{{mode="{mode}"}}'] == 1
    assert tel.timer_count("serve.dispatch_ms") == 1
    assert tel.timer_count("serve.decode_ms") == 1


def test_topk_shortfall_counts_against_requested_k():
    """A request asking for more rows than its tenant owns reports the gap
    through the device counter — against ITS k, not the padded bucket."""
    tel = Telemetry()
    idx = _fill_two_tenants(_index(tel))
    res = idx.search_fused_requests(
        [RetrievalRequest(query=_basis(0), tenant="a", k=16),
         RetrievalRequest(query=_basis(8), tenant="b", k=4)], **_KW)
    assert len(res[0].ids) == 8            # tenant a owns 8 non-super rows
    assert len(res[1].ids) == 4
    assert tel.counter_total("device.topk_shortfall") == 16 - 8


def test_ingest_counters_ride_the_readback():
    tel = Telemetry()
    idx = _index(tel)
    ids = [f"n{i}" for i in range(6)]
    # one tight cluster: every pairwise similarity clears the 0.5 link
    # gate, so the device-side accepted-link counter must see real work
    rng = np.random.default_rng(7)
    emb = (_basis(0)[None, :]
           + 0.05 * rng.standard_normal((6, D))).astype(np.float32)
    idx.add([f"seed{i}" for i in range(4)], emb[:4], [0.5] * 4, [0.0] * 4,
            ["semantic"] * 4, ["default"] * 4, "u")
    _, _, created = idx.ingest_batch(
        ids, emb, [0.5] * 6, [0.0] * 6, ["semantic"] * 6,
        ["default"] * 6, "u")
    n_created = sum(len(v) for v in created.values())
    assert n_created >= 1
    assert tel.counter_total("ingest.dispatches") == 1
    # device truth ≥ host-registered edges (the device count includes
    # accepted links whose (src, tgt) key the host already knew)
    assert tel.counter_total("ingest.links_accepted") >= n_created
    assert tel.counter_total("ingest.pool_slots_used") >= 1


def test_sharded_serve_reports_counters_and_spans():
    """The pod path (ONE distributed dispatch) reports the same device
    counters and host spans as the single-chip paths, and its dispatch
    count reaches the registry (satellite: it used to be visible only by
    wrapping the ``_dispatch`` hook)."""
    import jax

    from lazzaro_tpu.parallel.index import ShardedMemoryIndex
    from lazzaro_tpu.parallel.mesh import make_mesh

    tel = Telemetry()
    mesh = make_mesh(("data",), (2,), devices=jax.devices()[:2])
    idx = ShardedMemoryIndex(mesh, dim=D, capacity=127, dtype=np.float32,
                             telemetry=tel)
    idx.add(["s0"], _basis(0).reshape(1, -1), "u", supers=[True])
    idx.add([f"m{i}" for i in range(6)],
            np.stack([_basis(1 + i) for i in range(6)]), "u")
    res = idx.serve_requests([
        RetrievalRequest(query=_basis(0), tenant="u", k=4,
                         gate_enabled=True, boost=True),
        RetrievalRequest(query=_basis(3), tenant="u", k=4,
                         gate_enabled=True, boost=True)])
    assert res[0].fast and not res[1].fast
    assert idx.dispatch_count == 1
    assert tel.counter_total("serve.dispatches") == 1
    assert tel.timer_count("serve.dispatch_ms") == 1
    assert tel.timer_count("serve.decode_ms") == 1
    assert tel.counter_total("device.gate_hit") == 1
    assert tel.counter_total("device.gate_miss") == 1
    # only the gate-miss query boosts: min(cap_take=5, live=6) rows
    assert tel.counter_total("device.boost_rows") == 5
    assert tel.counter_total("device.topk_shortfall") == 0
    assert tel.counter_total("serve.live_requests") == 2


# ---------------------------------------------------- zero extra dispatches
def test_telemetry_adds_zero_dispatches(monkeypatch):
    """With telemetry ON (the default) and visibly recording, a chat turn
    still costs exactly ONE fused dispatch and a query-cache hit stays
    zero-RTT — observability is bytes on an existing readback, never an
    extra device program."""
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        assert ms.telemetry.enabled
        ms.start_conversation()
        calls = _count_dispatches(monkeypatch)
        ms.chat("fact 7 body")
        assert calls["search_fused_ragged"] == 1
        assert sum(calls.values()) == 1
        # the turn actually landed in the registry (spans + device tail)
        assert ms.telemetry.counter_total("serve.dispatches") == 1
        assert ms.telemetry.timer_count("serve.dispatch_ms") == 1
        assert ms.telemetry.timer_count("serve.queue_wait_ms") >= 1
        ms.chat("fact 7 body")             # query-cache hit
        assert sum(calls.values()) == 1    # STILL one: cached turn = 0
        assert ms.telemetry.counter_total("serve.dispatches") == 1
        ms.close()


# ------------------------------------------------------- exposure surfaces
def test_metrics_endpoint_matches_summary():
    """Acceptance: the dashboard's ``/metrics`` Prometheus gauges and the
    ``/api/metrics`` JSON must agree with MemorySystem.metrics_summary()."""
    from lazzaro_tpu.dashboard.api import make_server

    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.start_conversation()
        ms.chat("fact 7 body")
        ms.search_memories("fact 3 body")
        server = make_server(ms, "127.0.0.1", 0)
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/api/metrics") as r:
                api = json.loads(r.read())
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            summary = ms.metrics_summary()
        finally:
            server.shutdown()
            t.join(timeout=10)
            ms.close()

        # JSON surface == metrics_summary (same registry, same derivation)
        assert api["serve_dispatches"] == summary["serve_dispatches"]
        assert api["pad_waste_fraction"] == summary["pad_waste_fraction"]
        assert api["telemetry"]["counters"] == \
            summary["telemetry"]["counters"]

        # Prometheus surface: per-label counter samples sum to the
        # summary's totals, and the derived headline gauges match
        prom = {}
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            key, val = line.rsplit(" ", 1)
            prom[key] = float(val)
        dispatched = sum(v for k, v in prom.items()
                         if k.startswith("lazzaro_serve_dispatches_total"))
        assert dispatched == summary["serve_dispatches"] > 0
        assert prom["lazzaro_pad_waste_fraction"] == \
            pytest.approx(summary["pad_waste_fraction"])
        assert prom["lazzaro_queue_wait_ms_p50"] == \
            pytest.approx(summary["queue_wait_ms_p50"])
        # the device-counter tail reached the API (the chat turn boosts
        # its retrieved rows, counted ON DEVICE in the readback tail)
        assert summary["telemetry"]["counters"]["device.boost_rows"] >= 1


def test_tier_gauges_on_every_surface():
    """ISSUE 8 satellite: the tier gauges (tier.hot_rows / tier.cold_rows
    / tier.cold_hit_rate / tier.pump_chunk_ms) land in the registry and
    surface through metrics_summary(), the Prometheus ``/metrics`` text
    AND the JSON ``/api/metrics`` — the endpoint-parity contract extended
    to the tiered-memory subsystem."""
    from lazzaro_tpu.dashboard.api import make_server

    with tempfile.TemporaryDirectory() as tmp:
        ms = _system(tmp)
        ms.config.tier_hot_budget_rows = 8
        tmgr = ms.index.enable_tiering(8, hysteresis_s=0.0)
        _ingest(ms, convs=3)
        rows = [r for r in ms.index.row_to_id][:6]
        tmgr.demote_rows(rows)
        ms.chat("conv 1")                 # serving feeds cold_hit_rate
        summary = ms.metrics_summary()
        assert summary["tier"]["cold_rows"] == tmgr.cold_count > 0
        assert summary["tier"]["hot_rows"] == tmgr.hot_rows
        gauges = summary["telemetry"]["gauges"]
        for name in ("tier.hot_rows", "tier.cold_rows",
                     "tier.cold_hit_rate", "tier.pump_chunk_ms"):
            assert name in gauges, name
        assert gauges["tier.cold_rows"] == tmgr.cold_count

        server = make_server(ms, "127.0.0.1", 0)
        port = server.server_address[1]
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/api/metrics") as r:
                api = json.loads(r.read())
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics") as r:
                text = r.read().decode()
        finally:
            server.shutdown()
            t.join(timeout=10)
            ms.close()
        assert api["tier"]["cold_rows"] == summary["tier"]["cold_rows"]
        assert api["telemetry"]["gauges"]["tier.cold_rows"] == \
            gauges["tier.cold_rows"]
        assert f"lazzaro_tier_cold_rows {float(tmgr.cold_count)}" in text
        assert "lazzaro_tier_hot_rows" in text
        assert "lazzaro_tier_cold_hit_rate" in text


def test_metrics_summary_shape():
    with tempfile.TemporaryDirectory() as tmp:
        ms = _ingest(_system(tmp))
        ms.search_memories("fact 3 body")
        s = ms.metrics_summary()
        json.dumps(s)                      # JSON-able end to end
        assert 0.0 <= s["pad_waste_fraction"] < 1.0
        assert s["serve_dispatches"] >= 1
        assert s["ingest_dispatches"] >= 1
        assert s["scheduler"]["requests_served"] >= 1
        assert "device.gate_hit" not in s["telemetry"]["timers"]
        ms.close()


def test_counters_survive_checkpoint_roundtrip():
    """Satellite: ``link_pool_overflows`` used to silently reset on
    checkpoint load; it must survive the round trip now."""
    from lazzaro_tpu.core.checkpoint import load_index, save_index

    idx = _fill_two_tenants(_index())
    idx.link_pool_overflows = 3
    with tempfile.TemporaryDirectory() as tmp:
        save_index(idx, tmp)
        back = load_index(tmp)
    assert back.link_pool_overflows == 3
