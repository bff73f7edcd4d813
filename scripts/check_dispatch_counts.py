#!/usr/bin/env python3
"""Guard: fail when a bench artifact records a fused-serving regression.

The fused serving acceptance bar (ISSUE 2/3/4/5) is ONE device dispatch per
coalesced retrieval batch — on the mesh path ONE *distributed* dispatch —
and for the approximate coarse stages (int8, IVF) a recall floor the
artifact itself records. Bench stages that measure a fused path record a
MEASURED ``dispatches_per_turn`` in their JSON artifacts (bench.py
``bench_fused_quant`` / ``bench_fused_ivf`` wrap the jit entry points,
``bench_fused_sharded`` wraps the pod index's ``_dispatch`` hook), and
recall-bearing stages record ``recall_at_10`` next to their
``recall_floor``. This script walks every ``bench_artifacts/*.json`` (or
the paths passed as arguments) and exits nonzero if:

  - any ``dispatches_per_turn`` != 1 (a refactor quietly split a fused
    program back into multiple dispatches — single-chip or distributed),
    UNLESS the same dict records a matching ``planned_dispatches_per_
    turn`` (ISSUE 11: the HBM planner may split an over-budget turn into
    planned sub-dispatches — a PLANNED count is accepted when measured
    == planned, a silent one never is),
  - any dict carrying both keys has ``recall_at_10`` < ``recall_floor``
    (a coarse-stage change quietly traded recall for throughput),
  - any dict carrying both keys has ``fused_vs_classic_speedup`` <
    ``speedup_floor`` (the fused path quietly lost its throughput edge
    over the semantics-equivalent classic sequence), or
  - a SHARDED artifact (any dict carrying a ``mesh`` sub-dict) does NOT
    record a measured ``dispatches_per_turn`` at all — a pod-path stage
    that stops measuring its dispatch count must fail loudly, not pass
    vacuously,
  - (ISSUE 6) a post-observability artifact measuring a fused path (any
    dict carrying ``dispatches_per_turn``) has NO ``telemetry`` block —
    every fused bench stage embeds ``bench._telemetry_block`` (pad-waste
    fraction, batch occupancy, queue-wait p50/p95, peak-HBM gauges) so
    the padding and HBM-budget directions always have a measured
    baseline; pre-ISSUE-6 artifacts (``pr2_``…``pr5_`` prefixes) are
    grandfathered,
  - (ISSUE 6) a ``telemetry`` block is malformed — missing the required
    keys — or its registry snapshot PROVES padding waste happened
    (``serve.padded_slots`` > ``serve.live_requests``) while the block's
    ``pad_waste_fraction`` fails to record it: measured waste that the
    artifact under-reports is the one observability regression this
    whole layer exists to prevent,
  - (ISSUE 12) an ONLINE-IVF artifact (any dict with ``"ivf_online":
    true``) does not record a measured ``dispatches_per_conversation``
    (gated == 1 by the generic rule — in-dispatch IVF maintenance must
    never grow the write path past ONE dispatch), lacks a
    ``recall_at_10``/``recall_floor`` pair (online tables must match the
    offline rebuild they replaced), lacks an
    ``ingest_overhead_fraction``, or records an
    ``assignment_staleness_fraction`` that is missing or above its
    recorded ``assignment_staleness_max`` (default 0.02 — mini-batch
    centroid drift stranding members is the failure mode online IVF must
    bound),
  - (ISSUE 9) a SHARDED-INGEST artifact (any dict with
    ``"ingest_sharded": true``) does not record a measured
    ``dispatches_per_conversation`` (gated to == 1 like
    ``dispatches_per_turn`` — one coalesced mega-batch must cost ONE
    distributed dispatch on the fused pod write path), or lacks a
    ``write_scaling``/``write_scaling_floor`` pair, or records
    ``write_scaling`` below its floor (the sharded write path must never
    regress below the single-chip fused path; real >1 scaling is the
    TPU-window item — on a shared-socket CPU mesh the chips share
    cores). ``dispatches_per_conversation`` values anywhere are gated to
    == 1 exactly like ``dispatches_per_turn``, and a ``mesh``-carrying
    artifact satisfies its measured-count requirement with either key,
    not record ``cold_hit_rate`` and ``hot_fraction``, or lacks a
    ``recall_at_10``/``recall_floor`` pair (the generic recall gate then
    enforces the floor — tiering must never silently trade recall for
    capacity), or records a missing/over-budget
    ``cold_hit_dispatches_per_turn`` (> 2: a cold hit is allowed the ONE
    bounded finish dispatch on top of the coarse scan, never a cascade;
    the hot-only probe's ``dispatches_per_turn`` stays pinned to 1 by
    the generic dispatch gate). Earlier artifacts never carry the flag,
    so they are grandfathered by construction,
  - (ISSUE 17) a PAGED-ARENA artifact (any dict with ``"paged": true``)
    does not record a measured ``dispatches_per_turn`` (gated == 1 by
    the generic rule — the free-list pop/push and the row_map gather
    ride INSIDE the fused programs, never as sibling dispatches), lacks
    a ``paged_qps_ratio``/``paged_qps_floor`` pair or records the ratio
    below its floor (the indirection gather must stay within 10% of the
    dense scan), or records a missing/nonzero ``mirror_mismatches``
    (the host free-list mirror must agree with the device readback tail
    on every pop — a drifted mirror silently corrupts slot reuse),
  - (ISSUE 16) a FUSED-PQ artifact (any dict with ``"pq_fused": true``)
    does not record a measured ``dispatches_per_turn`` (gated == 1 by
    the generic rule — the m-byte ADC member scan, exact rescore, and
    the gate/CSR/boost tail must stay ONE dispatch), lacks a
    ``recall_at_10``/``recall_floor`` pair vs the classic
    ``ivf_pq_search`` path on the same fixture, or does not record
    ``bytes_per_row`` (the resident-footprint headline — PQ's whole
    reason to exist — must stay measured, and below the int8 shadow's
    when both are present as ``bytes_per_row``/``int8_bytes_per_row``),

  - (ISSUE 19) a LIFECYCLE artifact (any dict with ``"lifecycle": true``)
    does not record a measured ``dispatches_per_sweep`` (gated == 1 by
    the generic rule — decay + weak-edge prune + archive verdicts for
    ALL tenants must stay ONE donated all-tenant dispatch, never the
    classic 3-dispatches-per-tenant host loop), does not record
    ``"bit_parity": true`` (the fused sweep must stay bit-identical to
    the classic decay/prune/evict host loop on the churn fixture —
    approximate maintenance silently corrupts every downstream recall
    number), lacks a ``serve_p99_ratio``/``serve_p99_bound`` pair or
    records the ratio above its bound (lifecycle ticks run UNDER live
    serving — blowing the serving tail is exactly the host-stall
    failure mode this sweep exists to kill), or lacks a
    ``host_stall_speedup``/``host_stall_floor`` pair or records the
    speedup below its floor (the one-dispatch sweep quietly lost its
    wall-clock edge over the per-tenant loop),

  - (ISSUE 18) a REPLICA artifact (any dict with ``"replica": true``)
    does not record a measured ``dispatches_per_turn`` (gated == 1 by
    the generic rule — a routed turn must cost ONE group-local dispatch
    fleet-wide, no stray dispatch on any other group), lacks a
    ``qps_scaling``/``qps_scaling_floor`` pair or records the scaling
    below its floor (adding replica groups must keep buying aggregate
    QPS — the whole reason the placement layer exists), lacks a
    ``recall_at_10``/``recall_floor`` pair (the generic recall gate then
    enforces it — group-local serving must stay exact), records a
    missing/over-bound ``replica_staleness_s`` vs its
    ``staleness_bound_s`` (the journal fan-out's bounded-staleness
    window is a measured promise, not an assumption), or records a
    crash-replay cell with ``lost_facts`` or ``doubled_facts`` != 0
    (journal-subscriber recovery must converge exactly),

  - (ISSUE 20) a SEMANTIC-CACHE artifact (any dict with
    ``"semantic_cache": true``) does not record a measured
    ``dispatches_per_turn`` (gated == 1 by the generic rule — the
    similarity probe, the hit early-out, and the ring writeback all
    ride INSIDE the one fused dispatch, never as sibling dispatches),
    lacks a ``semantic_hit_rate``/``hit_rate_floor`` pair or records
    the rate below its floor (the Zipf repeated-intent workload stopped
    hitting — the ring geometry or the probe eligibility mask
    regressed), records a missing/nonzero ``stale_hits`` (under
    ingest/delete churn a cached window served results a fresh scan
    would not — the ONE correctness failure the invalidation reverse
    index exists to prevent), does not record ``"miss_parity": true``
    (a cold probe must be a bit-identical pass-through: ids AND scores
    of a never-seen population must match the cache-off twin), lacks a
    ``recall_at_10``/``recall_floor`` pair (the generic recall gate
    then enforces it — a hit-served window must BE the exact answer),
    or records ``semantic_vs_off_speedup`` below its ``speedup_floor``
    (hits stopped buying back their scan blocks),

so any of these regressions turns red in CI instead of shipping.

Usage:
    python scripts/check_dispatch_counts.py [artifact.json ...]
"""

from __future__ import annotations

import glob
import json
import os
import sys

# Artifacts from before the observability layer existed: exempt from the
# telemetry-block requirement (their numbers are still gate-checked).
_PRE_TELEMETRY_PREFIXES = ("pr2_", "pr3_", "pr4_", "pr5_")

_TELEMETRY_KEYS = ("pad_waste_fraction", "queue_wait_ms_p50",
                   "queue_wait_ms_p95", "peak_hbm_bytes")


_DISPATCH_KEYS = ("dispatches_per_turn", "dispatches_per_conversation",
                  "dispatches_per_sweep")


def _walk(obj, path, hits, recalls, speedups, meshes, tel_blocks,
          tiereds, ingests, online_ivfs, pq_fuseds, pageds, replicas,
          lifecycles, semantics):
    if isinstance(obj, dict):
        if "recall_at_10" in obj and "recall_floor" in obj:
            recalls.append((path, obj["recall_at_10"], obj["recall_floor"]))
        if "fused_vs_classic_speedup" in obj and "speedup_floor" in obj:
            speedups.append((path, obj["fused_vs_classic_speedup"],
                             obj["speedup_floor"]))
        if isinstance(obj.get("mesh"), dict):
            meshes.append((path, any(k in obj for k in _DISPATCH_KEYS)))
        if any(k in obj for k in _DISPATCH_KEYS) or "telemetry" in obj:
            tel_blocks.append((path,
                               any(k in obj for k in _DISPATCH_KEYS),
                               obj.get("telemetry")))
        if obj.get("tiered") is True:
            tiereds.append((path, obj))
        if obj.get("ingest_sharded") is True:
            ingests.append((path, obj))
        if obj.get("ivf_online") is True:
            online_ivfs.append((path, obj))
        if obj.get("pq_fused") is True:
            pq_fuseds.append((path, obj))
        if obj.get("paged") is True:
            pageds.append((path, obj))
        if obj.get("replica") is True:
            replicas.append((path, obj))
        if obj.get("lifecycle") is True:
            lifecycles.append((path, obj))
        if obj.get("semantic_cache") is True:
            semantics.append((path, obj))
        for k, v in obj.items():
            here = f"{path}.{k}"
            if k in _DISPATCH_KEYS:
                # ISSUE 11: a planner-split turn records its PLANNED
                # count next to the measured one — accepted iff equal.
                hits.append((here, v, obj.get("planned_" + k)))
            else:
                _walk(v, here, hits, recalls, speedups, meshes, tel_blocks,
                      tiereds, ingests, online_ivfs, pq_fuseds,
                      pageds, replicas, lifecycles, semantics)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _walk(v, f"{path}[{i}]", hits, recalls, speedups, meshes,
                  tel_blocks, tiereds, ingests, online_ivfs,
                  pq_fuseds, pageds, replicas, lifecycles, semantics)


def _check_telemetry(loc, measured_fused, block, grandfathered, bad):
    """The ISSUE 6 observability gate on one artifact dict."""
    if block is None:
        if measured_fused and not grandfathered:
            bad.append((loc, "fused-path artifact (has dispatches_per_turn)"
                             " records no 'telemetry' block"))
        return
    if not isinstance(block, dict):
        bad.append((loc, f"'telemetry' is {type(block).__name__}, "
                         f"expected a dict"))
        return
    for key in _TELEMETRY_KEYS:
        if key not in block:
            bad.append((loc, f"telemetry block missing '{key}'"))
    counters = (block.get("snapshot") or {}).get("counters") or {}
    live = sum(v for k, v in counters.items()
               if k.split("{")[0] == "serve.live_requests")
    padded = sum(v for k, v in counters.items()
                 if k.split("{")[0] == "serve.padded_slots")
    if padded > live > 0:
        truth = 1.0 - live / padded
        got = block.get("pad_waste_fraction")
        try:
            ok = abs(float(got) - truth) < 1e-3
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"padding waste happened (padded_slots="
                             f"{padded} > live_requests={live}, waste="
                             f"{truth:.4f}) but pad_waste_fraction "
                             f"records {got!r}"))


def _check_online_ivf(loc, obj, bad):
    """The ISSUE 12 online-IVF gate on one ``"ivf_online": true`` dict."""
    if "dispatches_per_conversation" not in obj:
        bad.append((loc, "online-ivf artifact must record a measured "
                         "'dispatches_per_conversation'"))
    if "recall_at_10" not in obj or "recall_floor" not in obj:
        bad.append((loc, "online-ivf artifact must record a recall_at_10/"
                         "recall_floor pair vs the offline rebuild"))
    if "ingest_overhead_fraction" not in obj:
        bad.append((loc, "online-ivf artifact must record "
                         "'ingest_overhead_fraction' (in-dispatch "
                         "maintenance cost vs maintenance-free ingest)"))
    stale = obj.get("assignment_staleness_fraction")
    ceiling = obj.get("assignment_staleness_max", 0.02)
    try:
        ok = float(stale) <= float(ceiling)
    except (TypeError, ValueError):
        ok = False
    if not ok:
        bad.append((loc, f"assignment_staleness_fraction == {stale!r} "
                         f"(must record a measured value <= {ceiling!r} — "
                         f"mini-batch centroid drift is stranding "
                         f"members)"))


def _check_pq_fused(loc, obj, bad):
    """The ISSUE 16 fused-PQ gate on one ``"pq_fused": true`` dict."""
    if "dispatches_per_turn" not in obj:
        bad.append((loc, "fused-pq artifact must record a measured "
                         "'dispatches_per_turn'"))
    if "recall_at_10" not in obj or "recall_floor" not in obj:
        bad.append((loc, "fused-pq artifact must record a recall_at_10/"
                         "recall_floor pair vs the classic ivf_pq_search "
                         "path"))
    bpr = obj.get("bytes_per_row")
    try:
        bpr_ok = float(bpr) > 0
    except (TypeError, ValueError):
        bpr_ok = False
    if not bpr_ok:
        bad.append((loc, f"fused-pq artifact records bytes_per_row == "
                         f"{bpr!r} (must be a measured positive number — "
                         f"the resident-footprint headline)"))
    int8_bpr = obj.get("int8_bytes_per_row")
    if bpr_ok and int8_bpr is not None:
        try:
            smaller = float(bpr) < float(int8_bpr)
        except (TypeError, ValueError):
            smaller = False
        if not smaller:
            bad.append((loc, f"fused-pq bytes_per_row {bpr!r} is not "
                             f"below the int8 shadow's {int8_bpr!r} — "
                             f"the PQ footprint advantage regressed"))


def _check_paged(loc, obj, bad):
    """The ISSUE 17 paged-arena gate on one ``"paged": true`` dict."""
    if "dispatches_per_turn" not in obj:
        bad.append((loc, "paged-arena artifact must record a measured "
                         "'dispatches_per_turn' (page maintenance must "
                         "ride inside the fused program)"))
    ratio = obj.get("paged_qps_ratio")
    floor = obj.get("paged_qps_floor")
    if ratio is None or floor is None:
        bad.append((loc, "paged-arena artifact must record both "
                         "'paged_qps_ratio' and 'paged_qps_floor'"))
    else:
        try:
            ok = float(ratio) >= float(floor)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"paged_qps_ratio == {ratio!r} < "
                             f"paged_qps_floor {floor!r} (the row_map "
                             f"gather cost regressed past the floor)"))
    mism = obj.get("mirror_mismatches")
    if mism != 0:
        bad.append((loc, f"mirror_mismatches == {mism!r} (must record a "
                         f"measured 0 — the host free-list mirror drifted "
                         f"from the device page table)"))


def _check_replica(loc, obj, bad):
    """The ISSUE 18 replica-serving gate on one ``"replica": true``
    dict."""
    if "dispatches_per_turn" not in obj:
        bad.append((loc, "replica artifact must record a measured "
                         "'dispatches_per_turn' (one group-local dispatch "
                         "per routed turn, fleet-wide)"))
    if "recall_at_10" not in obj or "recall_floor" not in obj:
        bad.append((loc, "replica artifact must record a recall_at_10/"
                         "recall_floor pair"))
    for i, grp in enumerate(obj.get("per_group") or []):
        measured = grp.get("measured_dispatches_per_turn")
        if measured != 1.0:
            bad.append((f"{loc}.per_group[{i}]",
                        f"measured_dispatches_per_turn == {measured!r} "
                        f"(every group count must serve a routed turn in "
                        f"exactly ONE group-local dispatch)"))
    scaling = obj.get("qps_scaling")
    floor = obj.get("qps_scaling_floor")
    if scaling is None or floor is None:
        bad.append((loc, "replica artifact must record both 'qps_scaling' "
                         "and 'qps_scaling_floor'"))
    else:
        try:
            ok = float(scaling) >= float(floor)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"qps_scaling == {scaling!r} < "
                             f"qps_scaling_floor {floor!r} (adding replica "
                             f"groups stopped buying aggregate QPS)"))
    stale = obj.get("replica_staleness_s")
    bound = obj.get("staleness_bound_s", 5.0)
    try:
        stale_ok = float(stale) <= float(bound)
    except (TypeError, ValueError):
        stale_ok = False
    if not stale_ok:
        bad.append((loc, f"replica_staleness_s == {stale!r} (must record "
                         f"a measured value <= {bound!r} — the journal "
                         f"fan-out's bounded-staleness window broke)"))
    crash = obj.get("crash_replay")
    if not isinstance(crash, dict):
        bad.append((loc, "replica artifact must record a 'crash_replay' "
                         "cell (injected mid-replay crash + journal "
                         "catch-up)"))
    else:
        for key in ("lost_facts", "doubled_facts"):
            if crash.get(key) != 0:
                bad.append((loc, f"crash_replay.{key} == "
                                 f"{crash.get(key)!r} (must record a "
                                 f"measured 0 — journal-subscriber "
                                 f"recovery diverged)"))


def _check_lifecycle(loc, obj, bad):
    """The ISSUE 19 lifecycle-sweep gate on one ``"lifecycle": true``
    dict."""
    if "dispatches_per_sweep" not in obj:
        bad.append((loc, "lifecycle artifact must record a measured "
                         "'dispatches_per_sweep' (decay + prune + archive "
                         "verdicts for ALL tenants in ONE dispatch)"))
    if obj.get("bit_parity") is not True:
        bad.append((loc, f"bit_parity == {obj.get('bit_parity')!r} (the "
                         f"fused sweep must record a measured true — "
                         f"bit-identical to the classic decay/prune/evict "
                         f"host loop)"))
    ratio = obj.get("serve_p99_ratio")
    bound = obj.get("serve_p99_bound")
    if ratio is None or bound is None:
        bad.append((loc, "lifecycle artifact must record both "
                         "'serve_p99_ratio' and 'serve_p99_bound' "
                         "(serving tail under concurrent maintenance)"))
    else:
        try:
            ok = float(ratio) <= float(bound)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"serve_p99_ratio == {ratio!r} > "
                             f"serve_p99_bound {bound!r} (maintenance "
                             f"sweeps are blowing the live serving tail)"))
    speedup = obj.get("host_stall_speedup")
    floor = obj.get("host_stall_floor")
    if speedup is None or floor is None:
        bad.append((loc, "lifecycle artifact must record both "
                         "'host_stall_speedup' and 'host_stall_floor'"))
    else:
        try:
            ok = float(speedup) >= float(floor)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"host_stall_speedup == {speedup!r} < "
                             f"host_stall_floor {floor!r} (the one-"
                             f"dispatch sweep lost its edge over the "
                             f"per-tenant host loop)"))


def _check_semantic(loc, obj, bad):
    """The ISSUE 20 semantic-cache gate on one ``"semantic_cache": true``
    dict."""
    if "dispatches_per_turn" not in obj:
        bad.append((loc, "semantic-cache artifact must record a measured "
                         "'dispatches_per_turn' (probe + early-out + "
                         "writeback ride INSIDE the one fused dispatch)"))
    rate = obj.get("semantic_hit_rate")
    floor = obj.get("hit_rate_floor")
    if rate is None or floor is None:
        bad.append((loc, "semantic-cache artifact must record both "
                         "'semantic_hit_rate' and 'hit_rate_floor'"))
    else:
        try:
            ok = float(rate) >= float(floor)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"semantic_hit_rate == {rate!r} < "
                             f"hit_rate_floor {floor!r} (the Zipf "
                             f"repeated-intent workload stopped hitting)"))
    stale = obj.get("stale_hits")
    if stale != 0:
        bad.append((loc, f"stale_hits == {stale!r} (must record a "
                         f"measured 0 — a cached window outlived the "
                         f"ingest/delete churn that invalidated it)"))
    if obj.get("miss_parity") is not True:
        bad.append((loc, f"miss_parity == {obj.get('miss_parity')!r} "
                         f"(a cold probe must record a measured true — "
                         f"bit-identical ids AND scores vs the cache-off "
                         f"twin on a never-seen population)"))
    if "recall_at_10" not in obj or "recall_floor" not in obj:
        bad.append((loc, "semantic-cache artifact must record a "
                         "recall_at_10/recall_floor pair (a hit-served "
                         "window must BE the exact answer)"))
    speedup = obj.get("semantic_vs_off_speedup")
    sfloor = obj.get("speedup_floor")
    if speedup is None or sfloor is None:
        bad.append((loc, "semantic-cache artifact must record both "
                         "'semantic_vs_off_speedup' and 'speedup_floor'"))
    else:
        try:
            ok = float(speedup) >= float(sfloor)
        except (TypeError, ValueError):
            ok = False
        if not ok:
            bad.append((loc, f"semantic_vs_off_speedup == {speedup!r} < "
                             f"speedup_floor {sfloor!r} (hits stopped "
                             f"buying back their scan blocks)"))


def _check_ingest(loc, obj, bad):
    """The ISSUE 9 sharded-ingest gate on one ``"ingest_sharded": true``
    dict."""
    if "dispatches_per_conversation" not in obj:
        bad.append((loc, "sharded-ingest artifact must record a measured "
                         "'dispatches_per_conversation'"))
    scaling = obj.get("write_scaling")
    floor = obj.get("write_scaling_floor")
    if scaling is None or floor is None:
        bad.append((loc, "sharded-ingest artifact must record both "
                         "'write_scaling' and 'write_scaling_floor'"))
        return
    try:
        ok = float(scaling) >= float(floor)
    except (TypeError, ValueError):
        ok = False
    if not ok:
        bad.append((loc, f"write_scaling == {scaling!r} < "
                         f"write_scaling_floor {floor!r} (the pod write "
                         f"path regressed below the single-chip fused "
                         f"path)"))


def _check_tiered(loc, obj, bad):
    """The ISSUE 8 tiered-memory gate on one ``"tiered": true`` dict."""
    for key in ("cold_hit_rate", "hot_fraction"):
        if key not in obj:
            bad.append((loc, f"tiered artifact must record '{key}'"))
    if "recall_at_10" not in obj or "recall_floor" not in obj:
        bad.append((loc, "tiered artifact must record a recall_at_10/"
                         "recall_floor pair"))
    if "dispatches_per_turn" not in obj:
        bad.append((loc, "tiered artifact must record the hot-only "
                         "probe's measured dispatches_per_turn"))
    cold_d = obj.get("cold_hit_dispatches_per_turn")
    try:
        ok = float(cold_d) <= 2.0
    except (TypeError, ValueError):
        ok = False
    if not ok:
        bad.append((loc, f"cold_hit_dispatches_per_turn == {cold_d!r} "
                         f"(must record a measured value <= 2 — coarse "
                         f"scan + ONE bounded finish)"))


def main(argv):
    if argv:
        paths = argv
    else:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, "bench_artifacts")
        paths = sorted(glob.glob(os.path.join(root, "*.json")))
    checked = 0
    checked_recall = 0
    checked_speedup = 0
    checked_mesh = 0
    checked_telemetry = 0
    checked_tiered = 0
    checked_ingest = 0
    checked_online_ivf = 0
    checked_pq = 0
    checked_paged = 0
    checked_replica = 0
    checked_lifecycle = 0
    checked_semantic = 0
    bad = []
    for p in paths:
        try:
            with open(p) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[check] skipping unreadable {p}: {e}", file=sys.stderr)
            continue
        (hits, recalls, speedups, meshes, tel_blocks, tiereds,
         ingests, online_ivfs, pq_fuseds, pageds, replicas, lifecycles,
         semantics) = (
            [], [], [], [], [], [], [], [], [], [], [], [], [])
        _walk(data, os.path.basename(p), hits, recalls, speedups, meshes,
              tel_blocks, tiereds, ingests, online_ivfs,
              pq_fuseds, pageds, replicas, lifecycles, semantics)
        grandfathered = os.path.basename(p).startswith(
            _PRE_TELEMETRY_PREFIXES)
        for loc, measured_fused, block in tel_blocks:
            checked_telemetry += 1
            _check_telemetry(loc, measured_fused, block, grandfathered, bad)
        for loc, obj in tiereds:
            checked_tiered += 1
            _check_tiered(loc, obj, bad)
        for loc, obj in ingests:
            checked_ingest += 1
            _check_ingest(loc, obj, bad)
        for loc, obj in online_ivfs:
            checked_online_ivf += 1
            _check_online_ivf(loc, obj, bad)
        for loc, obj in pq_fuseds:
            checked_pq += 1
            _check_pq_fused(loc, obj, bad)
        for loc, obj in pageds:
            checked_paged += 1
            _check_paged(loc, obj, bad)
        for loc, obj in replicas:
            checked_replica += 1
            _check_replica(loc, obj, bad)
        for loc, obj in lifecycles:
            checked_lifecycle += 1
            _check_lifecycle(loc, obj, bad)
        for loc, obj in semantics:
            checked_semantic += 1
            _check_semantic(loc, obj, bad)
        for loc, v, planned in hits:
            checked += 1
            if v == 1:
                continue
            try:
                planned_ok = planned is not None \
                    and float(v) == float(planned) >= 1
            except (TypeError, ValueError):
                planned_ok = False
            if planned_ok:
                # a PLANNED multi-dispatch turn (the HBM planner split
                # it, recorded it, and the artifact says so) — accepted;
                # an unplanned or unrecorded split still fails below
                continue
            bad.append((loc, f"{loc.rsplit('.', 1)[-1]} == {v!r} "
                             f"(expected 1, or a matching planned_"
                             f"{loc.rsplit('.', 1)[-1]})"))
        for loc, got, floor in recalls:
            checked_recall += 1
            try:
                ok = float(got) >= float(floor)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                bad.append((loc, f"recall_at_10 == {got!r} "
                                 f"< recall_floor {floor!r}"))
        for loc, got, floor in speedups:
            checked_speedup += 1
            try:
                ok = float(got) >= float(floor)
            except (TypeError, ValueError):
                ok = False
            if not ok:
                bad.append((loc, f"fused_vs_classic_speedup == {got!r} "
                                 f"< speedup_floor {floor!r}"))
        for loc, has_count in meshes:
            checked_mesh += 1
            if not has_count:
                bad.append((loc, "sharded artifact (has a 'mesh' dict) "
                                 "records no measured dispatches_per_turn"))
    for loc, msg in bad:
        print(f"REGRESSION: {loc}: {msg}")
    print(f"[check] {checked} dispatch-count value(s), "
          f"{checked_recall} recall pair(s), {checked_speedup} speedup "
          f"pair(s), {checked_mesh} sharded artifact(s), "
          f"{checked_telemetry} telemetry block(s), "
          f"{checked_tiered} tiered gate(s), "
          f"{checked_ingest} sharded-ingest gate(s), "
          f"{checked_online_ivf} online-ivf gate(s), "
          f"{checked_pq} fused-pq gate(s), "
          f"{checked_paged} paged-arena gate(s), "
          f"{checked_replica} replica gate(s), "
          f"{checked_lifecycle} lifecycle gate(s), and "
          f"{checked_semantic} semantic-cache gate(s) across "
          f"{len(paths)} artifact(s); {len(bad)} regression(s)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
