"""Timed paths broken underneath, for the reads that write: each takes the
system set-up built (``harness.run_cell(..., sabotage=fault)``,
``benchmark/study.py --sabotage tests/benchmark/faults.py:<name>``) and
breaks what a boosting dispatch leaves behind, never what it answers. Every
one has to come out NOT correct, by ``state_errors`` alone."""

import dataclasses
import time


def _wrap(ms, around):
    real = ms.index.search_fused_requests
    ms.index.search_fused_requests = lambda reqs, **kw: around(real, reqs, kw)


def boosts_off(ms):
    """The boost dropped: every request is served as a pure read."""
    _wrap(ms, lambda real, reqs, kw: real(
        [dataclasses.replace(r, boost=False) for r in reqs], **kw))


def boosts_twice(ms):
    """Every dispatch runs twice; the second's answers are returned."""
    def twice(real, reqs, kw):
        real(reqs, **kw)
        return real(reqs, **kw)
    _wrap(ms, twice)


def unboosted_boosts(ms):
    """A pure read leaves a trace (a mix with ``boost_share`` under 1)."""
    _wrap(ms, lambda real, reqs, kw: real(
        [dataclasses.replace(r, boost=True) for r in reqs], **kw))


def boosts_other_tenant(ms):
    """The right rows are served, and the same fact numbers of the NEXT
    tenant take the boost (through the index's own deferred-boost call)."""
    names = sorted(ms.index.tenant_nodes)

    def other(real, reqs, kw):
        out = real([dataclasses.replace(r, boost=False) for r in reqs], **kw)
        entries = {}
        for r, res in zip(reqs, out):
            if not r.boost:
                continue
            nxt = names[(names.index(r.tenant) + 1) % len(names)]
            for nid in res.ids[:kw["cap_take"]]:
                key = f"{nxt}:{nid.partition(':')[2]}"
                entries[key] = (entries.get(key, (0,))[0] + 1, 0, time.time())
        ms.index.apply_boosts(entries, kw["acc_boost"], kw["nbr_boost"])
        return out
    _wrap(ms, other)


def edges_dropped(ms):
    """The graph forgotten: boosts reach the served rows and no neighbour."""
    ms.index.edge_slots.clear()
    ms.index._csr_dirty = True


def fact_dropped(ms):
    """The write path loses a fact: every conversation's first extracted
    fact is dropped before the ingest, after it was acknowledged."""
    real = ms._ingest_facts_dedup_fused
    ms._ingest_facts_dedup_fused = lambda staged: real(staged[1:])


def window_row_served(ms):
    """A row written beside the readers is served to them: the first hit of
    every answer gives way to the newest row the index holds — a warm-up or
    window tenant's, never one of the installed stock's."""
    def newest(real, reqs, kw):
        out = real(reqs, **kw)
        nid = next(reversed(ms.index.row_to_id.values()))
        for r in out:
            if r.ids:
                r.ids = [nid] + list(r.ids[1:])
        return out
    _wrap(ms, newest)
