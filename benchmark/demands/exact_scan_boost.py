"""What one BOOSTING exact dispatch has to move, at least: the exact scan's
bytes and operations (``exact_scan.need``) plus what the boost's tail needs
from the deployment's sizes alone — the neighbour look-up of the rows a
``chat`` retrieval serves and the write of the rows it touches. A
configuration names this file under ``demand``; it states ``retrieval_cap``
and ``serve_max_nbr`` in its ``memory_config`` for it."""

from benchmark.demands.exact_scan import need as scan


def tail_bytes(cfg: dict) -> int:
    """Bytes one boosting query's tail moves, at most ``retrieval_cap``
    served rows with ``serve_max_nbr`` neighbours each: a served row's two
    ``indptr`` entries and its CSR slots (int32), the tenant (4) and alive
    (1) bytes of every gathered neighbour, and the three columns a boost
    writes (``access_count``, ``salience``, ``last_accessed``, 4 bytes each)
    read and written for the touched rows only, served and neighbour.
    Arena-wide histograms and whole-column rewrites are an implementation's,
    not the algorithm's: not counted."""
    mc = cfg["memory_config"]
    served, slots = mc["retrieval_cap"], mc["retrieval_cap"] * mc["serve_max_nbr"]
    return (served * 2 * 4 + slots * 4 + slots * (4 + 1)
            + (served + slots) * 3 * 4 * 2)


def need(cfg: dict, batch: float) -> dict:
    """One dispatch of ``batch`` boosting queries: the scan, and each
    query's tail. The tail adds bytes and no operations worth counting (a
    compare and an add a slot)."""
    out = scan(cfg, batch)
    return dict(out, bytes=out["bytes"] + batch * tail_bytes(cfg))
