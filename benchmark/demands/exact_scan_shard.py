"""What one exact serving dispatch over a row-sharded arena has to move ON
ONE CHIP, at least: a configuration that names its ``mesh`` names this file
under ``demand``, and the roofline share divides the least time these imply
by the device time the first chip's plane shows (every chip runs the same
program over its own rows)."""

import math

from benchmark.demands.exact_scan import need as whole


def need(cfg: dict, batch: float) -> dict:
    """One chip's share of ``exact_scan.need``: its 1/shards of the rows with
    their tenant and alive columns, against the WHOLE query batch (queries
    are replicated), so a 1/shards of the operations; then what the merge
    gathers onto every chip: each shard's ``k`` candidates of every query,
    an id and a score (8 bytes) each."""
    shards = math.prod(cfg["mesh"]["shape"])
    one = whole(dict(cfg, rows=cfg["rows"] / shards), batch)
    return dict(one, bytes=one["bytes"] + shards * batch * cfg["k"] * 8)
