"""What one exact serving dispatch has to move, at least: the bytes and
operations the algorithm needs, from the deployment's sizes alone. A
configuration names this file under ``demand``; a roofline share divides the
least time these imply (``peaks.least_seconds``) by the device time the trace
shows."""

_ITEM = {"bfloat16": 2, "float32": 4, "int8": 1}


def need(cfg: dict, batch: float) -> dict:
    """One dispatch of ``batch`` queries: every arena row is read once (the
    whole batch shares the pass) with its tenant and alive columns, every
    query is scored against every row, and ``k`` ids and scores come back per
    query. Whatever else an implementation moves (a block's score tile, the
    selection's running lists) is its own, not the algorithm's: not counted."""
    rows, dim = cfg["rows"], cfg["dim"]
    item = _ITEM[cfg["dtype"]]
    k = cfg["k"]
    return {
        "bytes": rows * dim * item + rows * (4 + 1)
                 + batch * dim * item + batch * k * 8,
        "ops": 2.0 * batch * rows * dim,
        "ops_peak": "bf16_flops_per_s",
    }
