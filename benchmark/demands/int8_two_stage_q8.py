"""What one serving dispatch of the int8 deployment has to move, at least:
the bytes and operations of a coarse scan over the int8 codes and an exact
rescore of its survivors, from the deployment's sizes alone. The demand is
the configuration's and counts the same work whatever implements it: a
program that materialises a ``[batch, rows]`` score tile and sorts it moves
far more and reads a small share of this roofline."""


def need(cfg: dict, batch: float) -> dict:
    """One dispatch of ``batch`` queries. Coarse: every row's int8 code is
    read once (the whole batch shares the pass) with its f32 scale and its
    tenant and alive columns, and every query's code is multiplied with
    every row's. Rescore: ``coarse_fetch`` rows a query are gathered from the
    master (2 B a component) and scored again. The f32 queries go in, ``k``
    ids and scores a query come back. The running lists of the selection and
    a block's score tile are an implementation's own: not counted.

    Operations: the coarse products alone, against the int8 peak —
    ``peaks.least_seconds`` takes one peak, and the rescore's 2 x batch x
    coarse_fetch x dim bf16 operations are rows / coarse_fetch = 36,765 times
    fewer. HBM binds either way: 4.76 ms against 1.25 ms at a batch of 64."""
    rows, dim, k = cfg["rows"], cfg["dim"], cfg["k"]
    fetch = cfg["coarse_fetch"]
    return {
        "bytes": rows * dim * 1 + rows * 4 + rows * (4 + 1)
                 + batch * fetch * dim * 2 + batch * dim * 4 + batch * k * 8,
        "ops": 2.0 * batch * rows * dim,
        "ops_peak": "int8_ops_per_s",
    }
