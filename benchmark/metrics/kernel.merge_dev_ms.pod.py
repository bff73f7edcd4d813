from benchmark import tracing

SCAN = "lz_select_scan"


def read(run):
    """Device milliseconds a dispatch spends OUTSIDE the shard-local scan
    (``lz_select_scan*``): the candidates' two all-gathers, the merge's
    top-k, the tier columns. First device plane, the window's operations by
    name, over the window's ``lz.serve.batch`` spans; None where the trace
    holds no such operation."""
    if run.trace is None or not run.trace["devices"]:
        return None
    window = tracing.window_of(run.trace)
    ops = next(iter(run.trace["devices"].values()))
    other = [e for e in tracing.clip(ops, window) if not e[0].startswith(SCAN)]
    n = len(tracing.spans_named(run.trace, "lz.serve.batch"))
    if not other or not n:
        return None
    return tracing.total(tracing.union(other)) / n / 1e6
