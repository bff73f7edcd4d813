from benchmark import tracing

SCAN = "lz_select_scan_q8"


def read(run):
    """Device milliseconds a dispatch spends OUTSIDE the coarse scan
    (``lz_select_scan_q8*``): the survivors' gather from the master, their
    rescore, the top-k of the rescored, the tier columns and the tail. First
    device plane, the window's operations by name, over the window's
    ``lz.serve.batch`` spans. A program without that kernel does all of its
    work outside it, and reads its whole dispatch here; None where the trace
    holds no device operation."""
    if run.trace is None or not run.trace["devices"]:
        return None
    window = tracing.window_of(run.trace)
    ops = next(iter(run.trace["devices"].values()))
    other = [e for e in tracing.clip(ops, window) if not e[0].startswith(SCAN)]
    n = len(tracing.spans_named(run.trace, "lz.serve.batch"))
    if not other or not n:
        return None
    return tracing.total(tracing.union(other)) / n / 1e6
