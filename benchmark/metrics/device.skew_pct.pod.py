from benchmark import tracing


def read(run):
    """How far the chips of one SPMD program are from doing equal work:
    100 x (the busiest device plane's busy time over the least busy one's,
    less 1), EVERY device plane read. None with fewer than two planes or a
    plane that never ran."""
    if run.trace is None or len(run.trace["devices"]) < 2:
        return None
    window = tracing.window_of(run.trace)
    busy = [tracing.total(tracing.union(tracing.clip(ops, window)))
            for ops in run.trace["devices"].values()]
    if min(busy) <= 0:
        return None
    return 100.0 * (max(busy) / min(busy) - 1.0)
