from benchmark.span_metrics import span_p50_ms


def read(run):
    """Median length of the free worker's wait for callers on their way back
    (lz.sched.hold, PR 32): the callers' return, serial with the pass."""
    return span_p50_ms(run, "lz.sched.hold")
