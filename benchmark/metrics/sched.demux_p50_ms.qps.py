from benchmark.span_metrics import span_p50_ms


def read(run):
    """Median length of the worker's demux loop (``lz.sched.demux``): a
    batch's 64 answers handed to the 64 threads that wait for them, one
    release of a handle's lock each (PR 42), under the interpreter lock the
    woken callers are queueing for."""
    return span_p50_ms(run, "lz.sched.demux")
