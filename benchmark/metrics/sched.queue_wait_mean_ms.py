from benchmark.span_metrics import counter_ratio


def read(run):
    """Mean over ALL served requests: a sum, where the accepted median reads
    a ring that drops samples (PERF.md section 3)."""
    return counter_ratio(run, "serve.queue_wait_us", "serve.requests", 1e-3)
