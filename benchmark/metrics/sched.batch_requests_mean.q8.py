from benchmark.span_metrics import counter_ratio


def read(run):
    """Requests a served batch carried, the window's mean: 64 where two
    batches' worth of callers wait."""
    return counter_ratio(run, "serve.requests", "serve.batches")
