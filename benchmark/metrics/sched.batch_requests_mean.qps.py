from benchmark.span_metrics import counter_ratio


def read(run):
    """Requests a served batch carried, the window's mean: 64 where callers
    that wait are served together (PR 32), 32 where the closed loop splits
    into a lone request and the rest."""
    return counter_ratio(run, "serve.requests", "serve.batches")
