from benchmark.span_metrics import counter_ratio


def read(run):
    """Host→device transfers a serving dispatch stages for its requests
    (``serve.h2d_puts`` / ``serve.dispatches``): 1 since the request carrier
    (PR 37); None for a program that does not count them."""
    return counter_ratio(run, "serve.h2d_puts", "serve.dispatches", 1.0)
