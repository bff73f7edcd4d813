from benchmark.span_metrics import counter_ratio


def read(run):
    """Served batches admitted after the free worker held a non-full window
    for callers on their way back (PR 32). serve.queue_wait_us is bumped on
    every served batch: with it present a program that never held one reads
    0, not None."""
    return counter_ratio(run, "serve.held_batches", "serve.batches", 100.0,
                         marker="serve.queue_wait_us")
