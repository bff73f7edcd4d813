from benchmark.span_metrics import counter_ratio


def read(run):
    """Dispatches that served ONE request. serve.queue_wait_us is bumped
    beside serve.lone_batches on every batch: it tells a program that counts
    no lone batch from one that had none."""
    return counter_ratio(run, "serve.lone_batches", "serve.batches", 100.0,
                         marker="serve.queue_wait_us")
