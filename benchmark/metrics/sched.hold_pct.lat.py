from benchmark.span_metrics import counter_ratio


def read(run):
    """The control of sched.hold_pct.qps: an open loop of callbacks never
    blocks in result(), so no batch is held and this has to read 0."""
    return counter_ratio(run, "serve.held_batches", "serve.batches", 100.0,
                         marker="serve.queue_wait_us")
