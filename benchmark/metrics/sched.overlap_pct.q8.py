from benchmark.span_metrics import counter_ratio


def read(run):
    """Batches admitted while another dispatch was in flight: with 128
    callers that wait, nearly every one. serve.queue_wait_us is bumped on
    every served batch: with it present a program that never overlapped
    reads 0, not None."""
    return counter_ratio(run, "serve.overlapped_batches", "serve.batches",
                         100.0, marker="serve.queue_wait_us")
