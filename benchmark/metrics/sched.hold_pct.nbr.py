from benchmark.span_metrics import counter_ratio


def read(run):
    """Share of the window's batches admitted after the scheduler's hold
    (PR 32) ran: 128 callers that wait behind ONE worker on a host-paced
    arena — the regime PERF.md section 7 says no cell had."""
    return counter_ratio(run, "serve.held_batches", "serve.batches", 100.0,
                         marker="serve.queue_wait_us")
