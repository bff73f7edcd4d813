from benchmark.stage_metrics import stage_mean_ms


def read(run):
    """What a request's answer waits after the executor call returned, the
    window's mean (serve.demux_wait_us / serve.requests): the worker's
    bookkeeping and the set_result of the up to 63 answers ahead of it in the
    batch, each waking a client thread."""
    return stage_mean_ms(run, "serve.demux_wait_us", "serve.requests")
