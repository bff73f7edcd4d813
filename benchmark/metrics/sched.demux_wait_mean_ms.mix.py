from benchmark.stage_metrics import stage_mean_ms


def read(run):
    """What a read's answer waits after the executor call returned, the
    window's mean, beside a writer (serve.demux_wait_us / serve.requests)."""
    return stage_mean_ms(run, "serve.demux_wait_us", "serve.requests")
