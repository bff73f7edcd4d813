from benchmark.stage_metrics import stage_mean_ms


def read(run):
    """From a waiting caller running again to its next submission being
    enqueued, the window's mean (serve.return_us / serve.returns): the caller's
    own time, counted only while the bound of the demux that released it still
    runs (the 64 callers the hold waits for)."""
    return stage_mean_ms(run, "serve.return_us", "serve.returns")
