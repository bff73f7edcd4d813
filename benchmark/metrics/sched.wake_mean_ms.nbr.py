from benchmark.stage_metrics import stage_mean_ms


def read(run):
    """From a request's answer being set to the thread that waited for it in
    result() running again, the window's mean (serve.wake_us / serve.wakes):
    128 client threads woken 64 at a time beside the worker's next pack, under
    one interpreter lock."""
    return stage_mean_ms(run, "serve.wake_us", "serve.wakes")
