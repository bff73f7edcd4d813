from benchmark.stage_metrics import stage_mean_ms


def read(run):
    """From a request's answer being set to the thread that waited for it in
    result() running again, the window's mean (serve.wake_us / serve.wakes): 64
    client threads, all woken by one demux while the worker holds its window
    for them."""
    return stage_mean_ms(run, "serve.wake_us", "serve.wakes")
