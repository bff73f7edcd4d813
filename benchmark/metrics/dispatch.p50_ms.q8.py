from benchmark.readers import timer_p50


def read(run):
    return timer_p50(run, "serve.dispatch_ms")
