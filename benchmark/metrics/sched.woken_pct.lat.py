from benchmark.span_metrics import counter_ratio


def read(run):
    """Share of the window's served requests whose answer woke a thread that
    waited for it in result() (serve.wakes / serve.requests): ~100 where the
    callers wait (a closed loop), 0 where answers go to done-callbacks on
    the worker (an open loop) — how often the handle's waiting path runs.
    None on a program that does not stamp a request's stages."""
    return counter_ratio(run, "serve.wakes", "serve.requests", 100.0,
                         marker="serve.exec_us")
