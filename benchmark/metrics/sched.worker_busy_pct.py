from benchmark.span_metrics import busy_pct


def read(run):
    return busy_pct(run, "lz.sched.idle")
