from benchmark.span_metrics import span_p50_ms


def read(run):
    return span_p50_ms(run, "lz.index.stage")
