from benchmark.readers import span_minus_child_p50_ms


def read(run):
    return span_minus_child_p50_ms(run, "lz.serve.batch", "lz.serve.")
