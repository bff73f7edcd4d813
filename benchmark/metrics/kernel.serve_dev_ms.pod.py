from benchmark.readers import device_ms_per_span


def read(run):
    return device_ms_per_span(run, "lz.serve.batch")
