from benchmark.readers import latency_percentile


def read(run):
    return latency_percentile(run, 95)
