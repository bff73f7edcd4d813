from benchmark.span_metrics import counter_ratio


def read(run):
    """Graph neighbours the device's boost scatter touched, a request: over
    0 where served rows have edges; a program that drops the neighbour
    gather, or a deployment without a graph, reads 0."""
    return counter_ratio(run, "device.nbr_boost_rows", "serve.requests", 1.0,
                         marker="serve.requests")
