from benchmark.readers import device_ms_per_span


def read(run):
    """Device milliseconds one dispatch keeps the chip busy: busy time inside
    the union of the window's ``lz.serve.batch`` spans, over their number —
    the coarse scan, the rescore and the tail together."""
    return device_ms_per_span(run, "lz.serve.batch")
