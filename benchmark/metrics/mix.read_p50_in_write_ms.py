from benchmark.mix_metrics import read_p50_in_write_ms


def read(run):
    """Median latency of the reads that fell DUE while a conversation of
    the writer was in progress."""
    return read_p50_in_write_ms(run)
