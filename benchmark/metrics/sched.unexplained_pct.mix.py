from benchmark.stage_metrics import unexplained_pct


def read(run):
    """Share of a read's mean latency (sent to done-callback) that the
    scheduler's stages do not add up to, beside a writer."""
    return unexplained_pct(run)
