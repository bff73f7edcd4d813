from benchmark.stage_metrics import unexplained_pct


def read(run):
    """Share of the harness's own mean latency that the request's stages the
    scheduler stamps (queue, account, executor call, demux wait, wake-up) do
    not add up to: what the layer map still leaves out of an open-loop
    request's latency (sent to done-callback)."""
    return unexplained_pct(run)
