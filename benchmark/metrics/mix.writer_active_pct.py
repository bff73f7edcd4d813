from benchmark.mix_metrics import writer_active_pct


def read(run):
    """Share of the window's seconds in which the one writer was inside a
    conversation (switch_user to end_conversation's return)."""
    return writer_active_pct(run)
