from benchmark import stats


def read(run):
    """How late the generator sent, against each request's due time."""
    return stats.percentile(run.late_ms, 95) if len(run.late_ms) else None
