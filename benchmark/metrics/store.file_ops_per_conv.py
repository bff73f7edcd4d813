from benchmark.span_metrics import span_count_per


def read(run):
    """From the spans, not from store.file_ops: the read-back after the
    window also switches users, and the registry is read at the end."""
    return span_count_per(run, ("lz.store.io", "lz.journal.io"),
                          "lz.api.end_conversation")
