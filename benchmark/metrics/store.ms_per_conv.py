from benchmark.span_metrics import span_ms_per

# every entry of the write path into the store: the fresh nodes of the
# ingest, the persist passes, the load of the next user
SPANS = ("lz.store.save", "lz.store.load", "lz.store.add")


def read(run):
    return span_ms_per(run, SPANS, "lz.api.end_conversation")
