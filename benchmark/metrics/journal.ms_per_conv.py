from benchmark.span_metrics import span_ms_per

# never nested in one another (tests/test_span_names.py), so they add
SPANS = ("lz.journal.turn", "lz.journal.sync", "lz.journal.append",
         "lz.journal.commit", "lz.journal.setup")


def read(run):
    return span_ms_per(run, SPANS, "lz.api.end_conversation")
