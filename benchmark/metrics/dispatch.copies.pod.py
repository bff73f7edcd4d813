from benchmark.span_metrics import has_counter


def read(run):
    """Serving dispatches of the window that took the COPYING twin (a whole
    shard copied on every chip) where the donated one would do: has to read
    0. serve.merge_candidates is bumped on every sharded dispatch by the
    program that counts copies: it tells that program from one that counts
    none (None)."""
    if not has_counter(run, "serve.merge_candidates"):
        return None
    return float(run.counter("serve.copy_dispatches"))
