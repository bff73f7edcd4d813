from benchmark.families import counted


def read(run):
    """CSR builds INSIDE the window (the registry is reset before it): has
    to read 0 — a boosting read never dirties the topology, so the graph
    built in warm-up serves the whole window. ``index.csr_lookups`` is
    bumped by every dispatch; None on a program that counts neither."""
    return counted(run, "index.csr_builds", "index.csr_lookups")
