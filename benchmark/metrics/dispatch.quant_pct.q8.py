NAME = "serve.dispatches"


def read(run):
    """Share of the window's serving dispatches the program labelled
    ``mode="quant"`` (``serve.dispatches{mode}``): has to read 100 — a
    program that falls back to the exact scan reads 0. None for a program
    that counted no dispatch."""
    tel = run.telemetry
    if tel is None:
        return None
    total = quant = 0
    for key, n in list(tel.counters.items()):
        if key == NAME or key.startswith(NAME + "{"):
            total += n
            if 'mode="quant"' in key:
                quant += n
    return 100.0 * quant / total if total else None
