from benchmark.readers import serve_roofline_pct


def read(run):
    return serve_roofline_pct(run)
