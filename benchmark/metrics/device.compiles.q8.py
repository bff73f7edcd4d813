from benchmark.readers import compiles


def read(run):
    return compiles(run)
