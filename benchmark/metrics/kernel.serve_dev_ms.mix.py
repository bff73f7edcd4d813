from benchmark.families import reader_for

read = reader_for(__file__)
