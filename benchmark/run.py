#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` in this process, on the machine it
is started on. Every input is made from ``--seed``. Without the TPU chips the
cell asks for it exits 2 and prints no result. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, with a trace ``breakdown``, and last
``compared``: each number the comparison with the reference rests on beside
its limit. The same numbers are the last lines of standard error.

``--cpu-debug`` runs a tiny geometry on whatever backend jax has, to debug
the harness itself: such a run exits 3 and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-debug", action="store_true")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [root]          # the checkout, not benchmark/ itself
    from benchmark import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), debug=args.cpu_debug,
                                  t_start=t_start)
    except harness.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    line = json.dumps(result)
    if args.cpu_debug:
        print(f"cpu-debug run (no result line): {line}", file=sys.stderr)
    else:
        print(line, flush=True)
    for name, v in result["compared"].items():
        print(f"compared {name}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr)
    return 3 if args.cpu_debug else 0


if __name__ == "__main__":
    sys.exit(main())
