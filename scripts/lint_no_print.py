#!/usr/bin/env python3
"""Lint: no bare ``print(`` in the lazzaro_tpu serving modules.

ISSUE 6 satellite: the serving stack reports through the Telemetry
registry and the ``lazzaro_tpu`` logging hierarchy — a stray ``print`` in
a library hot path can't be silenced, redirected, or scraped, so it fails
CI here. User-facing entry points (``cli/``, ``dashboard`` startup,
examples, bench, ``chip_smoke.py``) are exempt: stdout IS their interface.

A line may opt out with a trailing ``# noqa: print`` (e.g. a __main__
debugging harness), which keeps the lint grep-simple and the exemptions
visible in review.

Usage:
    python scripts/lint_no_print.py          # lint the default scope
    python scripts/lint_no_print.py a.py ... # lint specific files
"""

from __future__ import annotations

import glob
import os
import re
import sys

# Serving-path scope: every module a request or an ingest batch flows
# through. cli/, dashboard/, models/, integrations/ and scripts stay out.
SCOPE = (
    "lazzaro_tpu/core/*.py",
    "lazzaro_tpu/serve/*.py",
    "lazzaro_tpu/parallel/*.py",
    "lazzaro_tpu/ops/*.py",
    "lazzaro_tpu/tier/*.py",
    "lazzaro_tpu/models/*.py",
    "lazzaro_tpu/utils/batching.py",
    "lazzaro_tpu/utils/telemetry.py",
    "lazzaro_tpu/utils/compile_cache.py",
)

# A call statement, not the word: start-of-expression ``print(``.
_PRINT = re.compile(r"(?<![\w.])print\(")
_EXEMPT = "# noqa: print"


def lint(paths):
    bad = []
    for path in paths:
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError as e:
            print(f"[lint] unreadable {path}: {e}", file=sys.stderr)
            continue
        for no, line in enumerate(lines, 1):
            code = line.split("#", 1)[0]
            if _PRINT.search(code) and _EXEMPT not in line:
                bad.append((path, no, line.rstrip()))
    return bad


def main(argv):
    if argv:
        paths = argv
    else:
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir)
        paths = []
        for pattern in SCOPE:
            paths.extend(sorted(glob.glob(os.path.join(root, pattern))))
    bad = lint(paths)
    for path, no, line in bad:
        print(f"PRINT-IN-SERVING-MODULE: {path}:{no}: {line}")
    print(f"[lint] {len(paths)} file(s) checked; {len(bad)} violation(s)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
