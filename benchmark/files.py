"""Where the benchmark's files are and how they are loaded. Whatever belongs
to one configuration, one mix or one metric is a file found by the name or
path that ``BENCHMARK.json`` or the configuration gives: a later PR adds files
and entries and edits none."""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(relpath: str, root: str = ROOT):
    """The Python file ``relpath`` of the checkout ``root``, as a module of
    its own (a configuration's reference or demand, a metric's reader)."""
    path = os.path.join(root, relpath)
    name = "benchmark_file_" + re.sub(r"\W", "_", relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
