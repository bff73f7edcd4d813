"""Which backend the kernels are compiled for — decided in ONE place.

The Pallas kernels (``ops/pallas_topk.py``, ``ops/flash_attention.py``) run
compiled on a TPU and in interpret mode everywhere else (how the CPU test
suite exercises them); the auto-dispatch gates in ``core/state.py``,
``ops/topk.py`` and ``models/llm.py`` pick the Pallas path only on a TPU.
Every one of those decisions reads :func:`on_tpu`.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when jit-compiled programs of this process target a TPU."""
    return jax.default_backend() == "tpu"
