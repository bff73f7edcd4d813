"""Where the persistent XLA compile cache lives — placed from outside.

Process entry points (``chip_smoke.py``, ``bench.py``, the CLI, the
dashboard, ``__graft_entry__.py``) call :func:`place_compile_cache` before
their first compilation; ``import lazzaro_tpu`` never does, so a host
application keeps whatever cache policy it already has.

The directory is part of the cache key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads that
variable itself — nothing to do), else a fixed path under the checkout.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point jax's persistent compile cache at its one directory and return
    it. Call before the first jit compilation of the process."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
