"""Placement of JAX's persistent compilation cache for the entry scripts.

``chip_smoke.py``, ``examples/tpcc_serve.py`` and ``benchmarks/run.py`` call
:func:`use_compile_cache` before their first compile; no library module calls
it, so importing ``repro`` never changes JAX's configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/utils/jax_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored), so a later run from the same
    checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
