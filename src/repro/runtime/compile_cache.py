"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch.serve_lamc``,
``benchmarks.run``, ``examples/quickstart.py``) call :func:`enable`
before their first compile; importing this module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
nowhere else. Otherwise it lives in the fixed ``.jax_cache/`` directory
at the checkout root. The directory is where a later run looks its
programs up, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_DIR", "ENV_VAR", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/runtime/``
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
