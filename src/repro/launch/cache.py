"""The persistent compilation cache: one fixed directory per checkout.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing
here changes it.  Otherwise entry points call :func:`use_checkout_cache`,
which keeps compiled programs in ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``).  The path is fixed, never a temporary,
per-process or time-stamped one, so the next run of the same checkout
finds what this one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "use_checkout_cache"]

# src/repro/launch/cache.py -> the checkout's root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_cache() -> str:
    """Point JAX's persistent cache at the checkout unless the environment
    already names one; returns the directory in use.  Call it before the
    first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
