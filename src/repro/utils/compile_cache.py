"""JAX's persistent compilation cache, configured in one place.

Every entry point that compiles for the chip (``chip_smoke.py``,
``launch/serve.py``, ``launch/gateway.py``) calls :func:`enable` before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this module sets nothing.  Otherwise the cache goes to
``<repo>/.jax_cache`` (gitignored): a fixed path, never one built from a
temporary name, a PID or the time, because a cache that moves is never
found again.

Importing this module does not import JAX.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_DIR", "enable"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
