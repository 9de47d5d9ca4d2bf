"""Where JAX keeps compiled programs between processes.

Every entry point that jits (``pilosa-tpu``, ``chip_smoke.py``,
``benchmark/run.py``) calls :func:`place` before its first jit.  The
directory is part of the cache key, so it is one fixed path, never a
temp name: the operator's ``JAX_COMPILATION_CACHE_DIR`` when set
(JAX reads it itself; nothing is set in code), else ``.jax_cache``
at the root of the checkout.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
