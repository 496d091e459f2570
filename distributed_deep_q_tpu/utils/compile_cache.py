"""Where the persistent XLA compile cache lives — decided from outside.

One rule for every entry point that holds the chip (``main.main``,
``chip_smoke.py``, ``bench.main``), applied before first backend use:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code, so the environment is never overridden and no second
  directory appears.
- otherwise: ``<checkout>/.jax_cache`` (git-ignored). A FIXED path —
  the directory is part of the cache key, so a temp name, a pid or a
  timestamp would never hit.

Child processes: the in-code setting does not travel (spawned actors and
multi-process workers start from a fresh import and never call this), so
only an exported ``JAX_COMPILATION_CACHE_DIR`` reaches them. bench's
multi-process CPU workers must run WITHOUT the cache (deserialized
executables segfault inside the gloo collectives), so
``bench._multihost_curve`` strips the variable from their environment.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
