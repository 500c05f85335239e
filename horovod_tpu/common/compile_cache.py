"""Placement of jax's persistent compilation cache.

The cache directory is part of every entry's key, so it has to be a
fixed path: a directory built from a pid, the time or ``tempfile`` never
hits. One rule, applied by the entry points that compile large programs
(``chip_smoke.py``, the benchmark examples):

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it on its own; nothing is
  set in code (the operator, or the machine image, owns the placement).
- unset: ``<checkout>/.cache/jax`` (git-ignored; the path the example
  smoke tests already use).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache() -> str:
    """Point jax's persistent compile cache at its fixed place and return
    the directory in use. Call before the first compile of the process."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
