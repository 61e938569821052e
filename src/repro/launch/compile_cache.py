"""JAX's persistent compilation cache for the entry points.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable` before their first compile, so a second run of the same
program on the same installation loads its executables instead of
compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed directory of
the checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part
of what a later run must find, so it is never derived from a temporary
name, a pid or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
