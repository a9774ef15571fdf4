"""Where compiled programs persist between processes.

Entry points that compile the model (``launch.serve``, ``launch.train`` and
``chip_smoke.py``) call :func:`use_compile_cache` before their first
compile, so a second process with the same programs loads them instead of
compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed cache directory inside the checkout, resolved from this file and
#: not from the working directory: the path is part of every cache key, so
#: a directory that moved would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and nothing is
    set in code; otherwise the cache lives at :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
