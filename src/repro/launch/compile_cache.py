"""Where compiled programs persist between runs.

JAX's persistent compilation cache is keyed by the directory it lives in,
so a path that moves from run to run never hits.  ``JAX_COMPILATION_CACHE_DIR``
from the environment wins (JAX reads it itself at import); otherwise the
cache lives at one fixed, git-ignored path inside the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile;
    returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
