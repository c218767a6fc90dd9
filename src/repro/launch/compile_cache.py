"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` before their first ``jit``,
so a second process (or a second run on the same machine) loads compiled
programs instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Fixed, git-ignored directory at the root of the checkout.  The path is
#: part of the cache's key, so it never comes from a temp name, a pid or
#: the time.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Use ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it
    itself, so nothing is set here); otherwise cache in ``DEFAULT_DIR``."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
