"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting: when it is set, JAX
reads it and nothing here sets another directory.  Otherwise the cache goes
to ``<repo>/.jax_cache`` — a fixed path, never one built from a temporary
name, a pid or a time, so a later run of the same checkout finds what an
earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The directory the cache uses under ``environ``."""
    return environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's cache at :func:`compile_cache_dir`; returns the directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
