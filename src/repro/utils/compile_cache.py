"""Persistent XLA compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
:func:`enable_compile_cache` once at start-up; library modules never do,
so importing ``repro`` changes no global JAX setting.  Importing this
module starts the build recorder (``repro.utils.trace``), so a script
that turns the cache on also has its hits and misses counted from the
start.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.utils import trace  # noqa: F401  (records builds from start-up)

# A fixed path: a cache written to a directory that changes between runs
# is never found again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands untouched; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
