"""JAX's persistent compilation cache, switched on by the entry points.

The engine compiles many small plans (one per contraction structure, level
and fan-out shape) plus a Mosaic kernel inside most of them, so a cold
process spends much of its start-up compiling.  :func:`enable_compile_cache`
lets a later process load those executables instead.  Scripts call it once
at start-up (``chip_smoke.py``, ``benchmarks/run.py``, the examples); the
library never calls it on import.

- With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it: the
  directory is left to JAX and no other is set.
- Otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path —
  the path is part of what makes a later process find the entries.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: the engine's plans are small and quick to
    # compile one by one, but there are many of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
