"""Persistent XLA compile cache, placed from outside the program.

Entry points (`chip_smoke.py`, `examples/dram_codesign.py`,
`repro.launch.serve`, `benchmarks.run`) call `enable_compile_cache()` once
at start-up, before their first compile; importing this module changes
nothing, and tests never call it.

- If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it: the cache
  lives there and no other directory is set here.
- Otherwise the cache is `<checkout>/.jax_cache` (gitignored).  The path is
  fixed on purpose: it is part of the cache key, so a temp-, pid- or
  time-derived directory would never hit.

Every compile is cached (minimum compile time 0 s): the sweep is many
sub-second programs, and a second run in the same checkout should reload
all of them.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
