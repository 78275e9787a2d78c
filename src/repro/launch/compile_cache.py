"""Where compiled programs persist between runs: one rule for every entry
point (the ``repro.launch`` mains and ``chip_smoke.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and nothing here changes that. Otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored): a fixed path, so the next run of
the same checkout finds what this one compiled. Call
:func:`enable_compile_cache` before the first compilation.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
