"""Persistent XLA compile cache for the entry points.

Called by the programs a user runs (``chip_smoke.py``,
``benchmark/run.py``, ``bench_train.py``, ``python -m flexflow_tpu.serve``,
the chip tools under ``tools/``)
before their first compile — never at package import and never by the
tests. A cold 32-layer serving compile is minutes, and a fresh machine
starts with nothing compiled.

The directory is part of the cache key's environment, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the caller's environment sets it (JAX
reads the variable itself; nothing is set in code then), otherwise one
fixed path inside the checkout.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    import jax

    # Keep the sub-second programs too: building a 7B model dispatches
    # hundreds of them (per-weight init, quantisation), half a cold start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
