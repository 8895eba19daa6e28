"""JAX's persistent compilation cache, placed the same way by every entry
point (the render/serve/gateway CLIs, the gateway worker,
``benchmarks/run.py`` and ``chip_smoke.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): a path built from a
temporary name, a pid or the time would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
