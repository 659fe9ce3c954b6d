"""Process set-up shared by the entry points: the persistent compile cache
and the deploy-parameter build.

Nothing here runs at import; entry points call these from ``main``.
"""
from __future__ import annotations

import os
import pathlib
from typing import Any

import jax

# The checkout root of the src layout (src/repro/launch/runtime.py).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    Fixed, never derived from a temp name, a pid or the time, so a later
    run finds this run's entries."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def deploy_params(model, seed: int) -> Any:
    """Random deploy (packed) params from ``seed`` in ONE jitted
    init-and-convert, so the float32 latents are compiler temporaries and
    never all live at once on the device (at published widths the eager
    ``init`` alone can fill most of a 16 GB chip)."""
    return jax.jit(lambda k: model.convert(model.init(k)))(
        jax.random.PRNGKey(seed))
