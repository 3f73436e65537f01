"""Where JAX keeps its persistent compilation cache for this repository.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets nothing. Otherwise the entry points (``chip_smoke.py``,
``repro.launch.train``, ``repro.launch.sae_factory``) call :func:`configure`, which points the cache at ``<checkout>/.jax_cache``:
one fixed path, so a second run of the same program reads what the first
one compiled, where a temporary or per-process directory would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
