"""What every entry point does before it touches the device.

``enable_compile_cache()`` places JAX's persistent compilation cache:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and no
  other directory is configured here.
* otherwise: ``<repo>/.jax_cache``, a fixed path.  The path is part of the
  cache's key, so it is never built from a temporary name, a pid or the time.

``device_info()`` names the hardware a run's numbers came from, in the
shape ``chip_smoke.py`` reports it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def device_info() -> dict:
    """Platform, device kind and count as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
