"""What every entry point needs to know about the device it runs on.

``enable_compile_cache`` points JAX's persistent compilation cache at a
fixed directory of the checkout, so a second run of an entry point (or a
second process of the same run) reuses the first one's compiled programs.
Entry points call it from their ``__main__`` block; importing ``repro``
never does, so tests and library users keep JAX's own settings.

``device_info`` is the provenance every printed or recorded result carries:
which platform, which chip, how many.
"""

from __future__ import annotations

import os

import jax

__all__ = ["COMPILE_CACHE_DIR", "device_info", "enable_compile_cache"]

# <repo>/.jax_cache: a fixed path, because the path is part of the cache's
# key — a directory that moved between runs would never hit
COMPILE_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def device_info() -> dict:
    """``{"platform", "device_kind", "count"}`` as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}
