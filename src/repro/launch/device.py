"""Start-up shared by every process that drives the device (``serve.py``,
``chip_smoke.py``): name the device and place JAX's persistent
compilation cache.

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is
set (JAX reads the variable itself, so nothing is set in code), and
otherwise at one fixed path inside the checkout, ``<repo>/.jax_cache``
(listed in ``.gitignore``). The path is part of the cache key, so it
never holds a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Directory of the persistent compilation cache for this process."""
    return os.environ.get(CACHE_ENV) or str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """The device as JAX reports it: platform, kind and count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def startup(tag: str) -> dict:
    """Point the compile cache at ``compile_cache_dir()`` and print the
    device line. Call before the first compilation."""
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_CACHE_DIR))
    info = device_info()
    print(f"[{tag}] device platform={info['platform']} "
          f"kind={info['kind']} count={info['count']} "
          f"compile_cache={compile_cache_dir()}", flush=True)
    return info
