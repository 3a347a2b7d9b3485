"""One persistent XLA compile cache for every entry point.

A cold server start compiles every warm-up program (tens of seconds
each at 8B width); the cache turns every later start into loads. The
directory is part of the cache key, so it must never move:

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing
    here touches the directory config.
  - unset: ``<checkout>/.jax_cache`` (git-ignored), the same for the
    server, ``bench.py``, ``chip_smoke.py`` and the tests.

Call before the first compile.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure() -> str:
    """Point JAX at the persistent compile cache; returns the directory
    in use."""
    # JAX's default (1 s) skips most of what this system compiles: a
    # server start is ~100 programs of which ~40 take over half a second,
    # and the test suite re-traces the same sub-second scans and engine
    # programs hundreds of times, each a fresh compile without the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR
