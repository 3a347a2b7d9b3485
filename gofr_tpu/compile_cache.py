"""One persistent XLA compile cache for every entry point.

A cold server start compiles every warm-up program (tens of seconds
each at 8B width); the cache turns every later start into loads. The
directory is part of the cache key, so it must never move:

  - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing
    here touches the directory config.
  - unset: ``<checkout>/.jax_cache`` (git-ignored), the same for the
    server, ``benchmarks/run.py``, ``chip_smoke.py`` and the tests.

Call before the first compile.

The same module owns the one compile listener: ``clock()`` counts the
seconds JAX spends in backend compiles (persistent-cache loads
included), the programs, and the cache's hits and misses, and marks
each compile on the serving timeline so "which step recompiled" is a
mark on the host loop's track. The mark, and each miss, carry what was
being built: ``label``, which the start-up account (observe/startup.py)
sets to its phase or to the program and shape a warm-up is calling, and
the name JAX gives the jitted function.
"""

from __future__ import annotations

import os
import threading
import time

import jax
import jax.monitoring

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


SERVING = "serving"  # the label outside start-up and warm-up


class CompileClock:
    """Totals since the process's first ``clock()`` call. ``log`` keeps
    (monotonic time, seconds) of every compile; ``timeline`` is the
    serving timeline the marks go to (the engine attaches its own).
    ``missed`` names every compile that missed the persistent cache, as
    "<label> <jitted function>"; it stops growing at ``MISSED_MAX``."""

    MISSED_MAX = 4096

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        self.log: list[tuple[float, float]] = []
        self.timeline = None
        self.label = SERVING
        self.missed: list[str] = []
        # JAX reports a miss inside the compile it belongs to, on the
        # compiling thread, before that compile's duration
        self._miss = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, fun_name: str = "",
                  **_) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        self.seconds += seconds
        self.programs += 1
        self.log.append((time.monotonic(), seconds))
        what = f"{self.label} {fun_name}".rstrip()
        if getattr(self._miss, "pending", False):
            self._miss.pending = False
            if len(self.missed) < self.MISSED_MAX:
                self.missed.append(what)
        tl = self.timeline
        if tl is not None:
            tl.compile(seconds, what)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
            self._miss.pending = True

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "programs": self.programs,
                "hits": self.hits, "misses": self.misses}


_clock: CompileClock | None = None
_clock_lock = threading.Lock()


def clock() -> CompileClock:
    """The process's compile listener (JAX has no way to unregister
    one, so there is exactly one, made on first use)."""
    global _clock
    with _clock_lock:
        if _clock is None:
            _clock = CompileClock()
        return _clock
