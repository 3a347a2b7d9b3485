"""The engine's account of its own start-up: every phase from the
configuration to ready as an interval with its seconds and the chip's
memory at its end, and every warm-up call as a record with its compiles.

``setup_s`` was the one end-to-end number the program could not explain:
a log line said "ready" with no time in it. The account is written where
the work happens (``new_engine_from_config``, ``EnginePrograms.allocate``
and ``build``, the two ``warmup`` functions) and read through
``stats()["startup"]``, ``/debug/timeline``'s start-up track, two
gauges, the ready log line and, until ready, the health check.

  - PHASES (``phase``): the starting thread is in exactly one of
    configure / weights / allocate / programs / warmup / ready, or in
    none. One call ends a phase and begins the next, as the generation
    loop's account does (``tpu/generator.py:_LoopAccount``), so from the
    first phase to ``finish`` they are back to back and sum to
    ``t_ready - t_start``. A region entered from inside another (an
    ``allocate`` inside the engine's construction: ``within``) puts the
    phase it left back. A phase that closes reads ``memory_stats()`` of the
    fullest local device: ``peak_bytes_in_use`` only rises, so the phase
    that raised it is the one whose end first shows the new value.
  - WARM-UP (``warming`` / ``call``): one record a program call, from
    ``compile_cache.clock()`` snapshots around it: seconds, compile
    seconds (persistent-cache loads included), the cache's hits and
    misses, the peak after it. The clock is told what is being built
    (``CompileClock.label``), so its marks on the timeline and its
    ``missed`` list name the program. A warm-up called again while
    serving appends a phase and records of its own (``pass`` 1, 2, ...)
    and leaves start-up's as they were.

Times are ``time.monotonic()``, the timeline's clock. Nothing here is
called from the generation loop's per-block or per-token path. State
belongs to the thread that starts or warms the engine; readers copy.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any

__all__ = ["StartupAccount"]

# phases and records appended after ready (a warm-up while serving, a
# recovery's reallocations) stop being kept here
_KEEP = 1024


def _memory() -> tuple[int | None, int | None]:
    """(bytes in use, peak bytes in use) of the fullest local device;
    None where the backend reports none (a CPU)."""
    import jax

    used = peak = None
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if stats.get("bytes_in_use") is not None:
            used = max(used or 0, int(stats["bytes_in_use"]))
        if stats.get("peak_bytes_in_use") is not None:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return used, peak


def _compiles(before: dict, after: dict) -> dict:
    """What the compile clock counted between two snapshots."""
    return {"compile_seconds": after["seconds"] - before["seconds"],
            "hits": after["hits"] - before["hits"],
            "misses": after["misses"] - before["misses"]}


class StartupAccount:
    def __init__(self, timeline=None, metrics=None, tracer=None):
        self.tl = timeline if (timeline is not None
                               and timeline.enabled) else None
        self.metrics = metrics
        self.tracer = tracer
        self.t_start: float | None = None
        self.t_ready: float | None = None
        self.t_warm: float | None = None
        self.phases: list[dict] = []   # closed, oldest first
        self.records: list[dict] = []  # warm-up calls, oldest first
        self._ph: str | None = None
        self._ph_t0 = 0.0
        self._ph_detail: dict = {}
        self._ph_snap: dict = {}       # the compile clock at its start
        self._clock: Any = None        # compile_cache.clock(), from begin
        self._snap0: dict = {}
        self._miss0 = 0
        self._cache: dict | None = None        # frozen at t_warm
        self._missed: list[str] | None = None
        self._passes = 0               # warm-ups begun
        self._warming = False          # warming() is reentrant
        self._done = self._total = 0   # the warm-up under way
        self._root: str | None = None  # tpu.startup's traceparent

    # -- phases --------------------------------------------------------------
    def phase(self, name: str | None, **detail) -> None:
        """Move to phase ``name`` (None: to none). ``detail`` (an
        allocation's ``tag``) goes into the phase's record; ``note`` adds
        to it."""
        from .. import compile_cache  # not at import: it brings JAX in

        now = time.monotonic()
        if self._clock is None:
            self._clock = compile_cache.clock()
            if self.tl is not None:
                # the weights' compiles come before the generator, which
                # attaches the same timeline, is built
                self._clock.timeline = self.tl
            self._snap0 = self._clock.snapshot()
            self._miss0 = len(self._clock.missed)
            self.t_start = now
        snap = self._clock.snapshot()
        if self._ph is not None:
            self._close(now, snap)
        self._ph, self._ph_t0, self._ph_detail = name, now, detail
        self._ph_snap = snap
        tag = detail.get("tag")
        self._clock.label = compile_cache.SERVING if name is None \
            else f"{name}:{tag}" if tag else name

    @contextmanager
    def within(self, name: str, **detail):
        """Phase ``name`` for a region entered from inside another (an
        ``allocate`` inside the engine's construction), which is put back
        after it."""
        prev, prev_detail = self._ph, self._ph_detail
        self.phase(name, **detail)
        try:
            yield self
        finally:
            self.phase(prev, **prev_detail)

    def note(self, **detail) -> None:
        self._ph_detail.update(detail)

    def _close(self, now: float, snap: dict) -> None:
        used, peak = _memory()
        rec = {"name": self._ph, "t0": self._ph_t0,
               "seconds": now - self._ph_t0, "bytes_in_use": used,
               "peak_bytes": peak, **_compiles(self._ph_snap, snap),
               **self._ph_detail}
        if self.t_ready is None or len(self.phases) < _KEEP:
            self.phases.append(rec)
        if self.tl is not None:
            self.tl.startup(self._ph_t0, now, self._ph,
                            str(self._ph_detail.get("tag", "")))
        if self._root is not None:
            self._span(rec)

    def finish(self) -> dict:
        """The engine is ready: the open phase ends, a ``ready`` phase
        of no length holds the memory the engine starts serving with,
        ``t_ready`` is its end, the gauges are set and the spans go out.
        Returns what the ready log line adds."""
        self.phase("ready")
        self.phase(None)
        self.t_ready = self.phases[-1]["t0"] + self.phases[-1]["seconds"]
        self._publish()
        self._export()
        by_name = self._seconds_by_phase()
        top = sorted(by_name, key=by_name.get, reverse=True)[:3]
        missed = self._missed_now()
        return {"seconds": round(self.t_ready - self.t_start, 3),
                "phases": {n: round(by_name[n], 3) for n in top},
                # a cold start misses every program: the line names the
                # first few, stats()["startup"]["missed"] all of them
                "cache_misses": len(missed), "missed": missed[:8]}

    def _seconds_by_phase(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for p in list(self.phases):
            out[p["name"]] = out.get(p["name"], 0.0) + p["seconds"]
        return out

    # -- warm-up -------------------------------------------------------------
    @contextmanager
    def warming(self):
        """A warm-up: a ``warmup`` phase of its own unless one is open
        (``engine.warmup`` calls the generator's). ``expect`` says how
        many program calls it will make, for the health check."""
        if self._warming:
            yield self
            return
        self._warming = True
        self._done = self._total = 0
        self._passes += 1
        try:
            with self.within("warmup", **{"pass": self._passes - 1}):
                yield self
        finally:
            self._warming = False
            if self.t_warm is None:
                last = self.phases[-1]
                self.t_warm = last["t0"] + last["seconds"]
                self._cache = self._cache_now()
                self._missed = self._missed_now()
                if self.t_ready is not None:
                    self._publish()

    def expect(self, programs: int) -> None:
        self._total += programs

    @contextmanager
    def call(self, program: str, shape: tuple):
        """One program call of a warm-up; the caller blocks until the
        result is ready inside it."""
        clock, label = self._clock, self._clock.label
        clock.label = what = f"{program}{tuple(shape)}"
        before, t0 = clock.snapshot(), time.monotonic()
        try:
            yield
        finally:
            t1, after = time.monotonic(), clock.snapshot()
            clock.label = label
            self._done += 1
            rec = {"program": program, "shape": list(shape),
                   "pass": self._passes - 1, "t0": t0, "seconds": t1 - t0,
                   **_compiles(before, after), "peak_bytes": _memory()[1]}
            if self.t_warm is None or len(self.records) < _KEEP:
                self.records.append(rec)
            if self.tl is not None:
                self.tl.startup(t0, t1, "warmup", what)

    # -- read side -----------------------------------------------------------
    def _cache_now(self) -> dict:
        snap = self._clock.snapshot()
        return {k: snap[k] - self._snap0[k]
                for k in ("hits", "misses", "programs")}

    def _missed_now(self) -> list[str]:
        return list(self._clock.missed[self._miss0:])

    def stats(self) -> dict:
        """``cache`` and ``missed`` count from the first phase to the end
        of the first warm-up (to now before that); ``missed_later`` names
        what missed after it. JAX counts a miss where it writes the
        program to the cache: ``programs`` less hits and misses compiled
        in less than the cache keeps (compile_cache.configure) and are
        compiled again by every start."""
        if self._clock is None:
            return {"t_start": None, "t_ready": None, "t_warm": None,
                    "phases": [], "warmup": [], "missed": [],
                    "cache": {"hits": 0, "misses": 0, "programs": 0},
                    "missed_later": []}
        missed = self._missed_now()
        n = len(missed) if self._missed is None else len(self._missed)
        return {"t_start": self.t_start, "t_ready": self.t_ready,
                "t_warm": self.t_warm, "phases": list(self.phases),
                "warmup": list(self.records),
                "cache": self._cache or self._cache_now(),
                "missed": missed[:n], "missed_later": missed[n:]}

    def progress(self) -> dict | None:
        """What the health check says until the engine is ready."""
        if self.t_start is None or self.t_ready is not None:
            return None
        return {"phase": self._ph, "programs_done": self._done,
                "programs_total": self._total,
                "seconds": round(time.monotonic() - self.t_start, 3)}

    # -- gauges and spans ----------------------------------------------------
    def _publish(self) -> None:
        if self.metrics is None:
            return
        try:
            for name, seconds in self._seconds_by_phase().items():
                self.metrics.set_gauge("app_tpu_startup_seconds", seconds,
                                       phase=name)
            self.metrics.set_gauge(
                "app_tpu_startup_cache_misses",
                float((self._cache or self._cache_now())["misses"]))
        except Exception:
            pass  # telemetry must never stop a start

    def _export(self) -> None:
        """``tpu.startup`` and a child a phase, where somebody could read
        them: only with an exporter, as the other ``tpu.*`` spans."""
        if self.tracer is None or self.tracer.exporter is None:
            return
        try:
            root = self.tracer.record_span(
                "tpu.startup", self.t_start, self.t_ready,
                attributes={"missed": len(self._missed_now())})
            self._root = root.traceparent()
            for rec in self.phases:
                self._span(rec)
        except Exception:
            pass

    def _span(self, rec: dict) -> None:
        try:
            self.tracer.record_span(
                "tpu.startup." + rec["name"], rec["t0"],
                rec["t0"] + rec["seconds"], traceparent=self._root,
                attributes={k: v for k, v in rec.items()
                            if k not in ("name", "t0") and v is not None})
        except Exception:
            pass
