"""hbmwatch: the device-buffer leak harness — memory sibling of
lockwatch (gofrlint GL203/GL202's runtime complement).

Where lockwatch observes real lock acquisitions, hbmwatch observes
real device buffers: ``jax.live_arrays()`` is ground truth for every
array the process holds, and the hbm accounting registry
(``gofr_tpu/tpu/hbm.py``) says which subsystem CLAIMS which bytes.
Snapshots reconcile the two — declared bytes per subsystem (engine /
kvcache-t0 / lora / spec-decode / batcher), total live bytes, and the
unattributed remainder (dispatch temporaries, jit constants, anything
a subsystem allocated without accounting).

Two ways to use it:

  - **steady-state assertion** (the leak shape that killed the flat
    prefix cache: every request adds device state, nothing evicts):
    ``HBMWatch.assert_flat(fn, warmup=N, iters=M)`` runs ``fn`` — one
    request, one decode tick, one store/restore cycle — N warmup times
    (absorbing jit compiles, pool fills, caches reaching capacity),
    snapshots, runs M more, and raises :class:`HBMLeak` if live bytes
    grew. Used by ``tests/test_memory_regressions.py``.

  - **session mode**: ``pytest --hbmwatch`` (tests/conftest.py, or
    standalone ``-p gofr_tpu.testutil.hbmwatch``) snapshots around
    every test, prints the per-test leak deltas and the attribution
    table in the session summary, and FAILS the session when a test
    retains more than ``HBMWATCH_TEST_TOL_MB`` (default 32) or the
    whole session grows past ``HBMWATCH_SESSION_TOL_MB`` (default 64)
    after teardown — a closed engine must actually release its bytes.

Snapshots ``gc.collect()`` first: donated/dropped buffers are freed at
object collection, and without the collect a snapshot would read
garbage-pending bytes as leaks. Then they wait for the process to go
quiet: a serving loop still holds the outputs of the decode blocks it
had in flight when the caller's stream ended, for the milliseconds it
takes to reap them, and a read in that window is a few hundred bytes
off in either direction. A snapshot is the first reading that three
reads in a row, 4 ms apart, agree on.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any, Callable

__all__ = ["HBMLeak", "HBMWatch", "attribution", "live_device_bytes"]

_MB = 1 << 20


def live_device_bytes() -> int:
    """Total bytes of live, non-deleted jax arrays — ground truth for
    what the process holds on device right now."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            if getattr(a, "is_deleted", None) is not None and a.is_deleted():
                continue  # donated-away: no backing buffer
            total += int(a.nbytes)
        except Exception:
            continue
    return total


def attribution() -> dict:
    """Reconcile declared subsystem bytes against live ground truth."""
    from ..tpu import hbm

    accounted = hbm.live_bytes()
    live = live_device_bytes()
    return {
        "live_bytes": live,
        "accounted": accounted,
        "unattributed": live - sum(accounted.values()),
    }


class HBMLeak(AssertionError):
    """Raised on steady-state growth (or by the session gate)."""


def _fmt_mb(n: int) -> str:
    return f"{n / _MB:+.2f} MiB" if n < 0 else f"{n / _MB:.2f} MiB"


class HBMWatch:
    """Snapshot-based live-buffer tracker."""

    def __init__(self, name: str = "hbmwatch"):
        self.name = name
        self.deltas: dict[str, int] = {}  # nodeid -> retained bytes

    def snapshot(self) -> int:
        gc.collect()
        seen, agree = live_device_bytes(), 1
        deadline = time.monotonic() + 2.0  # a busy process never agrees
        while agree < 3 and time.monotonic() < deadline:
            time.sleep(0.004)
            now = live_device_bytes()
            seen, agree = now, agree + 1 if now == seen else 1
        return seen

    def assert_flat(self, fn: Callable[[], Any], *, warmup: int = 2,
                    iters: int = 3, tol_bytes: int = 0,
                    label: str = "") -> int:
        """Run ``fn`` ``warmup`` times, snapshot, run ``iters`` more,
        and raise :class:`HBMLeak` if live device bytes grew past
        ``tol_bytes``. Returns the observed growth (<= tol on
        success). Warmup absorbs one-time growth — jit compiles
        materializing constants, pools/caches filling to capacity —
        so the assertion is about STEADY STATE, exactly the regime a
        serving process lives in."""
        for _ in range(max(0, warmup)):
            fn()
        base = self.snapshot()
        for _ in range(max(1, iters)):
            fn()
        grown = self.snapshot() - base
        if grown > tol_bytes:
            att = attribution()
            raise HBMLeak(
                f"{self.name}: steady-state device-byte growth"
                f"{' in ' + label if label else ''}: {_fmt_mb(grown)} "
                f"over {iters} iteration(s) after {warmup} warmup(s) "
                f"(tol {_fmt_mb(tol_bytes)})\n"
                f"  live={_fmt_mb(att['live_bytes'])} "
                f"accounted={ {k: _fmt_mb(v) for k, v in att['accounted'].items()} } "
                f"unattributed={_fmt_mb(att['unattributed'])}")
        return grown

    def record(self, nodeid: str, delta: int) -> None:
        self.deltas[nodeid] = delta

    def summary(self) -> dict:
        top = sorted(self.deltas.items(), key=lambda kv: -kv[1])[:10]
        return {
            "watch": self.name,
            "tests": len(self.deltas),
            "top_deltas": top,
            **attribution(),
        }


# -- pytest session mode ------------------------------------------------------
# Registered by tests/conftest.py under --hbmwatch, or standalone via
# `pytest -p gofr_tpu.testutil.hbmwatch --hbmwatch` (what the
# seeded-leak self-test uses, where no repo conftest is in scope).

try:
    import pytest
except ImportError:  # pragma: no cover — production import path
    pytest = None


if pytest is not None:
    class SessionWatchPlugin:
        def __init__(self) -> None:
            self.watch = HBMWatch("pytest-session")
            self.test_tol = int(float(os.environ.get(
                "HBMWATCH_TEST_TOL_MB", "32")) * _MB)
            self.session_tol = int(float(os.environ.get(
                "HBMWATCH_SESSION_TOL_MB", "64")) * _MB)
            self.start: int | None = None

        @pytest.hookimpl(hookwrapper=True)
        def pytest_runtest_protocol(self, item, nextitem):
            before = self.watch.snapshot()
            if self.start is None:
                self.start = before
            yield
            self.watch.record(item.nodeid,
                              self.watch.snapshot() - before)

        def pytest_sessionfinish(self, session, exitstatus):
            end = self.watch.snapshot()
            start = self.start if self.start is not None else end
            s = self.watch.summary()
            print(f"\nhbmwatch: {s['tests']} test(s), live device bytes "  # noqa: T201
                  f"{_fmt_mb(start)} -> {_fmt_mb(end)} "
                  f"(session delta {_fmt_mb(end - start)})")
            acc = s["accounted"]
            print("hbmwatch attribution: " + (", ".join(  # noqa: T201
                f"{k}={_fmt_mb(v)}" for k, v in acc.items()) or "(empty)")
                + f"; unattributed={_fmt_mb(s['unattributed'])}")
            for nodeid, d in s["top_deltas"]:
                if d > 0:
                    print(f"hbmwatch delta: {_fmt_mb(d):>12}  {nodeid}")  # noqa: T201
            failures = []
            leakers = [(n, d) for n, d in self.watch.deltas.items()
                       if d > self.test_tol]
            if leakers:
                lines = "\n".join(f"  {_fmt_mb(d)}  {n}"
                                  for n, d in leakers)
                failures.append(
                    f"test(s) retained live device bytes past "
                    f"{_fmt_mb(self.test_tol)}:\n{lines}")
            if end - start > self.session_tol:
                failures.append(
                    f"session live device bytes grew {_fmt_mb(end - start)} "
                    f"(tol {_fmt_mb(self.session_tol)}) — something "
                    f"closed did not release its buffers")
            if failures:
                raise HBMLeak("hbmwatch: " + "\n\n".join(failures))

    def pytest_addoption(parser):  # standalone -p loading
        try:
            parser.addoption(
                "--hbmwatch", action="store_true", default=False,
                help="snapshot live device bytes around every test "
                     "(jax.live_arrays + the hbm accounting registry); "
                     "print per-test leak deltas and FAIL the session "
                     "on retained growth — the memory sibling of "
                     "--lockwatch")
        except ValueError:
            pass  # tests/conftest.py already registered it

    def pytest_configure(config):
        install_session_watch(config)

    def install_session_watch(config) -> None:
        """Idempotent: register the session plugin when --hbmwatch is
        on (called from the standalone plugin hook AND from
        tests/conftest.py)."""
        try:
            enabled = config.getoption("--hbmwatch")
        except ValueError:
            enabled = False
        if enabled and not config.pluginmanager.has_plugin(
                "hbmwatch-session"):
            plugin = SessionWatchPlugin()
            config._hbmwatch = plugin
            config.pluginmanager.register(plugin, "hbmwatch-session")
