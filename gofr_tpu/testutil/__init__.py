"""Test utilities (reference: pkg/gofr/testutil/ — NewMockConfig
mock_config.go:11, NewMockLogger mock_logger.go:32, Stdout/StderrOutputForFunc
os.go:8-36)."""

from __future__ import annotations

import io
import threading
from contextlib import redirect_stderr, redirect_stdout
from typing import Callable

from ..config import MapConfig
from ..glog import Logger, LogLevel


def new_mock_config(values: dict[str, str] | None = None) -> MapConfig:
    return MapConfig(values)


class MockLogger(Logger):
    """Logger capturing output for assertions."""

    def __init__(self, level: LogLevel = LogLevel.DEBUG):
        self.out_buf = io.StringIO()
        self.err_buf = io.StringIO()
        super().__init__(level=level, out=self.out_buf, err=self.err_buf, pretty=False)

    @property
    def stdout(self) -> str:
        return self.out_buf.getvalue()

    @property
    def stderr(self) -> str:
        return self.err_buf.getvalue()


def new_mock_logger(level: LogLevel = LogLevel.DEBUG) -> MockLogger:
    return MockLogger(level)


def stdout_output_for(fn: Callable[[], None]) -> str:
    buf = io.StringIO()
    with redirect_stdout(buf):
        fn()
    return buf.getvalue()


def stderr_output_for(fn: Callable[[], None]) -> str:
    buf = io.StringIO()
    with redirect_stderr(buf):
        fn()
    return buf.getvalue()


def framework_threads() -> list[threading.Thread]:
    """Live threads the framework started. Every one — engine loops,
    breaker probes, JWKS refreshers, pollers — is named and must be
    stopped by its owner's close()/stop(); the test session and
    chip_smoke.py both fail on any that outlive their owner."""
    return [
        t for t in threading.enumerate()
        if t is not threading.main_thread() and t.is_alive()
        and (t.name.startswith(("cb-probe-", "gofr-", "jwks-refresh",
                                "zipkin-exporter", "remote-log-level"))
             or "probe" in t.name or "poller" in t.name)]
