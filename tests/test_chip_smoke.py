"""chip_smoke.py fails without a chip — after its request logic ran — the
compile cache lands where it is told, and an unbuildable model is a
start-up failure."""

import importlib.util
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from gofr_tpu import App, compile_cache
from gofr_tpu.config import MapConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_on_cpu_fails_after_serving():
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env.update(JAX_PLATFORMS="cpu", TPU_MODEL="tiny",
               TPU_SEQ_BUCKETS="32,64", TPU_BATCH_BUCKETS="1",
               LOG_LEVEL="ERROR")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stderr[-3000:]
    assert r.stdout == ""  # no result line without a chip
    line = next(l for l in reversed(r.stderr.splitlines())
                if l.startswith("chip_smoke FAILED in ['device', 'kernels']"))
    summary = json.loads(line.split(": ", 1)[1])
    assert summary["ok"] is False and summary["claim"] is None
    assert summary["device"]["platform"] == "cpu"
    assert "platform is 'cpu'" in summary["phases"]["device"]["error"]
    server = summary["phases"]["server"]
    assert server["ok"], server
    assert server["max_in_flight"] >= 4 and server["prefix_cache"]["hits"] >= 1
    assert summary["tokens"] == server["streams"] * 16


def test_result_line_holds_ok_and_device_only():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line({"ok": True, "device": dict(device),
                                   "phases": {}, "tokens": 128,
                                   "claim": None})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert compile_cache.configure() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == "untouched"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.configure() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unbuildable_model_fails_startup():
    with pytest.raises(KeyError, match="no-such-model"):
        App(MapConfig({"TPU_MODEL": "no-such-model", "LOG_LEVEL": "FATAL"}))


def test_one_compile_listener_counts_and_marks_the_timeline():
    """compile_cache.clock() is the process's one compile listener:
    seconds, programs, hits and misses, a log of (time, seconds), and a
    ``compile`` instant on the serving timeline it is attached to."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.observe import Timeline

    clock = compile_cache.clock()
    assert compile_cache.clock() is clock  # one, however often asked
    was = clock.timeline
    tl = clock.timeline = Timeline(capacity=64)
    try:
        before = clock.snapshot()
        assert set(before) == {"seconds", "programs", "hits", "misses"}
        t0 = time.monotonic()

        def fresh(x):  # a program nobody compiled before
            return jnp.tanh(x * 3.25 + 0.125).sum()

        jax.jit(fresh)(jnp.arange(7.0)).block_until_ready()
        after = clock.snapshot()
    finally:
        clock.timeline = was
    assert after["programs"] >= before["programs"] + 1
    assert after["seconds"] > before["seconds"]
    new = [(t, s) for t, s in clock.log if t >= t0]
    assert new and all(s > 0 for _, s in new)
    marks = [e for e in tl.events() if e[3] == "compile"]
    assert len(marks) == after["programs"] - before["programs"]
    assert sum(e[4] for e in marks) == pytest.approx(
        after["seconds"] - before["seconds"], abs=1e-4)
    row = next(e for e in tl.chrome_trace()["traceEvents"]
               if e.get("cat") == "compile")
    assert row["name"].startswith("compile ") and row["tid"] == 3


def test_chip_smoke_keeps_no_copy_of_the_clock_or_the_parser():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    assert "class CompileClock" not in src
    assert "compile_cache.clock()" in src and "parse_prometheus" in src
