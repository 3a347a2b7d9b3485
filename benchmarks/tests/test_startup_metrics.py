"""The start-up readers on a recorded ``stats()["startup"]``: each reads
the program's own account (gofr_tpu/observe/startup.py) and nothing where
the program has none, as the parent of the PR that brought it."""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402  (benchmarks/run.py)

GB = 10 ** 9
# the harness's flow: ready without a warm-up at 130 s, the harness's own
# gen.warmup() from 131 to 151 s, a warm-up while serving much later
STARTUP = {
    "t_start": 110.0, "t_ready": 130.0, "t_warm": 151.0,
    "phases": [
        {"name": "configure", "t0": 110.0, "seconds": 1.0,
         "bytes_in_use": 0, "peak_bytes": 0},
        {"name": "weights", "t0": 111.0, "seconds": 12.0,
         "bytes_in_use": 7 * GB, "peak_bytes": 8 * GB, "leaves": 11},
        {"name": "allocate", "t0": 123.0, "seconds": 4.0, "tag": "cache",
         "bytes_in_use": 12 * GB, "peak_bytes": 12 * GB},
        {"name": "configure", "t0": 127.0, "seconds": 0.5,
         "bytes_in_use": 12 * GB, "peak_bytes": 12 * GB},
        {"name": "allocate", "t0": 127.5, "seconds": 1.5, "tag": "pool",
         "bytes_in_use": 13 * GB, "peak_bytes": 13 * GB},
        {"name": "programs", "t0": 129.0, "seconds": 1.0,
         "bytes_in_use": 13 * GB, "peak_bytes": 13 * GB},
        {"name": "ready", "t0": 130.0, "seconds": 0.0,
         "bytes_in_use": 13 * GB, "peak_bytes": 13 * GB},
        {"name": "warmup", "t0": 131.0, "seconds": 20.0, "pass": 0,
         "bytes_in_use": 13 * GB, "peak_bytes": 15 * GB},
        {"name": "allocate", "t0": 400.0, "seconds": 9.0, "tag": "pool",
         "bytes_in_use": 13 * GB, "peak_bytes": 15 * GB},
        {"name": "warmup", "t0": 500.0, "seconds": 7.0, "pass": 1,
         "bytes_in_use": 13 * GB, "peak_bytes": 15.5 * GB},
    ],
    "warmup": [
        {"program": "_prefill_jit", "shape": [1, 64], "pass": 0,
         "seconds": 2.0, "compile_seconds": 1.25, "hits": 1, "misses": 0,
         "peak_bytes": 14 * GB},
        {"program": "_step_jit", "shape": [40, 4, "host carry"], "pass": 0,
         "seconds": 9.0, "compile_seconds": 2.5, "hits": 0, "misses": 1,
         "peak_bytes": 15 * GB},
        {"program": "_step_jit", "shape": [40, 4, "host carry"], "pass": 1,
         "seconds": 3.0, "compile_seconds": 0.75, "hits": 0, "misses": 0,
         "peak_bytes": 15.5 * GB},
    ],
    "cache": {"hits": 30, "misses": 1, "programs": 40},
    "missed": ["_step_jit(40, 4, 'host carry') jit(_step_fn)"],
    "missed_later": [],
}

# run.py's T0 is t_open - setup_s = 100; the ramp starts at 240 - 20
WANT = {
    "setup.before_engine_s": 10.0,
    "setup.weights_s": 12.0,
    "setup.allocate_s": 5.5,           # not the reallocation at 400 s
    "setup.warmup_s": 20.0,            # the first warm-up alone
    "setup.warmup_compile_s": 3.75,    # its records alone
    "setup.cache_misses": 1.0,
    "setup.after_ready_s": 69.0,       # 240 - 20 - 151
    "hbm.startup_peak_gb": 15.0,
    "hbm.peak_gb": 15.5,
}


def _ctx(startup):
    stats = {"slots": 40}
    if startup is not None:
        stats["startup"] = startup
    return SimpleNamespace(
        traffic_name="batch-sat", engine_stats=stats, t_open=240.0,
        setup_s=140.0, traffic={"ramp_s": 20.0},
        memory=[{"peak_bytes_in_use": 15.5 * GB, "bytes_in_use": 13 * GB},
                {"peak_bytes_in_use": 15.25 * GB}])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_the_recorded_account(name):
    assert run.read_metric(name, _ctx(STARTUP)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(set(WANT) - {"hbm.peak_gb"}))
def test_a_program_without_the_account_reads_nothing(name):
    assert run.read_metric(name, _ctx(None)) is None
    # nor does an engine that was built and never began a phase
    assert run.read_metric(name, _ctx(dict(
        STARTUP, t_start=None, t_ready=None, t_warm=None, phases=[],
        warmup=[]))) is None


def test_where_nothing_was_warmed_set_up_ends_at_ready():
    ctx = _ctx(dict(STARTUP, t_warm=None, phases=STARTUP["phases"][:7],
                    warmup=[]))
    assert run.read_metric("setup.after_ready_s", ctx) == \
        pytest.approx(240.0 - 20.0 - 130.0)
    assert run.read_metric("setup.allocate_s", ctx) == pytest.approx(5.5)
    for name in ("setup.warmup_s", "setup.warmup_compile_s",
                 "hbm.startup_peak_gb"):
        assert run.read_metric(name, ctx) is None


def test_the_parts_and_the_ramp_come_to_setup_s():
    """before + weights + allocate + warmup + after + ramp, with the
    configure and programs phases and the second between ready and the
    harness's warm-up as the remainder."""
    ctx = _ctx(STARTUP)
    parts = sum(run.read_metric(n, ctx) for n in (
        "setup.before_engine_s", "setup.weights_s", "setup.allocate_s",
        "setup.warmup_s", "setup.after_ready_s"))
    assert ctx.setup_s - (parts + 20.0) == pytest.approx(1.5 + 1.0 + 1.0)


def test_the_new_metrics_are_appended_to_per_layer_in_this_order():
    """Nine entries in a row, after what was there before them (Laguna's
    readers); later PRs append behind them."""
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("setup.before_engine_s")
    assert first == names.index("decode_step_roofline.laguna") + 1
    last = bench["per_layer"][first:first + 9]
    assert [m["name"] for m in last] == [
        "setup.before_engine_s", "setup.weights_s", "setup.allocate_s",
        "setup.warmup_s", "setup.warmup_compile_s", "setup.cache_misses",
        "setup.after_ready_s", "hbm.startup_peak_gb", "hbm.peak_gb"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in last[:7]:
        assert (m["moves"], m["layer"]) == ("setup_s", "start-up")
        assert m["workloads"] == by_name["setup.compile_s"]["workloads"]
    for m in last[7:]:
        assert m["moves"] == "out_tok_s"
        assert m["workloads"] == by_name["hbm.in_use_gb"]["workloads"]
