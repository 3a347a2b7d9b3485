"""The readers of the stall watchdog's series on a hand-built ``ctx``
(``sched.stall_s``, ``sched.loop_cpu_pct``): each reads the change of
one counter between /metrics at the window's opening and at its close,
nothing where the program has no such series (the parent of the PR that
brought them; a /proc that cannot be read), and 0.0, not nothing, in a
clean window."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402  (benchmarks/run.py)

SERIES = {
    "sched.stall_s": "app_tpu_loop_stall_seconds_total",
    "sched.loop_cpu_pct": "app_tpu_loop_cpu_seconds_total",
}
# what the watchdog's counters read at the two ends of a 50 s window
# with one silence of 1.99 s in it
OPEN = {"app_tpu_loop_stall_seconds_total": 1.25,
        "app_tpu_loop_stall_total": 1.0,
        "app_tpu_loop_cpu_seconds_total": 40.0,
        "app_tpu_ttft_duration_count": 10.0}
CLOSE = {"app_tpu_loop_stall_seconds_total": 3.24,
         "app_tpu_loop_stall_total": 2.0,
         "app_tpu_loop_cpu_seconds_total": 50.5,
         "app_tpu_ttft_duration_count": 380.0}
WANT = {"sched.stall_s": 1.99, "sched.loop_cpu_pct": 21.0}


def ctx(prom_open, prom_close, traffic="batch-sat", seconds=50.0):
    return SimpleNamespace(prom_open=prom_open, prom_close=prom_close,
                           seconds=seconds, traffic_name=traffic,
                           timeline=[], trace=None)


@pytest.mark.parametrize("name", sorted(SERIES))
@pytest.mark.parametrize("traffic", ["batch-sat", "chat-rate"])
def test_the_parent_has_no_such_series_and_reads_nothing(name, traffic):
    """A program without the watchdog renders none of the names: the
    reader says None and the metric is left out of the line, not 0."""
    if traffic == "chat-rate":
        name += ".chat-rate"
    bare = {"app_tpu_ttft_duration_count": 380.0}
    assert run.read_metric(name, ctx(bare, bare, traffic)) is None


@pytest.mark.parametrize("name", sorted(SERIES))
def test_a_clean_window_reads_zero_not_nothing(name):
    flat = dict(CLOSE)
    assert run.read_metric(name, ctx(flat, flat)) == 0.0


@pytest.mark.parametrize("name", sorted(SERIES))
def test_each_reads_its_counters_change_over_the_window(name):
    assert run.read_metric(name, ctx(OPEN, CLOSE)) == \
        pytest.approx(WANT[name])
    # a series that first appears inside the window counts from 0
    late = {k: v for k, v in OPEN.items() if k != SERIES[name]}
    whole = CLOSE[SERIES[name]]
    assert run.read_metric(name, ctx(late, CLOSE)) == pytest.approx(
        whole if name.endswith("_s") else 100.0 * whole / 50.0)


@pytest.mark.parametrize("burned,want", [
    (10.5, 21.0),     # what the chip reads: a fifth of a core
    (50.0, 100.0),    # a thread busy all through
    (200.0, 400.0),   # the whole process's CPU under the thread's name,
])                    # or ticks in the wrong unit: it shows, uncut
def test_a_share_is_reported_as_read_and_never_cut_to_fit(burned, want):
    close = dict(CLOSE, app_tpu_loop_cpu_seconds_total=40.0 + burned)
    assert run.read_metric("sched.loop_cpu_pct", ctx(OPEN, close)) == \
        pytest.approx(want)
    assert run.read_metric("sched.loop_cpu_pct",
                           ctx(OPEN, close, seconds=0.0)) is None


def test_one_missing_series_leaves_the_others_their_numbers():
    """Where /proc could not be read the watchdog still counts stalls."""
    close = {k: v for k, v in CLOSE.items() if "stall" in k}
    got = {n: run.read_metric(n, ctx(OPEN, close)) for n in SERIES}
    assert got == {"sched.stall_s": pytest.approx(1.99),
                   "sched.loop_cpu_pct": None}


def test_they_are_in_the_benchmark_for_the_cells_that_report_what_they_move():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    nine = by_name["sched.dry_pct"]["workloads"]
    for name in SERIES:
        m, chat = by_name[name], by_name[name + ".chat-rate"]
        assert m["layer"] == chat["layer"] == "generation scheduler"
        assert m["source"] == chat["source"] == "program_counter"
        assert m["better"] == chat["better"] == "lower"
        assert (m["moves"], m["workloads"]) == ("out_tok_s", nine)
        assert (chat["moves"], chat["workloads"]) == (
            "tpot_p50_ms", ["mistral-7b-int8.chat-rate"])
        assert os.path.isfile(run.metric_file(name, "batch-sat"))
        assert run.metric_file(name + ".chat-rate", "chat-rate") == \
            run.metric_file(name, "batch-sat")
