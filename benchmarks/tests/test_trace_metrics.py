"""The readers of the program's own account of its time (timeline events
of kind loop, gap, first and decode), on a synthetic run:
python -m pytest benchmarks/tests -q

A timeline event is (seq, monotonic start, duration or None, kind, a, b,
c, d), as gofr_tpu/observe/timeline.py writes it.
"""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run  # noqa: E402  (benchmarks/run.py)


def _loop(t0, t1, phase, n=0):
    return (0, t0, t1 - t0, "loop", phase, n, None, None)


def _gap(t0, t1, slack=0.0):
    return (0, t0, t1 - t0, "gap", slack, None, None, None)


def _decode(t0, t1, slots, live=None):
    return (0, t0, t1 - t0, "decode", slots, 4, live, None)


def _first(rid, headers, submit, first_put, got, write1):
    return (0, write1, None, "first", rid, "ab" * 16,
            (headers, submit, first_put, got),
            (got, got, got, write1))


def _ctx(timeline, span=(10.0, 12.0), traffic="batch-sat"):
    return SimpleNamespace(
        timeline=timeline, traffic_name=traffic, slots=4,
        trace={"span": span} if span else None,
        engine_stats={"max_seq": 100})


# the traced span is 10.0-12.0 s: the loop waits, admits, dispatches,
# fetches, delivers; the device is dry for 0.1 s outside admission, for
# 0.3 s inside it, and for 0.5 s that start before the span opens
TIMELINE = [
    _loop(9.0, 10.5, "wait"), _loop(10.5, 11.0, "admit", 2),
    _loop(11.0, 11.1, "dispatch"), _loop(11.1, 11.6, "fetch"),
    _loop(11.6, 11.9, "deliver"), _loop(11.9, 12.5, "park"),
    _gap(9.7, 10.2), _gap(10.45, 10.55), _gap(10.7, 10.95, 0.001),
    _decode(10.0, 11.0, (0, 1), live=100), _decode(11.0, 12.0, (0, 1, 2),
                                                   live=200),
]


@pytest.mark.parametrize("name, value", [
    # 0.2 (clipped) + 0.1 + 0.25 of 2.0 s
    ("sched.dry_pct", 27.5),
    # 0.05 of the second gap and all 0.25 of the third lie inside admit
    ("sched.dry_admit_pct", 15.0),
    # wait 0.5 + fetch 0.5 + park 0.1 of 2.0 s are not the host's work
    ("sched.host_busy_pct", 45.0),
    # (100 x 1 s + 200 x 1 s) / 2 s = 150 of 4 x 100 positions
    ("kv.pool_fill_pct", 37.5),
])
def test_reader_on_the_synthetic_window(name, value):
    assert run.read_metric(name, _ctx(TIMELINE)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["sched.dry_pct", "sched.dry_admit_pct",
                                  "sched.host_busy_pct"])
def test_a_name_split_by_traffic_mix_has_the_one_reader(name):
    ctx = _ctx(TIMELINE, traffic="chat-rate")
    assert run.read_metric(name + ".chat-rate", ctx) == \
        run.read_metric(name, ctx)


def test_first_events_give_the_transport_medians():
    firsts = [_first(i, 20.0, 20.0 + ingress, 20.5, 20.5002, 20.5 + write)
              for i, (ingress, write) in enumerate(
                  [(0.001, 0.0004), (0.002, 0.0005), (0.009, 0.0030)])]
    # a stream that is no generation has no engine stamps: left out
    firsts.append(_first(None, 20.0, None, None, 20.1, 20.2))
    ctx = _ctx(firsts, traffic="chat-rate")
    assert run.read_metric("transport.first_write_ms.chat-rate", ctx) == \
        pytest.approx(0.5)
    assert run.read_metric("transport.ingress_ms.chat-rate", ctx) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", [
    "sched.dry_pct", "sched.dry_admit_pct", "sched.host_busy_pct",
    "transport.first_write_ms.chat-rate", "transport.ingress_ms.chat-rate",
    "kv.pool_fill_pct"])
def test_a_program_without_the_events_reads_nothing(name):
    """The parent commit writes no loop or first events and no live count;
    with TPU_TIMELINE=0 there is no event at all; an untraced run has no
    span. The reader then returns None and the line leaves the metric out
    (only sched.dry_pct finds the parent's gap events, in their old
    meaning)."""
    parent = [_gap(10.2, 10.4)[:4] + (None,) * 4,
              (0, 10.0, 1.0, "decode", (0, 1), 4, None, None)]
    ctx = _ctx(parent, traffic="chat-rate")
    if name == "sched.dry_pct":
        assert run.read_metric(name, ctx) == pytest.approx(10.0)
    else:
        assert run.read_metric(name, ctx) is None
    assert run.read_metric(name, _ctx([], traffic="chat-rate")) is None
    if name.startswith("sched."):
        assert run.read_metric(
            name, _ctx(TIMELINE, span=None, traffic="chat-rate")) is None
