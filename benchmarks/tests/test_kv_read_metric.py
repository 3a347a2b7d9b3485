"""attn.kv_read_pct from recorded decode events: the program's own count
of the positions a decode step's attention fetches, under both names the
benchmark gives it, and nothing where the events lack the field."""

import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import run  # noqa: E402  (benchmarks/run.py)


def _decode(t0, t1, slots, live, fetched):
    return (0, t0, t1 - t0, "decode", slots, 4, live, fetched)


def _ctx(timeline, traffic):
    return SimpleNamespace(timeline=timeline, traffic_name=traffic, slots=4,
                           trace={"span": (10.0, 12.0)},
                           engine_stats={"max_seq": 100})


CELLS = [("attn.kv_read_pct", "batch-sat"),
         ("attn.kv_read_pct.chat-rate", "chat-rate")]


@pytest.mark.parametrize("name,traffic", CELLS)
def test_reads_the_fetched_positions(name, traffic):
    """Two blocks of 1 s and 3 s fetching 128 and 64 of 400 reserved
    positions: (128 + 3 * 64) / 4 / 400."""
    timeline = [_decode(10.0, 11.0, (0, 1), 100, 128),
                _decode(11.0, 14.0, (0,), 50, 64),
                (0, 10.5, 0.1, "gap", 0.0, None, None, None)]
    assert run.read_metric(name, _ctx(timeline, traffic)) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("name,traffic", CELLS)
def test_the_reference_path_reads_all_that_is_reserved(name, traffic):
    timeline = [_decode(10.0, 11.0, (0, 1), 100, 400)]
    assert run.read_metric(name, _ctx(timeline, traffic)) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("name,traffic", CELLS)
@pytest.mark.parametrize("timeline", [
    [],                                                # TPU_TIMELINE=0
    [(0, 10.0, 1.0, "decode", (0, 1), 4, 100, None)],  # the parent commit
    [(0, 10.0, 1.0, "decode", (0, 1), 4, 100)],        # an older event
], ids=["no-events", "no-field", "short-event"])
def test_a_program_without_the_field_reads_nothing(name, traffic, timeline):
    assert run.read_metric(name, _ctx(timeline, traffic)) is None
