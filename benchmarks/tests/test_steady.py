"""The steadiness statistic on hand-made sets (benchmarks/steady.py)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import steady  # noqa: E402


def test_the_run_farthest_from_the_median_is_left_out():
    # median 100.5; 110 is farthest; the five left span 99..102
    assert steady.spread([100, 101, 99, 102, 100, 110]) == \
        pytest.approx(3 / 100.5)
    # a far run BELOW the median goes the same way
    assert steady.spread([100, 101, 99, 102, 100, 80]) == \
        pytest.approx(3 / 100.0)
    # of three, the two nearest the median are left
    assert steady.spread([10.0, 10.1, 12.0]) == pytest.approx(0.1 / 10.1)
    # two far runs in a set do harm: only one is left out
    assert steady.spread([100, 100, 100, 100, 110, 110]) == \
        pytest.approx(10 / 100)
    with pytest.raises(ValueError):
        steady.spread([1.0, 2.0])


def test_the_verdict_over_two_sets_is_the_mean_against_half_the_bound():
    """PR 26's refusal, from the numbers in its reason: spreads of 0.553793
    and 0.427141 ms at a median of 17.6141 ms, against a bound of 5%."""
    mid = 17.6141

    def a_set(width):  # five runs spanning `width` around the median + one far
        return [mid - width / 2, mid, mid, mid, mid + width / 2, mid + 3.0]

    v = steady.verdict([a_set(0.553793), a_set(0.427141)], 0.05)
    assert v["spreads"] == pytest.approx([0.553793 / mid, 0.427141 / mid])
    assert v["mean_spread"] * mid == pytest.approx(0.490467)
    assert not v["steady"]
    assert v["share"] == pytest.approx(0.490467 / (0.05 * mid))
    # the same sets under a bound of 8% are steady
    assert steady.verdict([a_set(0.553793), a_set(0.427141)], 0.08)["steady"]
    # one set alone is judged by its own spread
    one = steady.verdict([a_set(0.3)], 0.05)
    assert one["steady"] and one["mean_spread"] == pytest.approx(0.3 / mid)


def test_the_command_reads_result_lines(tmp_path, capsys):
    for name, values in (("a.jsonl", [1480, 1482, 1479, 1481, 1478, 1400]),
                         ("b.jsonl", [1480, 1483, 1477, 1481, 1479, 1480])):
        with open(tmp_path / name, "w") as f:
            for v in values:
                f.write(json.dumps({"correct": True, "metrics": {
                    "out_tok_s": {"value": v, "unit": "tokens/s"},
                    "not_in_benchmark_json": {"value": 1, "unit": "x"}}})
                    + "\n")
    rc = steady.main(["steady.py", str(tmp_path / "a.jsonl"),
                      str(tmp_path / "b.jsonl")])
    out = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert rc == 0 and [o["metric"] for o in out] == ["out_tok_s"]
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bound = next(m["bound"] for m in json.load(f)["end_to_end"]
                     if m["name"] == "out_tok_s")
    assert out[0]["bound"] == bound and out[0]["steady"]
    assert out[0]["spreads"] == pytest.approx([4 / 1479.5, 4 / 1480])
