#!/usr/bin/env python3
"""Is a cell steady enough for its bound? The driver's test of a cell it
measures anew, as its refusals state it (PERF_LEDGER.jsonl, PR 26), so
that every ``benchmark`` PR reckons it the same way before it is sent.

Two sets of runs of the same code, on other seeds. A set's spread leaves
out the run farthest from its median; of the readings those words allow
this takes the harsher, the range of the runs that are left over the
median (of five in a set of six, of two in a set of three). The mean of
the two sets' spreads may be at most half of the bound.

    python3 benchmarks/steady.py <set 1>.jsonl [<set 2>.jsonl]

reads result lines of ``run.py`` (one a line, ``--trace 0``) and prints
the verdict for every end-to-end metric of BENCHMARK.json they report.
Stdlib only.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

TIGHT_SHARE = 0.5   # mean spread / bound: the driver's limit


def spread(values: list[float]) -> float:
    """Range of the runs left when the one farthest from the median is
    taken out, as a share of the median of all."""
    if len(values) < 3:
        raise ValueError("a spread needs three runs or more")
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return (max(kept) - min(kept)) / abs(mid)


def verdict(sets: list[list[float]], bound: float) -> dict:
    """``sets``: one or two lists of a metric's readings. ``share`` is the
    mean spread over the bound: steady at TIGHT_SHARE or under."""
    spreads = [spread(s) for s in sets]
    mean = sum(spreads) / len(spreads)
    return {"medians": [statistics.median(s) for s in sets],
            "spreads": spreads, "mean_spread": mean, "bound": bound,
            "share": mean / bound, "steady": mean <= TIGHT_SHARE * bound}


def read_sets(paths: list[str]) -> dict[str, list[list[float]]]:
    """{metric: [readings of set 1, of set 2]} from files of result lines."""
    out: dict[str, list[list[float]]] = {}
    for i, path in enumerate(paths):
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                for name, m in json.loads(line)["metrics"].items():
                    sets = out.setdefault(name, [[] for _ in paths])
                    sets[i].append(m["value"])
    return out


def main(argv: list[str]) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__, file=sys.stderr)
        return 2
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    steady = True
    for name, sets in sorted(read_sets(argv[1:]).items()):
        if name not in bounds:
            continue
        v = verdict(sets, bounds[name])
        steady = steady and v["steady"]
        print(json.dumps({"metric": name, **v}))
    return 0 if steady else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
