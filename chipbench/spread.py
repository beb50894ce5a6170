"""The spread of a set of runs, as the bounds in ``BENCHMARK.json`` are set
from it: for every metric in the result lines of each file (one file = one set
of runs of one cell, one line per run), the median, the spread (distance
between the first and third quartile of ``statistics.quantiles(v, n=4)`` over
the median) and the spread with the run farthest from the median left out.

    python3 chipbench/spread.py <set>/<cell>.jsonl [...]
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values) -> float:
    """The spread without the one run farthest from the median."""
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return spread(rest)


def of_lines(lines) -> dict:
    """``{metric: (median, spread, trimmed spread, values)}`` of result lines."""
    rows = [json.loads(line) for line in lines if line.strip()]
    out = {}
    for name in rows[0]["metrics"]:
        v = [r["metrics"][name]["value"] for r in rows]
        out[name] = (statistics.median(v), spread(v), trimmed(v), v)
    return out


def main(paths) -> int:
    for path in paths:
        with open(path) as fh:
            lines = fh.readlines()
        correct = [json.loads(line)["correct"] for line in lines if line.strip()]
        print(f"{path}: {len(correct)} runs, correct {sum(correct)}")
        for name, (mid, full, cut, v) in of_lines(lines).items():
            print(f"  {name:18s} median {mid:.6g}  spread {100 * full:.2f} %  trimmed {100 * cut:.2f} %  {[round(x, 4) for x in v]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
