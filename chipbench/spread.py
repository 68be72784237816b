"""How widely a cell's runs spread: the number a bound is set from.

    python3 chipbench/spread.py <set 1 .jsonl> <set 2 .jsonl>

Each file holds the result lines of one set of runs of one cell, one
per line.  A spread is the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; a metric's spread is the wider of the two sets'.  A bound is
about five times the widest spread over the cells, and never under 1%.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list) -> int:
    sets = []
    for path in paths:
        with open(path) as f:
            sets.append([json.loads(line) for line in f if line.strip()])
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    for name in names:
        row = []
        for runs in sets:
            values = [r["metrics"][name]["value"] for r in runs
                      if name in r["metrics"]]
            row.append((statistics.median(values), spread(values), values))
        widest = max(s for _, s, _ in row)
        print(f"{name}: widest spread {100 * widest:.2f}% -> bound about "
              f"{max(0.01, 5 * widest):.3f}")
        for i, (median, s, values) in enumerate(row, 1):
            print(f"  set {i}: median {median:.4f} spread {100 * s:.2f}% "
                  f"values {[round(v, 3) for v in values]}")
    bad = [r for runs in sets for r in runs
           if not r["correct"] or r["failed"]]
    print(f"runs: {[len(s) for s in sets]}, not correct or with "
          f"failures: {len(bad)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
