#!/usr/bin/env python3
"""Spreads of a set of runs, as the bounds are set from them.

    python3 benchmarks/onchip/tools/spread.py set1/*.out -- set2/*.out

Each file holds one run's standard output; its last line is the result.
Per set and metric: the median, and the spread: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median, over all runs and with the run farthest from the
median left out.  Also every run's ``correct`` and checked numbers.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def read(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    for k, files in enumerate(sets):
        runs = [r for r in map(read, files) if r]
        print(f"set {k + 1}: {len(runs)} runs of {len(files)}; correct "
              f"{[r['correct'] for r in runs]}")
        for r in runs:
            print("  checks", json.dumps(r["checks"]))
        names = sorted({n for r in runs for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in runs
                    if n in r["metrics"]]
            print(f"  {n}: median {statistics.median(vals):.6g} spread "
                  f"{spread(vals)} trimmed {spread(trimmed(vals))} "
                  f"values {[round(v, 4) for v in vals]}")


if __name__ == "__main__":
    main(sys.argv[1:])
