#!/usr/bin/env python3
"""Spreads from result lines, by the rule the bounds are set by: for
each metric of each file given (one file per set of runs, the last JSON
object of each run on a line of its own), the median and the distance
between the first and third quartile (`statistics.quantiles(n=4)`) as a
share of the median.

    python3 benchmark/tools/spread.py set1.jsonl set2.jsonl
"""

import json
import statistics
import sys


def metrics_of(path):
    series = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith('{"correct"'):
                continue
            for name, m in json.loads(line)["metrics"].items():
                series.setdefault(name, []).append(m["value"])
    return series


def main(paths):
    for path in paths:
        for name, values in sorted(metrics_of(path).items()):
            med = statistics.median(values)
            if len(values) >= 2:
                q1, _, q3 = statistics.quantiles(values, n=4)
                spread = (q3 - q1) / med
            else:
                spread = float("nan")
            print(f"{path}\t{name}\tn={len(values)}\tmedian={med:.6g}\t"
                  f"iqr/median={spread:.4%}\tmin={min(values):.6g}\t"
                  f"max={max(values):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
