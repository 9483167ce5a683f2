#!/usr/bin/env python3
"""Spreads of a `chip_runs.sh sets` phase, by the rule the bounds follow:

    python3 benchmarks/spread.py chiprun_out/<cell>/sets/summary.txt

For each end-to-end metric and each set (A, B: the same 6 seeds), the median
and the spread = (Q3 - Q1) / median with `statistics.quantiles(values, n=4)`;
a bound is about five times the widest spread over the cells, never under 1%.
"""
import json
import statistics
import sys


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / (statistics.median(values) or float("nan"))


def main(path):
    sets = {}
    with open(path) as f:
        lines = f.read().splitlines()
    for head, line in zip(lines[::2], lines[1::2]):
        tag = head.split()[1]
        try:
            result = json.loads(line)
        except ValueError:
            print(f"{tag}: no result line ({head})")
            continue
        if not result["correct"] or result["failed"]:
            print(f"{tag}: correct={result['correct']} "
                  f"failed={result['failed']}")
        for name, m in result["metrics"].items():
            sets.setdefault(tag[0], {}).setdefault(name, []).append(m["value"])
    for which, metrics in sorted(sets.items()):
        for name, values in metrics.items():
            if len(values) < 2:
                print(f"set {which} {name}: {values}")
                continue
            print(f"set {which} {name}: n={len(values)} median "
                  f"{statistics.median(values):.6g} spread "
                  f"{100 * spread(values):.3f}% min {min(values):.6g} max "
                  f"{max(values):.6g}  {[round(v, 3) for v in values]}")


if __name__ == "__main__":
    main(sys.argv[1])
