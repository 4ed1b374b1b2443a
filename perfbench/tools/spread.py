"""Spreads of a cell's end-to-end metrics over sets of runs, for its bounds.

    python3 perfbench/tools/spread.py RESULT_FILE [RESULT_FILE ...]

Each file holds the output of one run (its last line is the result).  Files
are grouped into sets by the ``.<set>.`` part of their names
(``<workload>.<set>.<seed>.<trace>.out``).  For each metric and set it
prints the median and the spread: the distance between the first and third
quartiles as ``statistics.quantiles(values, n=4)`` gives them, as a share
of the median; also the spread with the run farthest from the median left
out, and five times the widest spread.
"""

from __future__ import annotations

import collections
import json
import os
import statistics
import sys


def spread(values: list[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def load(paths: list[str]) -> dict:
    sets: dict = collections.defaultdict(lambda: collections.defaultdict(list))
    for p in paths:
        parts = os.path.basename(p).split(".")
        with open(p) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            continue
        r = json.loads(lines[-1])
        for name, m in r["metrics"].items():
            sets[parts[1]][name].append(m["value"])
        sets[parts[1]]["correct"].append(1.0 if r["correct"] else 0.0)
    return sets


def main(paths: list[str]) -> int:
    sets = load(paths)
    names = sorted({n for s in sets.values() for n in s if n != "correct"})
    for name in names:
        widest = 0.0
        for tag, s in sorted(sets.items()):
            v = s.get(name, [])
            if len(v) < 3:
                continue
            sp, tsp = spread(v), spread(trimmed(v)) if len(v) > 3 else float("nan")
            widest = max(widest, sp)
            print(f"{name:>16} set {tag}: n={len(v)} median={statistics.median(v):.6g} "
                  f"spread={sp:.4%} trimmed={tsp:.4%} correct={sum(s['correct'])}/{len(s['correct'])}")
        print(f"{name:>16} widest spread {widest:.4%} -> 5x = {5 * widest:.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
