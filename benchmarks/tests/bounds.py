#!/usr/bin/env python3
"""Builder's tool: from the result lines ``series.py`` kept, the spread of
every end-to-end metric in each set of runs, the wider of a cell's two sets,
and the bound the contract's rule gives (about five times the widest spread
over the cells, never under 1 %, at most 10 %).

    python benchmarks/tests/bounds.py chiprun_out/<cell>.setA.jsonl \\
        chiprun_out/<cell>.setB.jsonl [more pairs ...]
"""

from __future__ import annotations

import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness.stats import spread  # noqa: E402


def main(paths) -> int:
    widest = {}
    for a, b in zip(paths[::2], paths[1::2]):
        sets = []
        for path in (a, b):
            with open(path) as f:
                sets.append([json.loads(l) for l in f if l.strip()])
        cell = sets[0][0]["workload"]
        for name in sets[0][0]["metrics"]:
            vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
            sp = [spread(v) for v in vals]
            med = [statistics.median(v) for v in vals]
            wide = max(x for x in sp if x is not None)
            widest[name] = max(widest.get(name, 0.0), wide)
            print(json.dumps({
                "cell": cell, "metric": name, "runs": [len(v) for v in vals],
                "medians": med, "spreads": sp,
                "second_vs_first": med[1] / med[0] - 1,
                "correct": [sum(r["correct"] for r in s) for s in sets]}))
    for name, w in widest.items():
        print(f"{name}: widest spread {w:.4f} -> bound "
              f"{min(0.1, max(0.01, round(5 * w, 3)))}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
