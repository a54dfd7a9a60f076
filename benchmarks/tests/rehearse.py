#!/usr/bin/env python3
"""CPU rehearsal of the harness, end to end, at a tiny size.

    python benchmarks/tests/rehearse.py [--seconds 4]

Runs the throw-away cells of ``tests/rehearsal/`` (configurations, mixes, a
generator, a topology, a float32 reference of another architecture and a
metric of each kind that exist ONLY as new files there, found by name through
a manifest of their own) with ``--trace 0`` and ``--trace 1``,
children on the CPU. It can never print a passing line: every result says
``"correct": false, "rehearsal": true`` and the script exits 2 when all it
rehearsed went well, 1 otherwise. Times it prints are CPU times and mean
nothing.
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness.catalog import BenchError, Catalog  # noqa: E402
from benchmarks.harness.cell import run_cell  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seconds", type=float, default=4.0)
    p.add_argument("--seed", type=int, default=2147483659)  # > 2**31
    a = p.parse_args()
    root = os.path.join(HERE, "rehearsal")
    cat = Catalog(os.path.join(root, "BENCHMARK.json"), roots=[root])
    ok = True
    for workload in ("tiny-qwen.drip", "tiny-qwen.loop", "tiny-moe.drip"):
        for trace in (False, True):
            try:
                code, line = run_cell(workload, a.seed, a.seconds, trace,
                                      time.monotonic(), catalog=cat,
                                      rehearsal=True)
            except BenchError as e:
                print(f"REHEARSAL FAILED {workload} trace={int(trace)}: {e}")
                ok = False
                continue
            want = {m["name"] for m in cat.metrics(
                "per_layer" if trace else "end_to_end", workload)}
            got = set(line["metrics"])
            # a reader that finds nothing returns nothing: device metrics
            # have nothing to read in a CPU trace
            missing = want - got - ({"device.idle_share"} if trace else set())
            good = (code == 2 and line["correct"] is False
                    and line["rehearsal"] is True and line["failed"] == 0
                    and line["attempted"] > 0 and not missing
                    and line["checks"]["sample"]["ok"]
                    and line["checks"]["compiled_in_window"] == 0)
            ok = ok and good
            print(("rehearsed " if good else "REHEARSAL FAILED ")
                  + json.dumps(line))
    print(f"rehearsal {'passed' if ok else 'FAILED'} in "
          f"{time.monotonic() - _STARTED:.0f}s (CPU; not a benchmark result)")
    return 2 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
