#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and everything that belongs to it from
files found by name (see ``harness/catalog.py``), starts the system through
the cell's topology, offers the cell's traffic for ``--seconds``, checks the
outputs and prints ONE JSON object as the last line of standard output. This
process never imports jax: the server, the float32 reference and the trace
reduction are children. There is no CPU fallback: without a TPU the run
fails and prints no result. Takes no notice of ``BENCH_RUN``.
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.catalog import BenchError  # noqa: E402
from benchmarks.harness.cell import run_cell  # noqa: E402


def compared(line) -> str:
    """Every number ``correct`` was decided from, beside its limit: the last
    lines of standard error in every run, so that the record of a run that
    is not correct says which comparison it failed."""
    sample, checks = line["checks"]["sample"], line["checks"]
    tol = sample["tolerances"]
    rows = [("failed requests", line["failed"], 0),
            ("rel_rms_diff", sample["rel_rms_diff"], tol["rel_rms"]),
            ("rel_max_diff", sample["rel_max_diff"], tol["rel_max"]),
            ("rel_tie_gap", sample["rel_tie_gap"], tol["rel_tie"]),
            ("compiled_in_window", checks["compiled_in_window"], 0),
            ("compile_seconds_in_window",
             checks["compile_seconds_in_window"], 0)]
    return "\n".join(f"compared {name}: {value:.6g} (limit {limit:g})"
                     for name, value, limit in rows) \
        + f"\ncorrect: {str(line['correct']).lower()}"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--probe", action="store_true",
                   help="builder's tool: also score the sample under two "
                        "broken models (see harness/reference.py)")
    a = p.parse_args(argv)
    try:
        code, line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                              _STARTED, probe=a.probe)
    except BenchError as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    print(compared(line), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
