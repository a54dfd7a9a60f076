#!/usr/bin/env python3
"""Builder's tool, on the chip: what the profiler hook costs a traced run.

    python benchmarks/tests/trace_cost.py --workload <cell> [--seed 7] \\
        [--seconds 50] [--steps 48] [--out chiprun_out/trace_cost.json]

One ``--trace 1`` run of the cell through the harness as it is, and beside the
result line what the line does not say: the size of the ``.xplane.pb`` the
capture wrote, and the longest pause any client saw between two chunks of
one stream in the window (client clock), which is where a capture that stalls
the engine thread at its stop shows; and the server's own mean time to first
token over the window (delta sum / delta count of ``llm_ttft_seconds`` between
the harness's two scrapes) beside the sum of the five stage means, which a
program that has the stages must match; and the engine thread's seconds by
loop phase with its dispatch counts over the window. ``--steps`` overrides the mix's
``trace_steps`` for this run only (the traffic file stays as it is). Reads
nothing of the program but what the harness reads, so it runs the same on a
commit from before the hook was repaired. Not part of any check.
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

from benchmarks.harness import cell, launch, runner  # noqa: E402
from benchmarks.harness.catalog import BenchError  # noqa: E402
from benchmarks.harness.stages import (DISPATCHES, phase_seconds,  # noqa: E402
                                       stage_mean_ms)

STAGES = ("pre_engine", "queue", "lane_wait", "prefill", "post_engine")


def longest_pause(results):
    """-> (seconds, seconds into the window) of the longest gap between two
    consecutive chunks of one stream."""
    worst, at = 0.0, None
    for r in results:
        times = [t for t, _ in r.chunks]
        for a, b in zip(times, times[1:]):
            if b - a > worst:
                worst, at = b - a, a
    return worst, at


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--steps", type=int)
    p.add_argument("--out")
    a = p.parse_args()

    seen = {}
    drive, scrape = runner.drive_window, launch.scrape

    def keep_scrape(base):
        """The harness scrapes right before and right after the window (and
        samples inside it, which are not kept here)."""
        series = scrape(base)
        if "driving" not in seen:
            seen["before"] = series
        elif "window" in seen:
            seen.setdefault("after", series)
        return series

    async def keep_results(*args, **kw):
        seen["driving"] = True
        seen["window"] = await drive(*args, **kw)
        return seen["window"]

    runner.drive_window, launch.scrape = keep_results, keep_scrape
    if a.steps is not None:
        prepare = cell.prepare

        def with_steps(*args, **kw):
            su = prepare(*args, **kw)
            su.env["DYN_PROFILE_STEPS"] = str(a.steps)
            return su

        cell.prepare = with_steps
    try:
        code, line = cell.run_cell(a.workload, a.seed, a.seconds, True,
                                   _STARTED)
    except BenchError as e:
        print(f"trace_cost: run failed: {e}", file=sys.stderr)
        return 1
    window = seen["window"]
    pause, at = longest_pause(window["results"])
    profile = os.path.join(cell.SCRATCH, a.workload, "profile")
    sizes = [os.path.getsize(os.path.join(d, f))
             for d, _, fs in os.walk(profile) for f in fs
             if f.endswith(".xplane.pb")]
    n = launch.delta(seen["before"], seen["after"], "llm_ttft_seconds_count")
    stages = [stage_mean_ms(seen, s) for s in STAGES]
    out = {"workload": a.workload, "seed": a.seed, "steps": a.steps,
           "server_ttft_mean_ms": None if n <= 0 else 1e3 * launch.delta(
               seen["before"], seen["after"], "llm_ttft_seconds_sum") / n,
           "stage_means_sum_ms": (None if None in stages else sum(stages)),
           # the engine thread's seconds by loop phase over the whole window,
           # and what it dispatched: the host side of "where the time goes"
           "phase_seconds": phase_seconds(seen),
           "dispatches": {k: launch.delta(seen["before"], seen["after"],
                                          DISPATCHES, kind=k)
                          for k in ("prefill", "decode", "verify")},
           "trace_bytes": max(sizes, default=0),
           "longest_pause_s": pause,
           "longest_pause_at_s": None if at is None else at - window["t0"],
           "line": line}
    text = json.dumps(out)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
