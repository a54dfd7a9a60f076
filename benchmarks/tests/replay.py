#!/usr/bin/env python3
"""Builder's tool, on the chip: how far do a cell's end-to-end metrics
spread from one window to the next, at one or more arrival rates, for the
price of one set-up?

    python benchmarks/tests/replay.py --workload <cell> --rates 5.6,4.5 \\
        --windows 6 --seconds 50 [--seed0 7] [--out chiprun_out/replay.json]

One server for the whole call. For each rate (``mix`` = the rate or client
count of the traffic file as it stands), ``--windows`` windows of the cell's
own mix, each with a seed of its own (other token ids, the same schedule, as
a run's), each followed to its end; after each the cell's end-to-end metrics
but ``setup_s``, read by the same ``e2e_metrics/<name>.py`` a run uses. Then
the spread of each metric over the windows of a rate (``stats.spread``).

A first estimate only: the windows share a process, a pool that keeps the
earlier windows' pages as prefix cache, and one start-up, where the check's
runs are processes of their own. The sets that a bound is set from are made
with ``series.py``. Not part of any check.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import measures, runner  # noqa: E402
from benchmarks.harness.catalog import Catalog  # noqa: E402
from benchmarks.harness.cell import (MODEL_NAME, _warm_set, bring_up,  # noqa: E402
                                     prepare)
from benchmarks.harness.stats import percentile, spread  # noqa: E402
from benchmarks.harness.traffic import RequestSource  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", default="mix")
    p.add_argument("--windows", type=int, default=6)
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--seed0", type=int, default=7)
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args()
    tree = os.path.join(HERE, "rehearsal")   # --rehearse: the CPU's tiny cells
    cat = (Catalog(os.path.join(tree, "BENCHMARK.json"), roots=[tree])
           if a.rehearse else Catalog())
    su = prepare(cat, a.workload, a.seed0, False, a.rehearse)
    e2e = [(m["name"], cat.module("e2e_metrics", m["name"]))
           for m in cat.metrics("end_to_end", a.workload)
           if m["name"] != "setup_s"]
    rows, summary = [], []
    handle, info = bring_up(su, a.rehearse)
    try:
        seed, first = a.seed0, True
        for rate in a.rates.split(","):
            params = dict(su.mix["arrivals"])
            if rate != "mix":
                key = "rate_per_s" if "rate_per_s" in params else "clients"
                params[key] = float(rate) if key == "rate_per_s" else int(rate)
            plan = su.gen.plan(params, a.seconds)
            values = {}
            for _ in range(a.windows):
                source = RequestSource(su.mix, su.config["vocab_size"],
                                       MODEL_NAME, seed, plan["block"])
                source.prepare(plan["blocks"])
                if first:
                    _warm_set(handle.base, source, seed, su.engine)
                    first = False
                w = asyncio.run(runner.drive_window(
                    su.gen, handle.base, source, params, a.seconds, seed,
                    int(su.mix.get("lengths_seed", 0)),
                    float(su.mix.get("drain_s", 30))))
                res = w["results"]
                run = {"results": res, "t0": w["t0"],
                       "seconds": float(a.seconds)}
                row = {"rate": rate, "seed": seed, "requests": len(res),
                       "failed": sum(not r.ok() for r in res),
                       "in_flight_at_close": w["in_flight_at_close"],
                       "drained_at_s": w["ended_s"],
                       "tpot_p50_ms": percentile(measures.tpot_ms(res), 50),
                       "lateness_p90_ms": percentile(
                           measures.lateness_ms(res), 90)}
                for name, mod in e2e:
                    row[name] = mod.reduce(run)
                    values.setdefault(name, []).append(row[name])
                rows.append(row)
                print(json.dumps(row), flush=True)
                seed += 2
            s = {"rate": rate, "windows": a.windows, "metrics": {
                k: {"median": statistics.median(v), "spread": spread(v),
                    "min": min(v), "max": max(v)} for k, v in values.items()}}
            summary.append(s)
            print("SPREADS", json.dumps(s), flush=True)
    finally:
        handle.stop()
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "seconds": a.seconds,
                       "device": info, "rows": rows, "summary": summary}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
