#!/usr/bin/env python3
"""Builder's tool, on the chip: find the knee of an open-loop cell once.

    python benchmarks/tests/sweep.py --workload <cell> --rates 4,6,8 \\
        --seconds 20 [--seed 7] [--out chiprun_out/sweep_<cell>.json]

One server for the whole sweep; for each rate, in rising order, one window of
the cell's own mix at that rate with token ids of its own (``--seed``, + 1 a
rate: the same sizes, no prompt the server has seen), followed to its end.
Prints one JSON line a rate: offered and completed tokens per second,
requests in flight when the window closed, failures, time to first token and
per-request token gap. The knee is the highest rate at which completed
tokens keep within 3 % of the offered ones and the number in flight does not
grow over the window; the cell's traffic file then gets 0.8 x that, as a
number. Not part of any check.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import measures, runner  # noqa: E402
from benchmarks.harness.catalog import Catalog  # noqa: E402
from benchmarks.harness.cell import (MODEL_NAME, _warm_set, bring_up,  # noqa: E402
                                     prepare)
from benchmarks.harness.stats import percentile  # noqa: E402
from benchmarks.harness.traffic import RequestSource  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args()
    cat = Catalog()
    su = prepare(cat, a.workload, a.seed, False, a.rehearse)
    rows = []
    handle, info = bring_up(su, a.rehearse)
    try:
        rates = sorted(float(r) for r in a.rates.split(","))
        for seed, rate in enumerate(rates, a.seed):
            # a seed a rate: one server serves them all, and with prefix
            # reuse on it would serve a rate's prompts from the cache of the
            # rate before (a knee read twice too high, PR 48)
            params = {**su.mix["arrivals"], "rate_per_s": rate}
            block = su.gen.plan(params, a.seconds)["block"]
            source = RequestSource(su.mix, su.config["vocab_size"],
                                   MODEL_NAME, seed, block)
            source.prepare(1)
            if seed == a.seed:
                _warm_set(handle.base, source, seed, su.engine)
            w = asyncio.run(runner.drive_window(
                su.gen, handle.base, source, params, a.seconds, seed,
                int(su.mix.get("lengths_seed", 0)),
                float(su.mix.get("drain_s", 30))))
            res = w["results"]
            ttft, tpot = measures.ttft_ms(res), measures.tpot_ms(res)
            # in flight half way through, to see whether the number grows
            mid = w["t0"] + a.seconds / 2
            in_mid = sum(1 for r in res if r.sent <= mid
                         and (r.last is None or r.last > mid))
            row = {
                "rate_per_s": rate, "seed": seed, "requests": len(res),
                "failed": sum(not r.ok() for r in res),
                "offered_tok_s": source.sizes()["output_tokens_sum"]
                / a.seconds,
                "completed_tok_s": measures.tokens_in_window(
                    res, w["t0"], w["t0"] + a.seconds) / a.seconds,
                "in_flight_mid": in_mid,
                "in_flight_at_close": w["in_flight_at_close"],
                "drained_at_s": w["ended_s"],
                "ttft_p50_ms": percentile(ttft, 50),
                "ttft_p90_ms": percentile(ttft, 90),
                "tpot_p50_ms": percentile(tpot, 50),
                "tpot_p90_ms": percentile(tpot, 90),
                "lateness_p90_ms": percentile(measures.lateness_ms(res), 90),
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        handle.stop()
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "seconds": a.seconds,
                       "seed": a.seed, "device": info, "rows": rows}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
