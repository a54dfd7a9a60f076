#!/usr/bin/env python3
"""Builder's tool, on the chip: what moves a cell's tail from run to run?

    python benchmarks/tests/repeat_windows.py --workload <cell> \\
        --weights 5100603,5100605 --tokens self,self,11,12,self \\
        [--seconds 50] [--out chiprun_out/repeat_<cell>.json]

One server a weight seed; on it one window of the cell's own mix and rate a
token seed, in the order given (``self`` = the weight seed, which is what
``benchmarks/run.py --seed`` gives both). The FIRST window of a server is
what ``run.py`` measures (bring-up, warm set, window); a seed given twice
says how far the same work reads the same, a seed of its own how far other
token ids move it, the next server how far other weights do. Per window one
JSON line (``ttft_p50_ms``, ``tpot_p50_ms``, ``tpot_p90_ms``); the file keeps
every request's record (due, first and last token, tokens, how many DIFFERENT
tokens it decoded: greedy decoding of seeded weights can lock into one) and
what the routed experts' counters and the engine's phase clocks gained.
PR 51 found with it that a window of 32 requests reads its ``tpot_p90_ms``
off ONE request. Not part of any check. Never ``--rehearse`` a tool of this
kind on a real cell: the configuration's server starts at full size on the
CPU (34 GB for command-a-plus-4l).
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import launch, measures, records, runner  # noqa: E402
from benchmarks.harness.catalog import Catalog  # noqa: E402
from benchmarks.harness.cell import (MODEL_NAME, _warm_set, bring_up,  # noqa: E402
                                     prepare)
from benchmarks.harness.modeldir import tokens_of  # noqa: E402
from benchmarks.harness.stats import percentile  # noqa: E402
from benchmarks.harness.traffic import RequestSource  # noqa: E402

def record(r, t0: float) -> dict:
    try:
        toks = tokens_of(" ".join(r.text))
    except ValueError:
        toks = []
    return {**records.request(r, t0), "distinct_tokens": len(set(toks))}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--weights", required=True)
    p.add_argument("--tokens", default="self,self")
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--out", default=None)
    a = p.parse_args()
    # the served text is kept, so that the decoded tokens can be counted
    runner.stream_one = functools.partial(runner.stream_one, keep_text=True)
    cat = Catalog()
    rows = []
    for weights in (int(s) for s in a.weights.split(",")):
        su = prepare(cat, a.workload, weights, False, False)
        params = su.mix["arrivals"]
        block = su.gen.plan(params, a.seconds)["block"]
        handle, _ = bring_up(su, False)
        try:
            for n, tok in enumerate(a.tokens.split(",")):
                seed = weights if tok == "self" else int(tok)
                source = RequestSource(su.mix, su.config["vocab_size"],
                                       MODEL_NAME, seed, block)
                source.prepare(1)
                if n == 0:
                    _warm_set(handle.base, source, seed, su.engine)
                before = records.counters(launch.scrape(handle.base))
                w = asyncio.run(runner.drive_window(
                    su.gen, handle.base, source, params, a.seconds, seed,
                    int(su.mix.get("lengths_seed", 0)),
                    float(su.mix.get("drain_s", 30))))
                after = records.counters(launch.scrape(handle.base))
                res = w["results"]
                tpot = measures.tpot_ms(res)
                row = {"weights": weights, "tokens": seed, "window": n,
                       "failed": sum(not r.ok() for r in res),
                       "ttft_p50_ms": percentile(measures.ttft_ms(res), 50),
                       "tpot_p50_ms": percentile(tpot, 50),
                       "tpot_p90_ms": percentile(tpot, 90)}
                print(json.dumps(row), flush=True)
                rows.append({**row, "gained": records.gained(before, after),
                             "requests": [record(r, w["t0"]) for r in res]})
        finally:
            handle.stop()
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
