#!/usr/bin/env python3
"""Builder's tool, on the chip: ONE prefill chunk program of a benchmark
configuration, its new K/V rows written a row at a time (``llama.kv_write``)
against a page run at a time (``llama.kv_write_pages``): ms a chunk of each
form on the same operands, the device operations of one call of each, and
whether the two forms sampled the same token and left the same pools.

    python scripts/prefill_write_ab.py <config> ... [--context 2048]
        [--reps 20] [--out chiprun_out/pr49/write_ab.json] [--tiny]

The chunk is the configuration's ``prefill_chunk`` real tokens of random ids
at the END of the context bucket, every key before them valid, so the flash
call does what a served chunk's does. Both programs are the engine's own
(``EngineCore._prefill_fn(1, C, S, form=)``), each compiled once. ``--tiny``
cuts layers, pages and context for a rehearsal on the CPU. This process
imports jax and holds the chip: run it alone. Not part of any check.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def one(name: str, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.models import llama
    from paged_kernel_alone import device_ops

    config = Catalog().data("configs", name)
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    eng = dict(config["benchmark"]["engine"])
    if args.tiny:
        eng.update(num_pages=64, max_context=512, max_batch=2)
        eng["prefill_chunk"] = min(eng["prefill_chunk"], 256)
        hf["num_hidden_layers"] = 2       # a model of uniform layers
    model = llama.LlamaConfig.from_hf_config(hf)
    core = EngineCore(JaxEngineConfig(model=model, seed=1, warmup=False,
                                      **eng))
    C, page, s = core.cfg.prefill_chunk, core.page_size, core.sampling
    S = core._bucket(min(args.context, core.s_buckets[-1]), core.s_buckets)
    start = (S - C) // page * page - page
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, (1, C)).astype(np.int32)
    pos = (start + np.arange(C, dtype=np.int32))[None]
    slots = (page + np.arange(S, dtype=np.int32))[None]   # pages 1 ..
    keys = s.key[jnp.asarray(np.zeros(1, np.int32))]
    win = core._win_dummies(1, C)
    if win:
        # the window cache's pages for the chunk, in its own pool
        win["w_write"] = slots[:, :C].copy()
        n = win["w_pages"].shape[1]
        win["w_pages"][0] = 1 + np.arange(n)
        win["w_pos"][0] = start + np.arange(n * page)
        win["w_valid"][0] = np.arange(n * page) < C
    ssm = core._ssm_rows(1)
    if ssm:
        # from a zero state at every call, so that each call is the same
        ssm["s_lanes"][0], ssm["s_valid"][0], ssm["s_reset"][0] = 0, C, True

    def call(fn):
        packed, _, _, *pools = fn(
            core.params, ids, pos, core.k_pool, core.v_pool,
            slots[:, start:start + C], slots,
            np.arange(S, dtype=np.int32)[None],
            (np.arange(S) < start + C)[None], np.full(1, C - 1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32), keys, **core._idx(), **win, **ssm)
        core._take_pools(pools)
        return packed

    out = {"config": name, "C": C, "S": S, "start": start,
           "cache_kinds": [k.label() for k in core.cache_kinds],
           "prefill_kv_write": core.prefill_kv_write}
    pools = {}
    for form in ("row", "page"):
        fn = core._prefill_fn(1, C, S, form=form)
        t0 = time.perf_counter()
        packed = np.asarray(call(fn))
        first = time.perf_counter() - t0
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(call(fn))
            times.append(time.perf_counter() - t0)
        pools[form] = {
            n: np.asarray(getattr(core, n)[:, :, 1:1 + S // page]
                          .astype(jnp.float32))
            for n in ("k_pool", "v_pool", "i_pool", "wk_pool", "wv_pool")
            if getattr(core, n, None) is not None}
        out[form] = {"chunk_ms_min": round(1e3 * min(times), 3),
                     "chunk_ms_median": round(1e3 * sorted(times)[
                         len(times) // 2], 3),
                     "first_call_s": round(first, 1),
                     "token": float(packed[0, 0]),
                     "logprob": float(packed[0, 1]),
                     "device_ops": device_ops(call, fn)}
    out["same_token"] = (out["row"]["token"] == out["page"]["token"]
                         and out["row"]["logprob"] == out["page"]["logprob"])
    # where the two forms' pools differ: elements, the largest difference
    # beside the largest value, and the [layer, head, page, row] it is at
    # (another fusion around the projections may round a row differently)
    out["pools_differ"] = {}
    for n, a in pools["row"].items():
        d = np.abs(a - pools["page"][n])
        if d.any():
            out["pools_differ"][n] = {
                "elements": int((d > 0).sum()), "of": int(d.size),
                "max_abs": float(d.max()), "pool_max_abs": float(
                    np.abs(a).max()),
                "at": [int(i) for i in np.unravel_index(d.argmax(),
                                                        d.shape)][:4],
                "layers": sorted({int(i) for i in np.nonzero(
                    d.reshape(d.shape[0], -1).any(1))[0]})}
    out["same_pools"] = not out["pools_differ"]
    out["saved_ms"] = round(out["row"]["chunk_ms_min"]
                            - out["page"]["chunk_ms_min"], 3)
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--context", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--tiny", action="store_true",
                    help="few pages, short context: a rehearsal on the CPU")
    args = ap.parse_args(argv)

    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    rows = []
    for name in args.configs:
        try:
            rows.append(one(name, args))
        except Exception as e:             # the next configuration still runs
            rows.append({"config": name, "error": repr(e)[:2000],
                         "same_token": False, "same_pools": False})
        gc.collect()                       # the engine's arrays, off the chip
        print(json.dumps(rows[-1]), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0 if all(r["same_token"] and r["same_pools"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
