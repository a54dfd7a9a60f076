#!/usr/bin/env python3
"""Builder's tool, on the chip: ``program_memory.py`` for a configuration
with GATED SHORT-CONVOLUTION layers (the folded K/V pools and the ONE
per-lane tail pool, the copies of each a program makes, ``bytes_in_use`` with
the engine built), and the engine's own programs TIMED on dummy operands:
the decode dispatch at the last context bucket and a chunk of each size in
``--chunks`` at each context in ``--contexts`` (how the configuration's
``prefill_chunk`` was chosen).

    python benchmarks/tests/program_memory_conv.py <config> \\
        [--chunks 256,512,1024] [--contexts 2048,8448] [--reps 5]

A chunk's rows are real tokens of random ids at the END of the context
bucket (every key before them valid), so the flash call, the conv scan and
the experts' dispatch do what a served chunk makes them do. This process
imports jax and holds the chip: run it alone. The numbers go into the
configuration file's ``memory`` and ``engine_why`` groups by hand. Not part
of any check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from program_memory import report  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("config")
    p.add_argument("--chunks", default="256,512,1024")
    p.add_argument("--contexts", default="2048,8448")
    p.add_argument("--reps", type=int, default=5)
    a = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    config = Catalog().data("configs", a.config)
    model = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    chunks = sorted(int(c) for c in a.chunks.split(","))
    eng = {**config["benchmark"]["engine"], "prefill_chunk": chunks[-1]}
    t0 = time.monotonic()
    core = EngineCore(JaxEngineConfig(model=model, seed=1, warmup=False,
                                      **eng))
    built = time.monotonic() - t0
    B, s, S = core.cfg.max_batch, core.sampling, core.s_buckets[-1]
    page = core.page_size
    rng = np.random.default_rng(0)
    out = {"config": a.config, "engine_built_s": round(built, 1),
           "context_buckets": core.s_buckets, "moe_dispatch": core.moe_dispatch,
           "cache_kinds": [k.label() for k in core.cache_kinds],
           "weights_bytes": int(sum(
               x.nbytes for x in jax.tree.leaves(core.params))),
           "pool_shapes": {"k": list(core.k_pool.shape),
                           "v": list(core.v_pool.shape),
                           "conv_tail": list(core.c_pool.shape)},
           "kv_pools_bytes": int(core.k_pool.nbytes + core.v_pool.nbytes),
           "state_pools_bytes": int(core.c_pool.nbytes)}

    def every(compiled):
        got = {}
        for nm, pool in (("k", core.k_pool), ("conv_tail", core.c_pool)):
            shape = tuple(pool.shape) + (1,) * (5 - pool.ndim)
            r = report(compiled, shape)
            got.update({k: r[k] for k in ("arguments", "aliased",
                                          "temporaries", "code",
                                          "tpu_custom_calls")})
            if nm == "k":
                got["k_pool_sized_copies"] = r["pool_sized_copies"]
                got["k_layer_pool_copies"] = r["layer_pool_copies"]
        return got

    # ---- the decode dispatch: every lane at a context of S - 8 tokens ----
    lens = np.full(B, S - 8, np.int32)
    pt = (1 + np.arange(B * (S // page), dtype=np.int32).reshape(B, -1)
          % (core.k_pool.shape[2] - 1))
    toks = rng.integers(0, model.vocab_size, B).astype(np.int32)
    flags, act = np.zeros(B, bool), np.ones(B, bool)
    fn = core._decode_fn(S)
    args = lambda: (core.params, toks, core.k_pool, core.v_pool, pt, lens,
                    s.temperature, s.top_p, s.top_k, s.key, core.gen_counts,
                    flags, act, s.freq_pen, s.pres_pen)
    out["decode_program"] = {"S": S, **every(
        fn.jitted.lower(*args(), **core._idx()).compile())}
    times = []
    for _ in range(a.reps + 1):
        t = time.perf_counter()
        packed, _, _, kp, vp, core.gen_counts, *ip = fn(*args(),
                                                        **core._idx())
        core._take_pools((kp, vp, *ip))
        jax.block_until_ready(packed)
        times.append(time.perf_counter() - t)
    hit = np.asarray(packed)[:, 0, 2]
    out["decode_program"].update(
        dispatch_ms=round(1e3 * min(times[1:]), 3),
        step_ms=round(1e3 * min(times[1:]) / core.cfg.decode_steps, 3),
        experts_hit_a_step=float(hit.mean()), first_call_s=round(times[0], 1))
    print(json.dumps(out), flush=True)

    # ---- chunks ---------------------------------------------------------
    rows = []
    for S_c in (int(x) for x in a.contexts.split(",")):
        S_c = core._bucket(S_c, core.s_buckets)
        if any(r["S"] == S_c for r in rows):
            continue
        for C in chunks:
            if C + 64 > S_c:
                continue
            fn = core._prefill_fn(1, C, S_c)
            start = S_c - C - 64
            pos = (start + np.arange(C, dtype=np.int32))[None]
            slots = (page + np.arange(S_c, dtype=np.int32))[None]
            ids = rng.integers(0, model.vocab_size, (1, C)).astype(np.int32)
            ssm = core._ssm_rows(1)
            ssm["s_lanes"][0], ssm["s_valid"][0] = 0, C
            keys = s.key[jnp.asarray(np.zeros(1, np.int32))]
            cargs = lambda: (core.params, ids, pos, core.k_pool, core.v_pool,
                             slots[:, start:start + C], slots,
                             np.arange(S_c, dtype=np.int32)[None],
                             (np.arange(S_c) < start + C)[None],
                             np.full(1, C - 1, np.int32),
                             np.zeros(1, np.float32), np.ones(1, np.float32),
                             np.zeros(1, np.int32), keys)
            row = {"C": C, "S": S_c}
            if C == chunks[-1] and S_c == core.s_buckets[-1]:
                row.update(every(fn.jitted.lower(
                    *cargs(), **core._idx(), **ssm).compile()))
            times = []
            for _ in range(a.reps + 1):
                t = time.perf_counter()
                packed, _, _, *pools = fn(*cargs(), **core._idx(), **ssm)
                core._take_pools(pools)
                jax.block_until_ready(packed)
                times.append(time.perf_counter() - t)
            row.update(chunk_ms=round(1e3 * min(times[1:]), 3),
                       us_a_token=round(1e6 * min(times[1:]) / C, 2),
                       experts_hit=float(np.asarray(packed)[0, 2]),
                       first_call_s=round(times[0], 1))
            rows.append(row)
            print(json.dumps(row), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"chunks": rows,
                      "bytes_in_use": stats.get("bytes_in_use"),
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "bytes_limit": stats.get("bytes_limit")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
