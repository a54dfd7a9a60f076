#!/usr/bin/env python3
"""Builder's tool, on the chip: ``program_memory.py`` for a configuration
whose model has an indexer, i.e. whose bucket programs take a THIRD pool (the
index keys, on the same pages): ``memory_analysis()`` of the decode program
and of the largest prefill program at the last context bucket, whole-pool
copies counted for the K/V shape and for the index-key shape, and the bytes
the engine holds.

    python benchmarks/tests/program_memory_indexed.py <config> [<config> ...]

This process imports jax and holds the chip: run it alone. The numbers go
into the configuration file's ``memory`` group by hand. Not part of any check.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from program_memory import report  # noqa: E402  (the same reading of a program)


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    cat = Catalog()
    for name in names:
        config = cat.data("configs", name)
        model = llama.LlamaConfig.from_hf_config(
            {k: v for k, v in config.items() if k != "benchmark"})
        t0 = time.monotonic()
        core = EngineCore(JaxEngineConfig(
            model=model, seed=1, warmup=False, **config["benchmark"]["engine"]))
        built = time.monotonic() - t0
        B, s, S = core.cfg.max_batch, core.sampling, core.s_buckets[-1]
        C = core.c_buckets[-1]
        zb, ones = np.zeros(B, np.int32), np.ones(B, np.int32)
        flags = np.zeros(B, bool)
        decode = core._decode_fn(S).jitted.lower(
            core.params, zb, core.k_pool, core.v_pool,
            np.zeros((B, S // core.page_size), np.int32), ones,
            s.temperature, s.top_p, s.top_k, s.key, core.gen_counts, flags,
            flags, s.freq_pen, s.pres_pen, i_pool=core.i_pool).compile()
        zt = np.zeros((1, C), np.int32)
        prefill = core._prefill_fn(1, C, S).jitted.lower(
            core.params, zt, zt, core.k_pool, core.v_pool, zt,
            np.zeros((1, S), np.int32), np.zeros((1, S), np.int32),
            np.zeros((1, S), bool), np.zeros(1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32),
            s.key[jnp.asarray(np.zeros(1, np.int32))],
            i_pool=core.i_pool).compile()

        def both(compiled):
            kv, ix = (report(compiled, core.k_pool.shape),
                      report(compiled, core.i_pool.shape))
            return {**kv, "index_pool_sized_copies": ix["pool_sized_copies"],
                    "index_layer_pool_copies": ix["layer_pool_copies"]}

        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "config": name, "engine_built_s": round(built, 1),
            "context_buckets": core.s_buckets, "chunk_buckets": core.c_buckets,
            "weights_bytes": int(sum(
                a.nbytes for a in jax.tree.leaves(core.params))),
            "kv_pools_bytes": int(core.k_pool.nbytes + core.v_pool.nbytes),
            "index_pool_bytes": int(core.i_pool.nbytes),
            "decode_program": {"S": S, **both(decode)},
            "prefill_program": {"C": C, "S": S, **both(prefill)},
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}), flush=True)
        del core, decode, prefill
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
