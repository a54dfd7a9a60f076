#!/usr/bin/env python3
"""Builder's tool, on the chip: ``program_memory.py`` for a configuration
with LATENT attention, whose two pools differ in width (the shared rotary
key's, the compressed vectors'): the copies of EACH a program makes, and
what the engine reports of the cache kind and the chunk form.

    python benchmarks/tests/program_memory_latent.py <config>

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

from program_memory import report  # noqa: E402


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
            flags, s.freq_pen, s.pres_pen).compile()
        zt = np.zeros((1, C), np.int32)
        prefill = core._prefill_fn(1, C, S).jitted.lower(
            core.params, zt, zt, core.k_pool, core.v_pool, zt,
            np.zeros((1, S), np.int32), np.zeros((1, S), np.int32),
            np.zeros((1, S), bool), np.zeros(1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32),
            s.key[jnp.asarray(np.zeros(1, np.int32))]).compile()
        pools = {"k": core.k_pool, "v": core.v_pool}

        def every(compiled):
            out = {}
            for nm, pool in pools.items():
                r = report(compiled, pool.shape)
                out.update({k: r[k] for k in ("arguments", "aliased",
                                              "temporaries", "code",
                                              "tpu_custom_calls")})
                out[nm + "_pool_sized_copies"] = r["pool_sized_copies"]
                out[nm + "_layer_pool_copies"] = r["layer_pool_copies"]
            return out

        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "config": name, "engine_built_s": round(built, 1),
            "cache_kinds": [k.label() for k in core.cache_kinds],
            "token_bytes": core.cache_kinds[0].token_bytes(2),
            "token_bytes_stored": core.cache_kinds[0].token_bytes(
                2, stored=True),
            "decode_kv_write": core.decode_kv_write,
            "context_buckets": core.s_buckets, "chunk_buckets": core.c_buckets,
            "weights_bytes": int(sum(
                a.nbytes for a in jax.tree.leaves(core.params))),
            "pool_shapes": {nm: list(p.shape) for nm, p in pools.items()},
            "pools_bytes": int(core.k_pool.nbytes + core.v_pool.nbytes),
            "decode_program": {"S": S, **every(decode)},
            "prefill_program": {"C": C, "S": S, **every(prefill)},
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}), flush=True)
        del core, decode, prefill
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
