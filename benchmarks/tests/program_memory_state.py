#!/usr/bin/env python3
"""Builder's tool, on the chip: ``program_memory.py`` for a configuration
with STATE-SPACE layers: the folded K/V pools, the per-lane state pool and
convolution-tail pool, and the copies of each a program makes, with the
engine built (``bytes_in_use``).

    python benchmarks/tests/program_memory_state.py <config>

This process imports jax and holds the chip: run it alone. The numbers go
into the configuration file's ``memory`` group by hand. Not part of any
check.
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
        pt = np.zeros((B, S // core.page_size), np.int32)
        decode = core._decode_fn(S).jitted.lower(
            core.params, zb, core.k_pool, core.v_pool, pt, ones,
            s.temperature, s.top_p, s.top_k, s.key, core.gen_counts, flags,
            flags, s.freq_pen, s.pres_pen, **core._idx()).compile()
        zt = np.zeros((1, C), np.int32)
        prefill = core._prefill_fn(1, C, S).jitted.lower(
            core.params, zt, zt, core.k_pool, core.v_pool, zt,
            np.zeros((1, S), np.int32), np.zeros((1, S), np.int32),
            np.zeros((1, S), bool), np.zeros(1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32),
            s.key[jnp.asarray(np.zeros(1, np.int32))],
            **core._idx(), **core._ssm_rows(1)).compile()
        pools = {"k": core.k_pool, "v": core.v_pool, "state": core.s_pool,
                 "conv_tail": core.c_pool}

        def every(compiled):
            out = {}
            for nm in ("k", "state"):
                r = report(compiled, pools[nm].shape)
                out.update({k: r[k] for k in ("arguments", "temporaries",
                                              "code", "tpu_custom_calls")})
                out[nm + "_pool_sized_copies"] = r["pool_sized_copies"]
                out[nm + "_layer_pool_copies"] = r["layer_pool_copies"]
            return out

        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "config": name, "engine_built_s": round(built, 1),
            "context_buckets": core.s_buckets, "chunk_buckets": core.c_buckets,
            "weights_bytes": int(sum(
                a.nbytes for a in jax.tree.leaves(core.params))),
            "pool_shapes": {nm: list(p.shape) for nm, p in pools.items()},
            "kv_pools_bytes": int(core.k_pool.nbytes + core.v_pool.nbytes),
            "state_pools_bytes": int(core.s_pool.nbytes + core.c_pool.nbytes),
            "decode_program": {"S": S, **every(decode)},
            "prefill_program": {"C": C, "S": S, **every(prefill)},
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}), flush=True)
        del core, decode, prefill
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
