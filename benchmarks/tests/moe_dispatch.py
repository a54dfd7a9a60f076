#!/usr/bin/env python3
"""Builder's tool, on the chip: dense against sorted dispatch of the routed
experts at the batch sizes a cell produces, on a configuration's own widths.

    python benchmarks/tests/moe_dispatch.py <config> [rows ...]

For each row count (default 12, the decode lanes, and the chunk sizes 256 and
512) it times ``models/moe.moe_ffn`` over all the configuration's layers
(stacked weights, one call a layer, as the layer loop makes them) with the
rule ``moe.sorted_wins`` forced either way and prints milliseconds a layer, the
temporaries of each program and the largest difference between the two
results. This process holds the chip: run it alone. Not part of any check.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.models import llama, moe
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    config = Catalog().data("configs", argv[0])
    cfg = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    rows = [int(a) for a in argv[1:]] or [12, 256, 512]
    L, E, D, F = (cfg.num_layers, cfg.num_experts, cfg.hidden_size,
                  cfg.expert_width)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    mk = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   * 0.05).astype(cfg.dtype),
                 static_argnums=1)
    wr = mk(ks[0], (L, D, E)) * 20
    wg, wu, wd = (mk(ks[1], (L, E, D, F)), mk(ks[2], (L, E, D, F)),
                  mk(ks[3], (L, E, F, D)))

    def layers_under(sorted_):
        # a function of its own for each rule: jit keeps what it traced by
        # the function, and the rule is read while tracing
        def layers(x, wr, wg, wu, wd):
            moe.sorted_wins = lambda *a: sorted_
            hits = 0
            for l in range(L):
                y, hit, _ = moe.moe_ffn(x, wr[l], wg, wu, wd,
                                        cfg.experts_per_token, layer=l)
                x, hits = x + y * 0.01, hits + hit
            return x, hits
        return layers

    out = []
    for n in rows:
        x = mk(ks[4], (1, n, D)) * 20
        rec = {"rows": n}
        for rule in ("dense", "sorted"):
            fn = jax.jit(layers_under(rule == "sorted")).lower(
                x, wr, wg, wu, wd).compile()
            y, hits = jax.block_until_ready(fn(x, wr, wg, wu, wd))
            t0 = time.perf_counter()
            for _ in range(10):
                y, hits = fn(x, wr, wg, wu, wd)
            jax.block_until_ready(y)
            rec[rule] = {
                "ms_per_layer": 1e3 * (time.perf_counter() - t0) / 10 / L,
                "temporaries": fn.memory_analysis().temp_size_in_bytes,
                "experts_hit_per_layer": float(hits) / L}
            rec[rule + "_y"] = y
        rec["max_abs_diff"] = float(jnp.max(jnp.abs(
            rec.pop("dense_y").astype(jnp.float32)
            - rec.pop("sorted_y").astype(jnp.float32))))
        out.append(rec)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
