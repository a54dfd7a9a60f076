#!/usr/bin/env python3
"""Builder's tool, on the chip: ``moe_dispatch.py`` for a configuration
served as a CHIP'S SHARE of its experts (``expert_shard``): dense against
sorted dispatch of the HELD experts, the router as wide as published, the
assignments to absent experts dropped before dispatch.

    python benchmarks/tests/moe_dispatch_share.py <config> [rows ...]

For each row count (default 32, the decode lanes, and the chunk sizes 64 and
256) it times ``models/moe.moe_ffn`` over the configuration's routed layers
(stacked weights, one call a layer) with the rule ``moe.sorted_wins`` forced
either way, under the configuration's own router law, and prints
milliseconds a layer, the assignments to held experts and the held experts
hit a layer, the temporaries of each program and the largest difference
between the two results. ``moe.sorted_wins``'s rule for a share is written
from its lines (PERF.md section 6). This process holds the chip: run it
alone. Not part of any check.
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
    rows = [int(a) for a in argv[1:]] or [32, 64, 256]
    L, E, R, D, F = (cfg.routed_layers, cfg.num_experts, cfg.router_experts,
                     cfg.hidden_size, cfg.expert_width)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    mk = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   / shape[-2] ** 0.5).astype(cfg.dtype),
                 static_argnums=1)
    # every matrix N(0, 1 / fan-in) and rows of unit rms, as the seeded
    # init makes them: router logits of spread 1, sigmoid scores of spread
    # 0.2 beside a selection bias of 0.02, so that a call's assignments fall
    # on the held experts as evenly as they do in the cell
    wr = mk(ks[0], (L, D, R))
    wg, wu, wd = (mk(ks[1], (L, E, D, F)), mk(ks[2], (L, E, D, F)),
                  mk(ks[3], (L, E, F, D)))
    bias = 0.02 * jax.random.normal(ks[5], (L, R), jnp.float32)

    def layers_under(sorted_):
        def layers(x, wr, wg, wu, wd, bias):
            moe.sorted_wins = lambda *a: sorted_
            hits = helds = 0
            for l in range(L):
                y, (hit, held), _ = moe.moe_ffn(
                    x, wr[l], wg, wu, wd, cfg.experts_per_token, layer=l,
                    router=cfg.router, bias=bias[l], first=cfg.expert_first)
                # (unit rms again for the next layer's router)
                x = x + y * 0.01
                hits, helds = hits + hit, helds + held
            return x, hits, helds
        return layers

    for n in rows:
        x = jax.random.normal(ks[4], (1, n, D), jnp.float32).astype(cfg.dtype)
        rec = {"rows": n, "held": E, "router": R}
        for rule in ("dense", "sorted"):
            fn = jax.jit(layers_under(rule == "sorted")).lower(
                x, wr, wg, wu, wd, bias).compile()
            y, hits, helds = jax.block_until_ready(
                fn(x, wr, wg, wu, wd, bias))
            t0 = time.perf_counter()
            for _ in range(10):
                y, hits, helds = fn(x, wr, wg, wu, wd, bias)
            jax.block_until_ready(y)
            rec[rule] = {
                "ms_per_layer": 1e3 * (time.perf_counter() - t0) / 10 / L,
                "temporaries": fn.memory_analysis().temp_size_in_bytes,
                "held_assignments_per_layer": float(helds) / L,
                "held_experts_hit_per_layer": float(hits) / L}
            rec[rule + "_y"] = y
        rec["max_abs_diff"] = float(jnp.max(jnp.abs(
            rec.pop("dense_y").astype(jnp.float32)
            - rec.pop("sorted_y").astype(jnp.float32))))
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
