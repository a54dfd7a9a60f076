#!/usr/bin/env python3
"""Builder's tool, no chip: compile each configuration's decode step and its
largest prefill chunk for a DESCRIBED v5e, at full width and depth, and print
``memory_analysis()``. A compile that passes is not a chip run; it says the
v5e compiler accepts the kernels at these head geometries and that one
program's arguments and temporaries fit 16 GB.

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_only.py [config ...]

The step compiled is ``llama.forward_decode`` / ``llama.forward`` as the
engine's bucket programs call them (without the engine's scan over
``decode_steps`` and its sampler).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from program_memory import report  # noqa: E402  (the same reading of a program)


def main(names) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel.mesh import serving_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    cat = Catalog()
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=SingleDeviceSharding(dev))
    mesh = serving_mesh(1, devices=[dev])
    for name in names or [c["name"] for c in cat.manifest["configs"]]:
        config = cat.data("configs", name)
        eng = config["benchmark"]["engine"]
        cfg = llama.LlamaConfig.from_hf_config(
            {k: v for k, v in config.items() if k != "benchmark"})
        page, B = eng["page_size"], eng["max_batch"]
        pad = -(-2 * eng["decode_steps"] // page) * page
        S = -(-(eng["max_context"] + pad) // 128) * 128
        C = eng["prefill_chunk"]
        shapes = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
        params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
        weights = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(shapes))
        pshape = (cfg.num_layers, cfg.num_kv_heads, eng["num_pages"], page,
                  cfg.head_dim)
        pool = sds(pshape, cfg.dtype)
        pool_bytes = 2 * jnp.dtype(cfg.dtype).itemsize
        for d in pshape:
            pool_bytes *= d
        out = {"config": name, "weights_bytes": weights,
               "kv_pools_bytes": pool_bytes, "context_bucket": S}
        programs = {
            "decode_step": (
                lambda p, t, k, v, pt, ln: llama.forward_decode(
                    p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh),
                (params, sds((B,), jnp.int32), pool, pool,
                 sds((B, S // page), jnp.int32), sds((B,), jnp.int32))),
            "prefill_chunk": (
                lambda p, t, pos, k, v, wi, ri, rp, rv, li: llama.forward(
                    p, cfg, t, pos, k, v, wi, ri, rp, rv, attn_impl="flash",
                    mesh=mesh, logits_idx=li),
                (params, sds((1, C), jnp.int32), sds((1, C), jnp.int32),
                 pool, pool, sds((1, C), jnp.int32), sds((1, S), jnp.int32),
                 sds((1, S), jnp.int32), sds((1, S), jnp.bool_),
                 sds((1,), jnp.int32))),
        }
        for what, (fn, args) in programs.items():
            t0 = time.monotonic()
            donate = (2, 3) if what == "decode_step" else (3, 4)
            compiled = jax.jit(fn, donate_argnums=donate).lower(
                *args).compile()
            out[what] = {"compile_s": round(time.monotonic() - t0, 1),
                         **report(compiled, pshape)}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
