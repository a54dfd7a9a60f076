#!/usr/bin/env python3
"""Builder's tool, no chip: ``scope_ops.py`` for a configuration with
STATE-SPACE layers (a per-lane state pool and convolution-tail pool beside a
folded K/V pool), whose bucket programs take those pools and their operands.
Same reading of the compiled programs (its ``scoped_keys``), same output, one
``<metric>.ops.json`` a scope; and, of the largest decode and prefill
program, ``memory_analysis()`` and the pool-sized copies they hold
(``--memory``: those two programs alone).

    JAX_PLATFORMS=cpu python benchmarks/tests/scope_ops_state.py \\
        [--memory] <config> dynamo.ssm_step dynamo.ssm_scan

``required``: under ``dynamo.ssm_step`` (decode) and ``dynamo.ssm_scan`` (a
prefill chunk) the operation that writes the new state INTO the state pool,
in place (its result type is the pool's own, [state layers, lanes, H, P, N]
float32: the state update fused with the dynamic-update-slice in decode,
the scatter of a chunk's rows in prefill). ``shared``: a key that operations
outside the scope carry too counts 0.0 (``SHARED_WHY``).
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from program_memory import report  # noqa: E402
from scope_ops import scoped_keys  # noqa: E402


SHARED_WHY = (
    "under these keys the scope holds the gated norm's row statistics "
    "(multiply_reduce_fusion f32[rows], add_rsqrt_fusion f32[rows], "
    "reduce_sum f32[]), copies of a chunk's activations and integer "
    "bookkeeping of the layer scan: microseconds a layer. OUTSIDE the "
    "scope multiply_reduce_fusion f32[rows] is every layer's out- and "
    "down-projection fused with the residual add and the next RMSNorm's "
    "sum of squares, the weight-streaming matmuls themselves (0.90 s of a "
    "traced run's 5.98 s of decode programs; my chip run, PR 36, call G): "
    "counted whole they would bill the feed-forward's time to the "
    "recurrence. A trace names an operation by its HLO line, so the two "
    "cannot be told apart by instance, and each counts 0.0: the scope's "
    "time is read a few microseconds a layer too SHORT and the share that "
    "much too high, against a recurrence of hundreds of microseconds a "
    "layer")


def programs(name: str):
    """-> (cfg, engine block, bucket grids, lower(kind, S, C) -> lowered
    program of the configuration for a described v5e)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine import engine as E
    from dynamo_tpu.engine.cache import cache_kinds
    from dynamo_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    config = Catalog().data("configs", name)
    eng = config["benchmark"]["engine"]
    cfg = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=SingleDeviceSharding(dev))
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    page, B, N = eng["page_size"], eng["max_batch"], eng["decode_steps"]
    pad = -(-2 * N // page) * page
    raw = E._buckets(min(256, eng["max_context"]), eng["max_context"] + pad)
    s_buckets = sorted({-(-b // (128 if b > 128 else page))
                        * (128 if b > 128 else page) for b in raw})
    c_buckets = E._buckets(min(32, eng["prefill_chunk"]), eng["prefill_chunk"])
    glob, state = cache_kinds(cfg)
    s_shape, c_shape = state.state_shapes(B)
    pools = [sds(s, cfg.dtype) for s in glob.pool_shapes(eng["num_pages"],
                                                         page)]
    pools += [sds(s_shape, jnp.float32), sds(c_shape, cfg.dtype)]
    mesh = E.serving_mesh(1, devices=[dev])
    i32 = jnp.int32

    def lower(kind, S, C=None):
        if kind == "decode":
            def step(p, t, k, v, s, c, pt, ln, act):
                def one(carry, _):
                    t, ln, k, v, s, c = carry
                    lg, k, v, s, c = llama.forward_decode(
                        p, cfg, t, k, v, pt, ln, attn_impl="pallas",
                        mesh=mesh, ssm=(s, c, act))
                    return (jnp.argmax(lg[:, 0], -1).astype(i32), ln + 1, k,
                            v, s, c), None
                return jax.lax.scan(one, (t, ln, k, v, s, c), None,
                                    length=N)[0]
            return jax.jit(step, donate_argnums=(2, 3, 4, 5)).lower(
                params, sds((B,), i32), *pools, sds((B, S // page), i32),
                sds((B,), i32), sds((B,), jnp.bool_))

        def chunk(p, t, pos, k, v, s, c, w, ri, rp, rv, li, sl, sr, sv):
            return llama.forward(
                p, cfg, t, pos, k, v, w, ri, rp, rv, attn_impl="flash",
                mesh=mesh, logits_idx=li, read_pages=ri[:, ::page] // page,
                ssm=(s, c, sl, sr, sv))
        return jax.jit(chunk, donate_argnums=(3, 4, 5, 6)).lower(
            params, sds((1, C), i32), sds((1, C), i32), *pools,
            sds((1, C), i32), sds((1, S), i32), sds((1, S), i32),
            sds((1, S), jnp.bool_), sds((1,), i32), sds((1,), i32),
            sds((1,), jnp.bool_), sds((1,), i32))

    return cfg, eng, s_buckets, c_buckets, pools, lower


def memory(name: str) -> dict:
    """Of the largest decode and prefill program: ``memory_analysis()`` and
    the pool-sized copies of the K/V pool and of the state pool."""
    cfg, eng, s_buckets, c_buckets, pools, lower = programs(name)
    out = {"config": name, "pool_shapes": [list(p.shape) for p in pools]}
    for kind, C in (("decode", None), ("prefill", c_buckets[-1])):
        compiled = lower(kind, s_buckets[-1], C).compile()
        kv, st = report(compiled, pools[0].shape), report(compiled,
                                                          pools[2].shape)
        out[kind] = {**{k: kv[k] for k in ("arguments", "temporaries",
                                           "code", "tpu_custom_calls")},
                     "kv_pool_sized_copies": kv["pool_sized_copies"],
                     "kv_layer_pool_copies": kv["layer_pool_copies"],
                     "state_pool_sized_copies": st["pool_sized_copies"],
                     "state_layer_pool_copies": st["layer_pool_copies"]}
    return out


def main(argv) -> int:
    if argv[:1] == ["--memory"]:
        print(json.dumps(memory(argv[1]), indent=1))
        return 0
    name, scopes = argv[0], argv[1:]
    cfg, eng, s_buckets, c_buckets, pools, lower = programs(name)
    B = eng["max_batch"]
    inside = {s: {} for s in scopes}
    outside = {}

    def file(text):
        ins, out = scoped_keys(text, scopes)
        for s in scopes:
            for k, n in ins[s].items():
                inside[s][k] = inside[s].get(k, 0) + n
        for k, n in out.items():
            outside[k] = outside.get(k, 0) + n

    for S in s_buckets:
        file(lower("decode", S).compile().as_text())
        for C in c_buckets:
            file(lower("prefill", S, C).compile().as_text())
        print(f"S {S}: compiled", file=sys.stderr, flush=True)
    pool = ",".join(str(n) for n in pools[2].shape)

    def required(s):
        kind = "decode" if s == "dynamo.ssm_step" else "prefill"
        return {kind: [k for k in sorted(inside[s])
                       if k.endswith(f" f32[{pool}]")]}

    print(json.dumps({
        "config": name, "context_buckets": s_buckets,
        "chunk_buckets": c_buckets, "lanes": B,
        "scopes": {s: {"ops": sorted(inside[s]),
                       "shared": {k: 0.0 for k in sorted(inside[s])
                                  if k in outside},
                       "shared_why": SHARED_WHY,
                       "outside_instances": {k: outside[k]
                                             for k in sorted(inside[s])
                                             if k in outside},
                       "required": required(s)}
                   for s in scopes}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
