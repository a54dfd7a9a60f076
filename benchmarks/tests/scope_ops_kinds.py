#!/usr/bin/env python3
"""Builder's tool, no chip: ``scope_ops.py`` for a PER-KIND configuration
(window and full layers with page pools of their own; a chip's share of the
experts), whose bucket programs take two more pools and their tables than
that tool passes. Same reading of the compiled programs (its
``scoped_keys``), same output, one ``<metric>.ops.json`` a scope.

    JAX_PLATFORMS=cpu python benchmarks/tests/scope_ops_kinds.py <config> \\
        dynamo.attn_full dynamo.attn_window dynamo.moe_ffn

``required``: under an attention scope the Pallas kernel of that kind and
kind of program (its result type is the kind's own: [lanes, Hkv, G, Dv] a
decode step, [Hkv, G, chunk, Dv] a chunk); under ``dynamo.moe_ffn`` the
gate / up fusions of the dense dispatch. ``shared``: a key that operations
outside the scope carry too counts whole (1.0) where it is the scope's
kernel, and by ``scope_ops.shared_part`` otherwise.
"""

from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from scope_ops import scoped_keys  # noqa: E402


def shared_part(scope: str, key: str, cfg) -> float:
    """The part of a shared key's device time that is the scope's.
    ``fusion f32[<rows>]`` is a layer's down-projection fused with the
    residual add and the next RMSNorm's sum of squares: under
    ``dynamo.moe_ffn`` the held experts' of a DENSE dispatch (reads E x Fe x
    D weights in each routed layer), outside it every layer's attention-out
    matmul (Hq x Dv x D) and the dense layers' down-projection (F x D):
    parted by those bytes over the whole stack. Where a call of that many
    rows dispatches SORTED (``moe.sorted_wins`` under the share) the
    down-projection is ``ragged_dot``'s own fusions and what the scope
    keeps of the key is the residual add: 0. Every other shared key is
    integer bookkeeping of a few hundred bytes on both sides, or (``fusion
    bf16[256,4096]``: inside, the sorted dispatch's down-projection of 32
    rows x 8 assignments; outside, one elementwise operation of a 256-token
    chunk) small outside: whole."""
    from dynamo_tpu.models import moe

    rows = re.fullmatch(r"fusion f32\[(\d+)\]", key)
    if scope == "dynamo.moe_ffn" and rows:
        if _sorted(moe, cfg, int(rows.group(1))):
            return 0.0
        ours = cfg.routed_layers * cfg.num_experts * cfg.expert_width
        others = (cfg.num_layers * cfg.num_heads * cfg.v_dim
                  + (cfg.num_layers - cfg.routed_layers)
                  * cfg.intermediate_size)
        return round(ours / (ours + others), 4)
    return 1.0


def _sorted(moe, cfg, rows: int) -> bool:
    return moe.sorted_wins(rows, cfg.experts_per_token, cfg.num_experts,
                           cfg.num_experts / cfg.router_experts)


def required(scope: str, keys, cfg, B: int, chunks=()) -> dict:
    kernel = [k for k in keys if k.startswith("tpu_custom_call ")]
    if scope.startswith("dynamo.attn_"):
        return {"decode": [k for k in kernel if f"[{B}," in k],
                "prefill": [k for k in kernel if f"[{B}," not in k]}
    if scope == "dynamo.moe_ffn":
        from dynamo_tpu.models import moe

        # the gate / up matmuls: of a dense dispatch [rows, E, Fe], of a
        # sorted one ``lax.ragged_dot``'s own
        dense = [k for k in keys if re.fullmatch(
            rf"fusion bf16\[\d+,(\d+,)?{cfg.num_experts},"
            rf"{cfg.expert_width}\]", k)]
        # (on the chip ``ragged-dot-*`` custom calls, listed by prefix: a
        # compile for a described device lowers them to fusions)
        return {"prefill": dense + ["ragged-dot"] * any(
                    _sorted(moe, cfg, C) for C in chunks),
                "decode": ["ragged-dot"] if _sorted(moe, cfg, B) else [
                    k for k in dense if k.startswith(f"fusion bf16[{B},")]}
    return {}


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine import engine as E
    from dynamo_tpu.engine.cache import WindowPages, cache_kinds
    from dynamo_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    name, scopes = argv[0], argv[1:]
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
    # the engine's own bucket grid, without building an engine on a device
    page, B, N = eng["page_size"], eng["max_batch"], eng["decode_steps"]
    pad = -(-2 * N // page) * page
    raw = E._buckets(min(256, eng["max_context"]), eng["max_context"] + pad)
    s_buckets = sorted({-(-b // (128 if b > 128 else page))
                        * (128 if b > 128 else page) for b in raw})
    c_buckets = E._buckets(min(32, eng["prefill_chunk"]), eng["prefill_chunk"])
    glob, win = cache_kinds(cfg)
    per_lane = WindowPages.lane_pages(win.window, eng["prefill_chunk"], page)
    pools = [sds(s, cfg.dtype) for s in (
        *glob.pool_shapes(eng["num_pages"], page),
        *win.pool_shapes(B * per_lane + 1, page))]
    mesh = E.serving_mesh(1, devices=[dev])
    inside = {s: {} for s in scopes}
    outside = {}

    def file(text):
        ins, out = scoped_keys(text, scopes)
        for s in scopes:
            for k, n in ins[s].items():
                # a compile for a described device names a Pallas kernel
                # after its scope; the chip's trace says tpu_custom_call
                k = k.replace(f"{s}:tpu_custom_call", "tpu_custom_call")
                inside[s][k] = inside[s].get(k, 0) + n
        for k, n in out.items():
            outside[k] = outside.get(k, 0) + n

    i32 = jnp.int32
    for S in s_buckets:
        def step(p, t, k, v, wk, wv, pt, wt, ln):
            def one(carry, _):
                t, ln, k, v, wk, wv = carry
                lg, k, v, wk, wv = llama.forward_decode(
                    p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh,
                    win=(wk, wv, wt), stats={})
                return (jnp.argmax(lg[:, 0], -1).astype(i32), ln + 1, k, v,
                        wk, wv), None
            return jax.lax.scan(one, (t, ln, k, v, wk, wv), None,
                                length=N)[0]
        file(jax.jit(step, donate_argnums=(2, 3, 4, 5)).lower(
            params, sds((B,), i32), *pools, sds((B, S // page), i32),
            sds((B, S // page), i32), sds((B,), i32)).compile().as_text())
        for C in c_buckets:
            Sw = WindowPages.chunk_read_pages(win.window, C, page) * page

            def chunk(p, t, pos, k, v, wk, wv, w, ri, rp, rv, li, ww, wpg,
                      wps, wvd):
                return llama.forward(
                    p, cfg, t, pos, k, v, w, ri, rp, rv, attn_impl="flash",
                    mesh=mesh, logits_idx=li, read_pages=ri[:, ::page] // page,
                    win=(wk, wv, ww, wpg, wps, wvd), stats={})
            file(jax.jit(chunk, donate_argnums=(3, 4, 5, 6)).lower(
                params, sds((1, C), i32), sds((1, C), i32), *pools,
                sds((1, C), i32), sds((1, S), i32), sds((1, S), i32),
                sds((1, S), jnp.bool_), sds((1,), i32), sds((1, C), i32),
                sds((1, Sw // page), i32), sds((1, Sw), i32),
                sds((1, Sw), jnp.bool_)).compile().as_text())
        print(f"S {S}: compiled", file=sys.stderr, flush=True)
    print(json.dumps({
        "config": name, "context_buckets": s_buckets,
        "chunk_buckets": c_buckets, "lanes": B,
        "scopes": {s: {"ops": sorted(inside[s]),
                       "shared": {k: shared_part(s, k, cfg)
                                  for k in sorted(inside[s]) if k in outside},
                       "outside_instances": {k: outside[k]
                                             for k in sorted(inside[s])
                                             if k in outside},
                       "inside_instances": {k: inside[s][k]
                                            for k in sorted(inside[s])
                                            if k in outside},
                       "required": required(s, sorted(inside[s]), cfg, B,
                                            c_buckets)}
                   for s in scopes}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
