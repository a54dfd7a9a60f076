#!/usr/bin/env python3
"""Builder's tool, no chip: which operations of a configuration's bucket
programs lie under a ``jax.named_scope``, as the keys a trace summary files
them under (``harness/xplane.py`` ``op_key``: ``<name> <result type>``).

    JAX_PLATFORMS=cpu python benchmarks/tests/scope_ops.py <config> \\
        dynamo.index_select dynamo.moe_ffn

Every decode and prefill bucket program of the configuration's engine block
is compiled for a DESCRIBED v5e (the same compiler as on the chip, nothing
runs), and every instruction outside a fused computation whose metadata
``op_name`` holds the scope is filed under its key. The result is pasted as
data beside the per-layer metric that reads those operations' device time
(``layer_metrics/<metric>.ops.json``): a trace names an operation by its HLO
line, not by its scope, so the reader needs the list, and the cell's fixed
geometry makes it stable. A key that operations outside the scope share is
listed under ``shared`` with the PART of its time that is the scope's
(``shared_part`` below: by the bytes the two operations must read, since
such fusions are bound by memory; 1.0 where what shares the key is
negligible), and ``required`` names per kind the keys of which a trace with
that kind's work must hold one (``harness/routed.py`` ``op_seconds`` raises
otherwise: a change in fusion renames operations without any error). Run it
again when the program's mathematics under a scope changes.
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


def scoped_keys(text: str, scopes):
    """-> ({scope: {key: instructions}}, {key: instructions outside})."""
    from benchmarks.harness.xplane import op_key

    inside = {s: {} for s in scopes}
    outside = {}
    fused = False
    for line in text.splitlines():
        head = line.strip()
        if head.endswith("{") and "(" in head and " = " not in head:
            fused = "fused_computation" in head or head.startswith("%fused")
            continue
        if fused or " = " not in head or not head.startswith(("%", "ROOT")):
            continue
        if re.search(r"\b(parameter|constant|get-tuple-element|tuple|"
                     r"bitcast)\(", head):
            continue
        key = op_key(head.removeprefix("ROOT ").strip())
        name = re.search(r'op_name="([^"]*)"', head)
        where = [s for s in scopes if name and s in name.group(1)]
        for s in where:
            inside[s][key] = inside[s].get(key, 0) + 1
        if not where:
            outside[key] = outside.get(key, 0) + 1
    return inside, outside


def shared_part(scope: str, key: str, cfg) -> float:
    """The part of a shared key's device time that is the scope's.
    ``fusion f32[<rows>]`` is a layer's matmul fused with the residual add
    and the next RMSNorm's sum of squares: under ``dynamo.moe_ffn`` the
    experts' down-projection of a DENSE dispatch (reads E x F x D weights),
    outside it the attention-out matmul (reads Hq x Dh x D): parted by those
    bytes. Where the call's dispatch is sorted the down-projection is
    ``ragged-dot``'s own custom call and the key is the attention's: 0."""
    from dynamo_tpu.models import moe

    rows = re.fullmatch(r"fusion f32\[(\d+)\]", key)
    if scope == "dynamo.moe_ffn" and rows:
        if moe.sorted_wins(int(rows.group(1)), cfg.experts_per_token,
                           cfg.num_experts):
            return 0.0
        ours = cfg.num_experts * cfg.expert_width
        return round(ours / (ours + cfg.num_heads * cfg.head_dim), 4)
    return 1.0


def required(scope: str, keys, cfg, B: int) -> dict:
    """Per kind, the keys (or prefixes) of which a trace with that kind's
    work under the scope must hold at least one."""
    if scope == "dynamo.moe_ffn":
        gate_up = [k for k in keys if re.fullmatch(
            rf"fusion bf16\[\d+,{cfg.num_experts},{cfg.expert_width}\]", k)]
        return {"prefill": gate_up, "decode": ["ragged-dot"]}
    if scope == "dynamo.index_select":
        scores = lambda rows: [k for k in keys if re.fullmatch(
            r"fusion f32\[(\d+),\d+\]", k) and (k.startswith(
                f"fusion f32[{B},") == rows)]
        return {"prefill": scores(False), "decode": scores(True)}
    return {}


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine import engine as E
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
    pool = sds((cfg.num_layers, cfg.num_kv_heads, eng["num_pages"], page,
                cfg.head_dim), cfg.dtype)
    ipool = sds(llama.index_pool_shape(cfg, eng["num_pages"], page),
                cfg.dtype)
    mesh = E.serving_mesh(1, devices=[dev])
    inside = {s: {} for s in scopes}
    outside = {}

    def file(text):
        ins, out = scoped_keys(text, scopes)
        for s in scopes:
            for k, n in ins[s].items():
                inside[s][k] = inside[s].get(k, 0) + n
        for k, n in out.items():
            outside[k] = outside.get(k, 0) + n

    i32 = jnp.int32
    for S in s_buckets:
        def step(p, t, k, v, i, pt, ln):
            def one(carry, _):
                t, ln, k, v, i = carry
                lg, k, v, i = llama.forward_decode(
                    p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh,
                    i_pool=i, stats={})
                return (jnp.argmax(lg[:, 0], -1).astype(i32), ln + 1, k, v,
                        i), None
            return jax.lax.scan(one, (t, ln, k, v, i), None, length=N)[0]
        file(jax.jit(step, donate_argnums=(2, 3, 4)).lower(
            params, sds((B,), i32), pool, pool, ipool,
            sds((B, S // page), i32), sds((B,), i32)).compile().as_text())
        for C in c_buckets:
            def chunk(p, t, pos, k, v, i, w, ri, rp, rv, li):
                return llama.forward(
                    p, cfg, t, pos, k, v, w, ri, rp, rv, attn_impl="flash",
                    mesh=mesh, logits_idx=li, read_pages=ri[:, ::page] // page,
                    i_pool=i, stats={})
            file(jax.jit(chunk, donate_argnums=(3, 4, 5)).lower(
                params, sds((1, C), i32), sds((1, C), i32), pool, pool, ipool,
                sds((1, C), i32), sds((1, S), i32), sds((1, S), i32),
                sds((1, S), jnp.bool_), sds((1,), i32)).compile().as_text())
        print(f"S {S}: compiled", file=sys.stderr, flush=True)
    print(json.dumps({
        "config": name, "context_buckets": s_buckets,
        "chunk_buckets": c_buckets, "lanes": B,
        "scopes": {s: {"ops": sorted(inside[s]),
                       "shared": {k: shared_part(s, k, cfg)
                                  for k in sorted(inside[s]) if k in outside},
                       "required": required(s, sorted(inside[s]), cfg, B)}
                   for s in scopes}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
