#!/usr/bin/env python3
"""Builder's tool, no chip: lower (not compile) each configuration's decode
step and prefill chunk for a DESCRIBED v5e, as the engine's bucket programs
call ``llama.forward_decode`` / ``llama.forward`` (context read by page,
``stats``, the index-key pool of a model with an indexer), and print the
SHA-256 of each program's StableHLO text, Mosaic kernels included.

    JAX_PLATFORMS=cpu python benchmarks/tests/lowered_same.py [--layers N] [config ...]

Two trees whose lines agree trace and lower a configuration's layer body,
pool access and kernels to the same program: what a PR that touches the
shared decoder layer for a NEW model owes the models the benchmark has
(PR 32 lost 20 % of every cell's ``setup_s`` there). It uses nothing a tree
older than itself lacks, so it runs in a parent checkout unchanged (copy
this file there): run it in a ``git archive`` of the parent and in the
change, and compare the lines. No fixture pins a commit's lines: a PR that
is MEANT to alter these programs may not edit a file here to renew one, and
the engine's own programs are held in tier-1
(``tests/test_kv_write_pages.py``, ``tests/test_stored_params.py``,
``tests/test_chip_compile.py``). It lowers ``llama.init_params`` shapes and
a plain pool, not the stored parameters and the page-run write the engine
runs. Not part of any check.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

CONFIGS = ("qwen2-1.5b", "mistral-7b-16l", "keye-vl2-30b-a3b-6l")


def lowered(name: str, layers=None, dev=None) -> dict:
    """-> {program: StableHLO text} of configuration ``name``'s decode step
    (largest context bucket) and prefill chunk (largest chunk, that
    bucket), at ``layers`` layers (None: as the configuration runs)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel.mesh import serving_mesh

    if dev is None:
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=SingleDeviceSharding(dev))
    mesh = serving_mesh(1, devices=[dev])
    config = Catalog().data("configs", name)
    eng = config["benchmark"]["engine"]
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    if layers:
        hf["num_hidden_layers"] = layers
    cfg = llama.LlamaConfig.from_hf_config(hf)
    page, B = eng["page_size"], eng["max_batch"]
    pad = -(-2 * eng["decode_steps"] // page) * page
    S = -(-(eng["max_context"] + pad) // 128) * 128
    C = eng["prefill_chunk"]
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), shapes)
    pool = sds((cfg.num_layers, cfg.num_kv_heads, eng["num_pages"], page,
                cfg.head_dim), cfg.dtype)
    extra = ()
    if cfg.has_indexer:
        extra = (sds(llama.index_pool_shape(cfg, eng["num_pages"], page),
                     cfg.dtype),)

    def decode(p, t, k, v, pt, ln, *ip):
        stats = {}
        out = llama.forward_decode(
            p, cfg, t, k, v, pt, ln, attn_impl="pallas", mesh=mesh,
            stats=stats, **({"i_pool": ip[0]} if ip else {}))
        return out, stats

    def prefill(p, t, pos, k, v, wi, ri, rp, rv, li, *ip):
        stats = {}
        out = llama.forward(
            p, cfg, t, pos, k, v, wi, ri, rp, rv, attn_impl="flash",
            mesh=mesh, logits_idx=li, stats=stats,
            **({"i_pool": ip[0]} if ip else {}),
            read_pages=ri[:, ::page] // page)
        return out, stats

    i32 = jnp.int32
    programs = {
        "decode_step": (decode, (
            params, sds((B,), i32), pool, pool, sds((B, S // page), i32),
            sds((B,), i32), *extra)),
        "prefill_chunk": (prefill, (
            params, sds((1, C), i32), sds((1, C), i32), pool, pool,
            sds((1, C), i32), sds((1, S), i32), sds((1, S), i32),
            sds((1, S), jnp.bool_), sds((1,), i32), *extra)),
    }
    return {what: jax.jit(fn).lower(*args).as_text()
            for what, (fn, args) in programs.items()}


def without_locations(text: str) -> str:
    """``text`` with every Mosaic kernel's serialised body (MLIR bytecode,
    which carries the kernel's source file and line numbers, so it changes
    with any edit above it and with the checkout's path) replaced by the
    SHA-256 of the same module printed without debug information."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def plain(m):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))
                                  ).operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22sha256:%s\\22' % hashlib.sha256(
            asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, text)


def digests(name: str, layers=None, dev=None) -> dict:
    return {what: hashlib.sha256(without_locations(text).encode()).hexdigest()
            for what, text in lowered(name, layers, dev).items()}


def main(argv) -> int:
    layers = None
    if argv[:1] == ["--layers"]:
        layers, argv = int(argv[1]), argv[2:]
    for name in argv or CONFIGS:
        print(json.dumps({"config": name, "layers": layers,
                          **digests(name, layers)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
