#!/usr/bin/env python3
"""Builder's tool, on the chip: what a float32 residual stream buys a
configuration against its float32 reference, before a PR decides
``LlamaConfig.stream_dtype`` for it.

    python scripts/stream_dtype_ab.py <config> [--seeds 3] [--tokens 1536]

One process, no server. For each seed: the server's seeded weights
(``llama.init_params``), one random prompt, ``llama.forward`` over it whole
(dense attention, bfloat16 weights, every routed layer by the dispatch the
row count gives) once with the stream in the model's dtype and once in
float32, and the configuration's reference (``benchmarks/references/``)
``full`` and ``int8`` on the same tokens. At the last 256 positions the
program's greedy token's log-probability is compared with the reference's
for that token in units of the reference's logit spread, as
``benchmarks/harness/correct.py`` compares a served sample: prints
``rel_rms`` of each stream against ``full`` and against ``int8`` (the sound
reading and the reading a limit has to keep out). Not the served path (no
chunks, no cache, no decode): the rounding and the routing flips are the
same. This process holds the chip: run it alone. Not part of any check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("config")
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--tokens", type=int, default=1536)
    p.add_argument("--positions", type=int, default=256)
    a = p.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.cache import cache_kinds
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    cat = Catalog()
    config = cat.data("configs", a.config)
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    ref = cat.module("references", config["benchmark"]["reference"])
    cfg = llama.LlamaConfig.from_hf_config(hf)
    T, n, page = a.tokens, a.positions, 64
    kinds = cache_kinds(cfg)
    ks, vs = kinds[0].pool_shapes(T // page + 1, page)

    def program(stream):
        # a function of its own a stream: jit keeps what it traced by the
        # function, and the property is read while tracing
        def run(params, tokens):
            llama.LlamaConfig.stream_dtype = property(lambda self: stream)
            ssm = {}
            if cfg.has_state:
                # (a recurrent state in float32 where the kind has one,
                # then the tail in the model's dtype)
                shapes = kinds[1].state_shapes(1)
                pools = [jnp.zeros(s, d) for s, d in zip(
                    shapes, [jnp.float32] * (len(shapes) - 1) + [cfg.dtype])]
                ssm = {"ssm": (*pools, jnp.zeros(1, jnp.int32),
                               jnp.ones(1, bool), jnp.full(1, T, jnp.int32))}
            out = llama.forward(
                params, cfg, tokens[None], jnp.arange(T)[None],
                jnp.zeros(ks, cfg.dtype), jnp.zeros(vs, cfg.dtype),
                (page + jnp.arange(T))[None], None, jnp.arange(T)[None],
                jnp.ones((1, T), bool),
                read_pages=(1 + jnp.arange(T // page))[None], **ssm)
            return jax.nn.log_softmax(
                out[0][0, T - n:].astype(jnp.float32), -1)
        return jax.jit(run)

    programs = {"model_dtype": program(cfg.dtype),
                "float32": program(jnp.float32)}
    was = llama.LlamaConfig.stream_dtype
    rows = []
    for seed in range(a.seeds):
        state = ref.build(hf, 4400 + seed)
        tokens = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, T).astype(np.int32)
        scored = {v: np.asarray(ref.tail_logprobs(state, tokens, T - n, n, v))
                  for v in ("full", "int8")}
        sigma = scored["full"].std(-1)
        row = {"seed": 4400 + seed}
        for name, fn in programs.items():
            logp = np.asarray(fn(state["params"], jnp.asarray(tokens)))
            tok = logp.argmax(-1)
            mine = logp[np.arange(n), tok]
            for v, theirs in scored.items():
                d = np.abs(mine - theirs[np.arange(n), tok]) / sigma
                row[f"{name}.{v}"] = round(float(np.sqrt((d ** 2).mean())), 5)
        rows.append(row)
        print(json.dumps(row), flush=True)
        del state
    llama.LlamaConfig.stream_dtype = was
    return 0


if __name__ == "__main__":
    sys.exit(main())
