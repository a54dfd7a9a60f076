#!/usr/bin/env python3
"""Builder's tool, on the chip: the number that tells int8 weights from a
sound run in ``deepseek-v2-5l``, which ``rel_rms`` as the harness takes it
does not (``configs/deepseek-v2-5l.json`` ``reference_tolerance``).

    python benchmarks/tests/served_routing.py [--seeds 1 2 3] [--tiny]

Why. Top-6 routing inside 3 of 8 groups is discontinuous, the program's
router sees a stream that carries bfloat16 rounding and the float32
reference's does not, so at a near-tie the two route differently with
nothing wrong; one such flip at a scored position moves its logits by
0.04-0.35 sigma, and flips at a tenth of the positions carry most of a sound
run's squared distance and as much of an int8 run's. The served routing does
not leave the server, so the harness cannot take the flips out. This tool
can: no server, one process. The program's own ``llama.forward`` (bfloat16,
dense attention, the same seeded weights) generates 4 x 64 tokens greedily
after 576-token prompts and hands over its chosen experts
(``stats["chosen"]``); the float32 reference scores those tokens (a) routing
by itself, near-ties mixed, as the harness has it, and (b) made to route as
the program did (``deepseek_v2.route(forced=)``); both against ``full`` and
against ``int8``. The distance is ``correct.compare``'s: |served -
reference| log-probability of the served token over the reference's logit
spread, root mean square over the 256 positions.

One JSON line a seed; exit 1 unless every (b) reading of ``full`` lies under
``reference_tolerance.rel_rms_served_routing`` and every one of ``int8``
over it. ``--tiny`` rehearses the code on the CPU at a toy size (no limit
held).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

TINY = {"hidden_size": 128, "num_hidden_layers": 3, "num_attention_heads": 8,
        "num_key_value_heads": 8, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 8, "n_shared_experts": 2,
        "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
        "vocab_size": 512, "max_position_embeddings": 1024,
        "expert_shard": {"router_experts": 32, "first_expert": 0}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from benchmarks.references import deepseek_v2 as ref
    from dynamo_tpu.engine.cache import cache_kinds
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    config = Catalog().data("configs", "deepseek-v2-5l")
    limit = config["benchmark"]["reference_tolerance"][
        "rel_rms_served_routing"]
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    B, Tp, N, page = 4, 576, 64, 64
    if a.tiny:
        hf, B, Tp, N, page, limit = {**hf, **TINY}, 2, 48, 16, 8, None
    T = Tp + N                              # the padded sequence, whole pages
    cfg = llama.LlamaConfig.from_hf_config(hf)
    dims = ref.hf_dims(hf)
    kind, = cache_kinds(cfg)
    P = T // page

    @jax.jit
    def program(params, tokens):
        """[B,T] tokens -> (log-softmax [B,T,V] as the program's head makes
        it, chosen experts [routed layers,B,T,K])."""
        kp, vp = (jnp.zeros(s, cfg.dtype)
                  for s in kind.pool_shapes(B * P + 1, page))
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        pages = 1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
        slots = jnp.take_along_axis(pages, pos // page, 1) * page + pos % page
        stats = {"chosen": []}
        logits, *_ = llama.forward(
            params, cfg, tokens, pos, kp, vp, slots, None, pos,
            jnp.ones((B, T), bool), read_pages=pages, attn_impl="xla",
            stats=stats)
        return (jax.nn.log_softmax(logits, -1),
                jnp.stack([c for c in stats["chosen"] if c is not None]))

    layers = {}

    def reference(params, tokens, how, forced=None):
        """One sequence [T] through the reference's layers and head, a
        program a kind of layer as ``deepseek_v2.forward_tail`` has them.
        -> log-softmax [T,V]."""
        def step(x, at, ia, ff, jf, forced):
            with jax.default_matmul_precision("highest"):
                return ref.layer(x, ref._at(at, ia), ref._at(ff, jf), dims,
                                 1.0, how, forced=forced)[0]
        x = params["embed"][tokens].astype(jnp.float32)
        r = 0
        for l, (at, ia, ff, jf) in enumerate(ref._layers(params, dims)):
            routed = bool(dims["routed"][l])
            f = forced[r] if routed and forced is not None else None
            key = (routed, f is not None, how["int8"])
            if key not in layers:
                layers[key] = jax.jit(step)
            x = layers[key](x, at, ia, ff, jf, f)
            r += routed
        key = ("head", how["int8"])
        if key not in layers:
            layers[key] = jax.jit(partial(ref._head_step, n_tail=T,
                                          dims=dims, how=how))
        return np.asarray(layers[key](x, params["final_norm"],
                                      params["lm_head"], 0))

    scored = np.arange(Tp - 1, T - 1)        # positions that predict a token

    def distance(served, want, tokens):
        nxt = tokens[scored + 1]
        d = np.abs(served[scored, nxt] - want[scored, nxt])
        return d / want[scored].std(-1)

    rms = lambda e: float(np.sqrt(np.mean(np.square(np.concatenate(e)))))
    hows = {"full": dict(ref.HOW), "int8": {**ref.HOW, "int8": True}}
    ok = True
    for seed in a.seeds:
        params = ref.build(hf, seed)["params"]
        toks = np.zeros((B, T), np.int32)
        toks[:, :Tp] = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (B, Tp))
        for i in range(N):                   # greedy, one token a pass
            lp, _ = program(params, jnp.asarray(toks))
            toks[:, Tp + i] = np.asarray(jnp.argmax(lp[:, Tp + i - 1], -1))
        lp, chosen = (np.asarray(x) for x in program(params,
                                                      jnp.asarray(toks)))
        rec = {"seed": seed, "positions": B * N}
        for name, how in hows.items():
            own, forced = [], []
            for b in range(B):
                t = jnp.asarray(toks[b])
                own.append(distance(lp[b], reference(params, t, how),
                                    toks[b]))
                forced.append(distance(lp[b], reference(
                    params, t, how, jnp.asarray(chosen[:, b])), toks[b]))
            rec[name] = {"rel_rms": rms(own),
                         "rel_rms_served_routing": rms(forced)}
        if limit is not None:
            rec["limit"] = limit
            rec["separated"] = (
                rec["full"]["rel_rms_served_routing"] < limit
                < rec["int8"]["rel_rms_served_routing"])
            ok = ok and rec["separated"]
        print(json.dumps(rec), flush=True)
        del params
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
