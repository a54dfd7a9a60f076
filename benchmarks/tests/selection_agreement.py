#!/usr/bin/env python3
"""Builder's tool, on the chip: the SELECTED SETS and the CHOSEN EXPERTS of a
configuration with an indexer and routed experts, served path against
reference, at the published widths. Top-k selection and top-k routing are
discontinuous: a bfloat16 path and a float32 reference part at near-ties
with nothing wrong, and those partings are most of what a sound run reads
against the reference (PERF.md section 6, PR 28, second session). This shows
them: how many sets agree, and that every disagreement sits at a near-tie of
the float32 scores. (The control variants of the reference, ``top7``,
``experts_zeroed``, ``no_selection``, go through ``correct.compare`` itself:
``own_variants.py``.)

    python benchmarks/tests/selection_agreement.py <config> [tokens] [seed]
                                                   [xla]

One sequence of ``tokens`` (default 6144) random ids is run through the
layer loop exactly as the engine's bucket programs call it — ``llama.forward``
with the flash kernel in chunks of the configuration's ``prefill_chunk``
against paged pools of the configuration's page size, pages in order, then
the last 32 positions one at a time through ``llama.forward_decode`` and the
paged dma kernel — in bfloat16 with the program's seeded weights, and through
``references/<name>.py`` ``trace`` in float32 on the same weights. Printed:
per layer the share of queries whose selected set is the reference's, the
share of selected keys that agree, the share of tokens whose expert set is
the reference's, and for every disagreement the float32 margin it sits at
(index score minus the query's k-th largest, in units of the spread of the
query's visible scores; router logit of the K-th expert over the next one).
This process holds the chip: run it alone. Not part of any check.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def served_sets(cfg, params, tokens, chunk, page, n_dec=32, impl=None):
    """-> (keep [L,T,T] bool, chosen [L,T,K], log-softmax [n_dec,V] at the
    decoded positions) of the bfloat16 layer loop. ``impl`` "xla" takes the
    kernels out (a builder's control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama

    T = len(tokens)
    S = -(-(T + page) // 128) * 128
    S = -(-S // page) * page
    n_pages = S // page
    shape = (cfg.num_layers, cfg.num_kv_heads, n_pages + 1, page,
             cfg.head_dim)
    kp, vp = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
    ip = jnp.zeros(llama.index_pool_shape(cfg, n_pages + 1, page), cfg.dtype)
    pages = np.arange(1, n_pages + 1, dtype=np.int32)[None]
    slots = (pages[0][:, None] * page + np.arange(page)[None]).reshape(-1)
    rpos = np.arange(S, dtype=np.int32)[None]
    tpu = jax.devices()[0].platform == "tpu" and impl != "xla"

    # the weights are ARGUMENTS: closed over, 8.75 GB would be lowered as
    # constants of the program, through the host
    @jax.jit
    def prefill(params, toks, pos, kp, vp, ip, w, valid):
        stats = {"keep": [], "chosen": []}
        out = llama.forward(
            params, cfg, toks, pos, kp, vp, w, None, jnp.asarray(rpos),
            valid, attn_impl="flash" if tpu else "xla",
            read_pages=jnp.asarray(pages), i_pool=ip, stats=stats,
            logits_idx=jnp.zeros(1, jnp.int32))
        return out[1:], stats

    @jax.jit
    def decode(params, tok, kp, vp, ip, length):
        stats = {"keep": [], "chosen": []}
        out = llama.forward_decode(
            params, cfg, tok, kp, vp, jnp.asarray(pages), length,
            attn_impl="pallas" if tpu else "xla", i_pool=ip, stats=stats)
        return out[1:], stats, jax.nn.log_softmax(out[0][0, 0])

    keep = np.zeros((cfg.num_layers, T, T), bool)
    chosen = np.zeros((cfg.num_layers, T, cfg.experts_per_token), np.int32)

    def take(stats, t0, t1):
        n = t1 - t0                                 # rows that are tokens
        for l in range(cfg.num_layers):
            k = stats["keep"][l]
            keep[l, t0:t1] = (np.asarray(k[0])[:n, :T] if k is not None else
                              np.tril(np.ones((T, T), bool))[t0:t1])
            chosen[l, t0:t1] = np.asarray(stats["chosen"][l][0])[:n]

    for c0 in range(0, T - n_dec, chunk):
        c1 = min(c0 + chunk, T - n_dec)
        pos = np.arange(c0, c0 + chunk, dtype=np.int32)[None]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : c1 - c0] = tokens[c0:c1]
        w = np.zeros((1, chunk), np.int32)          # padding -> scratch page
        w[0, : c1 - c0] = slots[c0:c1]
        (kp, vp, ip), stats = prefill(params, toks, pos, kp, vp, ip, w,
                                      rpos < c1)
        take(stats, c0, c1)
    logp = []
    for t in range(T - n_dec, T):
        (kp, vp, ip), stats, lp = decode(params, np.asarray([tokens[t]]), kp,
                                         vp, ip, np.asarray([t + 1]))
        take(stats, t, t + 1)
        logp.append(np.asarray(lp))
    return keep, chosen, np.stack(logp)


def compare(cat, config: dict, T: int, seed: int, impl=None) -> dict:
    import jax
    import numpy as np

    from dynamo_tpu.models import llama

    hf = {k: v for k, v in config.items() if k != "benchmark"}
    module = cat.module("references", config["benchmark"]["reference"])
    state = module.build(hf, seed)
    cfg = llama.LlamaConfig.from_hf_config(hf)
    eng = config["benchmark"]["engine"]
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, T).astype(np.int32)
    keep, chosen, logp = served_sets(cfg, state["params"], tokens,
                                     eng["prefill_chunk"], eng["page_size"],
                                     impl=impl)
    n_dec = len(logp)
    padded = np.zeros(-(-T // 128) * 128, np.int32)
    padded[:T] = tokens
    want_lp = np.asarray(module.tail_logprobs(state, padded, T - n_dec,
                                              n_dec, "full"))
    best = logp.argmax(-1)
    rows = np.arange(n_dec)
    rel = np.abs(logp[rows, best] - want_lp[rows, best]) / want_lp.std(-1)
    sel, want, (scores, probs) = jax.tree.map(
        np.asarray, module.trace(state, tokens, detail=True))
    k = cfg.index_topk
    causal = np.tril(np.ones((T, T), bool))
    out = {"tokens": T, "seed": seed, "topk": k, "impl": impl or "kernels",
           "decoded_positions": n_dec,
           "decoded_rel_rms": float(np.sqrt((rel ** 2).mean())),
           "decoded_rel_max": float(rel.max()),
           "decoded_argmax_agree": int((best == want_lp.argmax(-1)).sum()),
           "device": jax.devices()[0].device_kind, "layers": []}
    for l in range(cfg.num_layers):
        differ = keep[l] != sel[l]
        binds = np.arange(T) >= k                   # queries that select
        rows = differ.any(-1)
        vis = np.where(causal, scores[l], np.nan)
        spread = np.nanstd(vis, axis=-1)
        kth = -np.sort(-np.where(causal, scores[l], -np.inf), axis=-1)[
            :, min(k, T) - 1]
        margin = np.abs(scores[l] - kth[:, None]) / spread[:, None]
        same_experts = (np.sort(chosen[l], -1) == np.sort(want[l], -1)
                        ).all(-1)
        p = -np.sort(-probs[l], axis=-1)
        K = cfg.experts_per_token
        gap = np.log(p[:, K - 1]) - np.log(p[:, K])     # in router logits
        out["layers"].append({
            "queries_that_select": int(binds.sum()),
            "selected_set_identical_share": float(
                1.0 - rows[binds].mean()) if binds.any() else None,
            "selected_keys_agree_share": float(
                (keep[l] & sel[l]).sum() / sel[l].sum()),
            "selected_per_query_ok": bool(
                (keep[l].sum(-1) == np.minimum(np.arange(T) + 1, k)).all()),
            "disagreeing_keys": int(differ.sum()),
            "disagreement_margin_max_sigma": float(
                margin[differ].max()) if differ.any() else 0.0,
            "disagreement_margin_p99_sigma": float(
                np.quantile(margin[differ], 0.99)) if differ.any() else 0.0,
            "experts_identical_share": float(same_experts.mean()),
            "expert_disagreement_gap_max": float(
                gap[~same_experts].max()) if (~same_experts).any() else 0.0,
            "expert_gap_median_all": float(np.median(gap)),
        })
    return out


def main(argv) -> int:
    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    cat = Catalog()
    print(json.dumps({"config": argv[0], **compare(
        cat, cat.data("configs", argv[0]),
        int(argv[1]) if len(argv) > 1 else 6144,
        int(argv[2]) if len(argv) > 2 else 28000001,
        argv[3] if len(argv) > 3 else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
