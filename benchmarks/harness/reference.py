"""The plain reference: a float32 ``jax.numpy`` forward of the Llama-family
block (Qwen2, Mistral: RMSNorm, rotary GQA attention with optional q/k/v
bias, SwiGLU), written from the published description. No kernel, no cache,
no batching, ``jax.default_matmul_precision("highest")``.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls, so the same seed gives the same tensors. The layout of that tree is
the only thing this file knows of the program:

    embed [V,D]; final_norm [D]; lm_head [D,V] (absent when tied)
    layers.* stacked on L: ln1, ln2 [L,D]; wq [L,D,Hq,Dh]; wk, wv
    [L,D,Hkv,Dh]; wo [L,Hq,Dh,D]; wg, wu [L,D,F]; wd [L,F,D];
    bq [L,Hq,Dh], bk, bv [L,Hkv,Dh] (Qwen2 only)

Run as a child while no server holds the chip:

    python -m benchmarks.harness.reference <in.json> <out.json>

``in.json``: {"config": <hf config>, "seed": n, "samples": [{"prompt":
[...], "served": [...]}], "probe": false}. For every sample the served
tokens are teacher-forced (one full forward over prompt + served tokens) and
``out.json`` gives, per generated position, the reference log-probability of
the served token, the reference's own best log-probability and its argmax,
and the standard deviation of the reference logits over the vocabulary (the
scale against which a difference is small or large).
With ``probe`` it also gives the same under two deliberately broken models
(last layer dropped; weights rounded to int8 per output channel), which is
how the tolerance in ``correct.py`` was shown to separate them.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from functools import partial


def hf_dims(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    return {
        "L": hf["num_hidden_layers"], "D": hf["hidden_size"], "Hq": heads,
        "Hkv": hf.get("num_key_value_heads", heads),
        "Dh": hf.get("head_dim", hf["hidden_size"] // heads),
        "F": hf["intermediate_size"], "V": hf["vocab_size"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rotary(x, positions, theta):
    """x [T,H,Dh]; the published (rotate-half) convention: the first and
    second halves of a head are the pairs."""
    import jax.numpy as jnp

    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fake_int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def layer(x, lp, dims, on, int8):
    """One block on x [T,D] float32; ``lp`` is one layer's slice of the
    stacked weights. ``on`` (0 or 1) switches the layer off for the probe."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = (lambda w, ax: fake_int8(w, ax)) if int8 else (lambda w, ax: w)
    T = x.shape[0]
    Hq, Hkv, Dh = dims["Hq"], dims["Hkv"], dims["Dh"]
    pos = jnp.arange(T)
    h = rms_norm(x, f32(lp["ln1"]), dims["eps"])
    q = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wq"]), (0,)))
    k = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wk"]), (0,)))
    v = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wv"]), (0,)))
    if "bq" in lp:
        q, k, v = q + f32(lp["bq"]), k + f32(lp["bk"]), v + f32(lp["bv"])
    q, k = rotary(q, pos, dims["theta"]), rotary(k, pos, dims["theta"])
    # query head h reads key/value head h // (Hq // Hkv)
    qg = q.reshape(T, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("tgqk,sgk->gqts", qg, k) / math.sqrt(Dh)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("gqts,sgk->tgqk", p, v).reshape(T, Hq, Dh)
    x = x + on * jnp.einsum("thk,hkd->td", a, q8(f32(lp["wo"]), (0, 1)))
    h = rms_norm(x, f32(lp["ln2"]), dims["eps"])
    g = jax.nn.silu(h @ q8(f32(lp["wg"]), (0,))) * (h @ q8(f32(lp["wu"]), (0,)))
    return x + on * (g @ q8(f32(lp["wd"]), (0,)))


def tail_logprobs(params, dims, tokens, first, n_tail, layers_on, int8=False):
    """log-softmax over the vocabulary at positions first .. first+n_tail-1
    of one sequence ``tokens`` [T] (causal, so padding after them is inert)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)

        def body(x, xs):
            lp, on = xs
            return layer(x, lp, dims, on, int8), None

        x, _ = jax.lax.scan(body, x, (params["layers"], layers_on))
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), dims["eps"])
        head = (params["lm_head"] if "lm_head" in params
                else params["embed"].T).astype(jnp.float32)
        if int8:
            head = fake_int8(head, (0,))
        return jax.nn.log_softmax(x @ head, axis=-1)


def score_samples(params, dims, samples, variant="full"):
    """Teacher-force each sample; -> per sample dict of lists."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_tail = max(len(s["served"]) for s in samples)
    T = max(len(s["prompt"]) for s in samples) + n_tail
    T = -(-T // 128) * 128
    on = np.ones(dims["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    fn = jax.jit(partial(tail_logprobs, dims=dims, n_tail=n_tail,
                         int8=(variant == "int8")))
    out = []
    for s in samples:
        served = list(s["served"])
        seq = list(s["prompt"]) + served[:-1]
        toks = np.zeros(T, np.int32)
        toks[: len(seq)] = seq
        lp = np.asarray(fn(params, tokens=jnp.asarray(toks),
                           first=len(s["prompt"]) - 1,
                           layers_on=jnp.asarray(on)))[: len(served)]
        out.append({
            "logit_std": [float(x) for x in lp.std(-1)],
            "served_logprob": [float(lp[i, t]) for i, t in enumerate(served)],
            "best_logprob": [float(x) for x in lp.max(-1)],
            "best_token": [int(x) for x in lp.argmax(-1)],
        })
    return out


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    with open(src) as f:
        job = json.load(f)
    t0 = time.monotonic()
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # the cache directory the server uses, so one limit bounds both
        from benchmarks.harness.catalog import ROOT

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from dynamo_tpu.models import llama

    t1 = time.monotonic()
    cfg = llama.LlamaConfig.from_hf_config(job["config"])
    params = jax.block_until_ready(
        llama.init_params(cfg, jax.random.PRNGKey(int(job["seed"]))))
    t2 = time.monotonic()
    dims = hf_dims(job["config"])
    result = {"device": {"platform": jax.devices()[0].platform,
                         "kind": jax.devices()[0].device_kind},
              "full": score_samples(params, dims, job["samples"])}
    # the same two steps the server's start-up makes first, timed here
    # because the server logs neither: a baseline for a start-up PR
    result["timing"] = {"import_and_devices_s": t1 - t0,
                        "init_params_s": t2 - t1,
                        "score_s": time.monotonic() - t2}
    if job.get("probe"):
        for variant in ("dropped_layer", "int8"):
            result[variant] = score_samples(params, dims, job["samples"],
                                            variant)
    with open(dst, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
