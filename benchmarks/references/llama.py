"""Reference ``llama``: a float32 ``jax.numpy`` forward of the Llama-family
dense block (Qwen2, Mistral: RMSNorm, rotary GQA attention with optional
q/k/v bias, SwiGLU), written from the published description. No kernel, no
cache, no batching, ``jax.default_matmul_precision("highest")``.

A reference file offers ``build`` and ``tail_logprobs`` (the contract is in
``harness/catalog.py``); ``harness/reference.py`` runs it as a child while no
server holds the chip, and a configuration names it in
``benchmark.reference``.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls, so the same seed gives the same tensors. The layout of that tree is
the only thing this file knows of the program:

    embed [V,D]; final_norm [D]; lm_head [D,V] (absent when tied)
    layers.* stacked on L: ln1, ln2 [L,D]; wq [L,D,Hq,Dh]; wk, wv
    [L,D,Hkv,Dh]; wo [L,Hq,Dh,D]; wg, wu [L,D,F]; wd [L,F,D];
    bq [L,Hq,Dh], bk, bv [L,Hkv,Dh] (Qwen2 only)

Besides the model (``full``) it scores two deliberately broken ones, which
is how the tolerance in ``harness/correct.py`` was shown to separate them:
``dropped_layer`` (the last layer switched off) and ``int8`` (every weight
matrix rounded to int8 per output channel).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8")


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


def forward_tail(params, dims, tokens, first, n_tail, layers_on, int8=False):
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


def build(config: dict, seed: int) -> dict:
    """The weights as the server's seeded random init makes them, and the
    dimensions ``forward_tail`` reads. ``config`` is the published
    ``config.json`` (the configuration file without its ``benchmark`` group)."""
    import jax

    cfg = program.LlamaConfig.from_hf_config(config)
    params = jax.block_until_ready(
        program.init_params(cfg, jax.random.PRNGKey(int(seed))))
    return {"params": params, "dims": hf_dims(config)}


def tail_logprobs(state: dict, tokens, first: int, n_tail: int,
                  variant: str = "full"):
    """-> [n_tail, V] float32 log-softmax at positions first .. of the one
    padded sequence ``tokens`` [T]. One whole-sequence program per
    (n_tail, precision), compiled on first use and kept in the state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if variant not in VARIANTS:
        raise ValueError(f"no variant {variant!r} ({', '.join(VARIANTS)})")
    dims = state["dims"]
    key = (n_tail, variant == "int8")
    fn = state.setdefault("programs", {}).get(key)
    if fn is None:
        fn = state["programs"][key] = jax.jit(partial(
            forward_tail, dims=dims, n_tail=n_tail, int8=key[1]))
    on = np.ones(dims["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    return fn(state["params"], tokens=jnp.asarray(tokens), first=first,
              layers_on=jnp.asarray(on))
