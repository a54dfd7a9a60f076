"""Reference ``tiny_moe``: a float32 forward of the Mixtral block at the
program's ``tiny-moe`` sizes, written from the published description:
pre-norm rotary GQA attention without bias, then a sparse mixture of SwiGLU
experts: a linear router, a softmax over ALL experts, the top ``k`` of them,
their probabilities renormalised to sum to one, and the experts' outputs
added with those weights. Every expert is computed for every token and the
unchosen ones get the weight zero: no sorting, no segments, no capacity.

A throw-away for the rehearsal. It exists to show that the reference of
another architecture is a new file (the contract is in
``harness/catalog.py``), and shares no line with ``references/llama.py`` or
with the program's ``models/moe.py``. From the program it takes the weights
as data: ``llama.init_params`` of the ``tiny-moe`` preset under the job's
seed, whose tree it reads as

    embed [V,D]; final_norm [D]; lm_head [D,V]
    layers.* stacked on L: ln1, ln2 [L,D]; wq [L,D,Hq,Dh]; wk, wv
    [L,D,Hkv,Dh]; wo [L,Hq,Dh,D]; wr [L,D,E] (router); wg, wu [L,E,D,F];
    wd [L,E,F,D]
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8")


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T,H,Dh] at positions 0..T-1; pairs are (i, i + Dh/2)."""
    T, _, dh = x.shape
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def _block(x, lp, d, on, int8):
    """One layer on x [T,D]; ``lp`` one layer's weights, already float32."""
    w = (lambda name, ax: _int8(lp[name], ax)) if int8 else (
        lambda name, ax: lp[name])
    T = x.shape[0]
    h = _norm(x, lp["ln1"], d["eps"])
    q = _rotate(jnp.einsum("td,dhk->thk", h, w("wq", (0,))), d["theta"])
    k = _rotate(jnp.einsum("td,dhk->thk", h, w("wk", (0,))), d["theta"])
    v = jnp.einsum("td,dhk->thk", h, w("wv", (0,)))
    k = jnp.repeat(k, d["Hq"] // d["Hkv"], axis=1)     # head h reads h // G
    v = jnp.repeat(v, d["Hq"] // d["Hkv"], axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) / math.sqrt(d["Dh"])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    x = x + on * jnp.einsum("thk,hkd->td", a, w("wo", (0, 1)))

    h = _norm(x, lp["ln2"], d["eps"])
    prob = jax.nn.softmax(h @ w("wr", (0,)), axis=-1)            # [T,E]
    kth = jnp.sort(prob, axis=-1)[:, -d["K"]][:, None]
    gate = jnp.where(prob >= kth, prob, 0.0)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    up = jnp.einsum("td,edf->tef", h, w("wu", (1,)))
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, w("wg", (1,)))) * up
    y = jnp.einsum("tef,efd->ted", act, w("wd", (1,)))
    return x + on * jnp.einsum("te,ted->td", gate, y)


def _forward(params, d, tokens, first, n_tail, layers_on, int8):
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens]
        for l in range(d["L"]):
            x = _block(x, jax.tree.map(lambda a: a[l], p["layers"]), d,
                       layers_on[l], int8)
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        head = _int8(p["lm_head"], (0,)) if int8 else p["lm_head"]
        return jax.nn.log_softmax(
            _norm(x, p["final_norm"], d["eps"]) @ head, axis=-1)


def build(config: dict, seed: int) -> dict:
    cfg = dataclasses.replace(
        program.LlamaConfig.from_hf_config(config),
        num_experts=int(config["num_local_experts"]),
        experts_per_token=int(config["num_experts_per_tok"]))
    if cfg != program.preset("tiny-moe"):
        # the rehearsal serves the preset, not this file's sizes
        raise ValueError(f"the configuration is not the program's tiny-moe "
                         f"preset: {cfg} != {program.preset('tiny-moe')}")
    params = jax.block_until_ready(
        program.init_params(cfg, jax.random.PRNGKey(int(seed))))
    d = {"L": cfg.num_layers, "Hq": cfg.num_heads, "Hkv": cfg.num_kv_heads,
         "Dh": cfg.head_dim, "K": cfg.experts_per_token,
         "theta": float(config["rope_theta"]),
         "eps": float(config["rms_norm_eps"])}
    return {"params": params, "dims": d, "programs": {}}


def tail_logprobs(state: dict, tokens, first: int, n_tail: int,
                  variant: str = "full"):
    if variant not in VARIANTS:
        raise ValueError(f"no variant {variant!r} ({', '.join(VARIANTS)})")
    d = state["dims"]
    key = (n_tail, variant == "int8")
    if key not in state["programs"]:
        state["programs"][key] = jax.jit(partial(
            _forward, d=d, n_tail=n_tail, int8=key[1]))
    on = np.ones(d["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    return state["programs"][key](state["params"], tokens=jnp.asarray(tokens),
                                  first=first, layers_on=jnp.asarray(on))
