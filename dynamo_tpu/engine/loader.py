"""Checkpoint loading: HF safetensors -> sharded stacked param pytree.

Maps the HF LlamaForCausalLM parameter names onto our stacked-layer layout
(llama.init_params structure) and device_puts each tensor directly into its
NamedSharding — per-shard placement, no full-model host copy beyond the
memory-mapped safetensors views.

Reference capability: the model-weight fast path noted in SURVEY §5.4
(safetensors -> sharded jax arrays is the only 'resume'-like path).
"""

from __future__ import annotations

import glob
import json
import os
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig


def _open_all(path: str) -> Dict[str, Any]:
    """tensor name -> (file, slice accessor) across all shards."""
    from safetensors import safe_open

    tensors: Dict[str, Any] = {}
    for fn in sorted(glob.glob(os.path.join(path, "*.safetensors"))):
        f = safe_open(fn, framework="numpy")
        for name in f.keys():
            tensors[name] = f
    return tensors


def _get(tensors: Dict[str, Any], name: str) -> np.ndarray:
    t = tensors[name].get_tensor(name)
    if t.dtype == np.uint16:  # bf16 stored raw
        t = t.view(jnp.bfloat16)
    return t


def load_llama_params_host(path: str, cfg: LlamaConfig) -> Dict[str, Any]:
    """Build the stacked host-numpy param tree from a safetensors dir
    WITHOUT any device placement — the weight-mobility cache pins these
    trees in host RAM so a later hot-swap pays only the h2d, and
    :func:`load_llama_params` device_puts the same tree at cold load."""
    if cfg.has_indexer or cfg.moe_intermediate_size:
        # no tensor names are published for this family (Keye-VL-2.0: index
        # projections, experts of their own width): guessing a layout would
        # load wrong weights without an error. Random init is the supported
        # path (no params_path).
        raise ValueError(
            f"cannot load a checkpoint from {path}: the tensor names of a "
            f"model with an indexer / experts of their own width are not "
            f"published; serve it without weights (random init)")
    tensors = _open_all(path)
    L, D, Hq, Hkv, Dh = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                         cfg.num_kv_heads, cfg.head_dim)
    # Gemma3 VLM checkpoints nest the text model under language_model; the
    # hub's actual naming is "language_model.model." (transformers <4.52
    # export), newer exports flatten to "model.language_model."
    pfx = ""
    for cand in ("model.language_model.", "language_model.model.",
                 "language_model.", "model."):
        if any(k.startswith(cand + "layers.") for k in tensors):
            pfx = cand
            break

    def lay(i: int, name: str) -> np.ndarray:
        return _get(tensors, f"{pfx}layers.{i}.{name}.weight")

    def stack(name: str, transform) -> np.ndarray:
        return np.stack([transform(lay(i, name)) for i in range(L)])

    dt = cfg.dtype
    # HF Llama calls the PRE-FFN norm "post_attention_layernorm"; Gemma2's
    # sandwich layout has four norms and names the pre-FFN one
    # "pre_feedforward_layernorm" instead
    ln2_name = ("pre_feedforward_layernorm" if cfg.sandwich_norms
                else "post_attention_layernorm")
    # HF Linear stores [out, in]; our layout is [in, ...out...]
    params: Dict[str, Any] = {
        "embed": _get(tensors, f"{pfx}embed_tokens.weight").astype(dt),
        "layers": {
            "ln1": stack("input_layernorm",
                         lambda w: w.astype(np.float32)).reshape(L, D),
            "ln2": stack(ln2_name,
                         lambda w: w.astype(np.float32)).reshape(L, D),
            "wq": stack("self_attn.q_proj",
                        lambda w: w.astype(dt).T.reshape(D, Hq, Dh)),
            "wk": stack("self_attn.k_proj",
                        lambda w: w.astype(dt).T.reshape(D, Hkv, Dh)),
            "wv": stack("self_attn.v_proj",
                        lambda w: w.astype(dt).T.reshape(D, Hkv, Dh)),
            "wo": stack("self_attn.o_proj",
                        lambda w: w.astype(dt).T.reshape(Hq, Dh, D)),
            "wg": stack("mlp.gate_proj", lambda w: w.astype(dt).T),
            "wu": stack("mlp.up_proj", lambda w: w.astype(dt).T),
            "wd": stack("mlp.down_proj", lambda w: w.astype(dt).T),
        },
        "final_norm": _get(tensors, f"{pfx}norm.weight").astype(np.float32),
    }
    if cfg.sandwich_norms:
        params["layers"]["ln1_post"] = stack(
            "post_attention_layernorm",
            lambda w: w.astype(np.float32)).reshape(L, D)
        params["layers"]["ln2_post"] = stack(
            "post_feedforward_layernorm",
            lambda w: w.astype(np.float32)).reshape(L, D)
    if cfg.qk_norm:
        params["layers"]["ln_q"] = stack(
            "self_attn.q_norm", lambda w: w.astype(np.float32)).reshape(
            L, Dh)
        params["layers"]["ln_k"] = stack(
            "self_attn.k_norm", lambda w: w.astype(np.float32)).reshape(
            L, Dh)
    if cfg.attention_bias:
        def bias(i, name, h):
            return _get(tensors, f"{pfx}layers.{i}.{name}.bias") \
                .astype(dt).reshape(h, Dh)

        params["layers"]["bq"] = np.stack(
            [bias(i, "self_attn.q_proj", Hq) for i in range(L)])
        params["layers"]["bk"] = np.stack(
            [bias(i, "self_attn.k_proj", Hkv) for i in range(L)])
        params["layers"]["bv"] = np.stack(
            [bias(i, "self_attn.v_proj", Hkv) for i in range(L)])
    if not cfg.tie_embeddings:
        # the VLM nesting puts lm_head BESIDE the inner model
        # ("language_model.lm_head.weight"), not under the layer prefix
        head = next(
            (k for k in ("lm_head.weight", f"{pfx}lm_head.weight",
                         pfx.rsplit("model.", 1)[0] + "lm_head.weight")
             if k in tensors), f"{pfx}lm_head.weight")
        params["lm_head"] = _get(tensors, head).astype(dt).T
    return params


def load_llama_params(path: str, cfg: LlamaConfig,
                      shardings: Dict[str, Any]) -> Dict[str, Any]:
    params = load_llama_params_host(path, cfg)
    from .engine import global_put
    from ..obs.flows import record_flow

    t0 = time.perf_counter()
    placed = jax.tree.map(lambda a, s: global_put(a, s), params, shardings)
    # one flow for the whole cold load: puts are enqueued async, so this
    # meters the enqueue wall-time, not the device copy — the swap path's
    # barrier-bounded record is the honest h2d rate
    record_flow("weight_prefetch",
                sum(int(np.asarray(a).nbytes)
                    for a in jax.tree.leaves(params)),
                time.perf_counter() - t0)
    return placed


def save_llama_params(path: str, params: Dict[str, Any], cfg: LlamaConfig) -> None:
    """Write params back out in HF layout (used by tests to round-trip):
    the published tree or the one an engine stores (``llama.stored_params``:
    a layer's ``wq`` / ``wk`` / ``wv`` [H x Dh, D] is the file's own
    ``[out, in]``)."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    # safetensors writes the raw buffer: every transposed view MUST be made
    # contiguous first or the transpose is silently lost
    C = np.ascontiguousarray
    L, D, Hq, Dh = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                    cfg.head_dim)
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"], np.float32),
        "model.norm.weight": np.asarray(params["final_norm"], np.float32),
    }
    sandwich = "ln1_post" in lp
    for i in range(L):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = np.asarray(lp["ln1"][i], np.float32)
        if sandwich:
            # Gemma2 naming: ln2 is the PRE-ffw norm; post_attention is
            # the attn-branch output norm (see load_llama_params)
            out[p + "pre_feedforward_layernorm.weight"] = np.asarray(
                lp["ln2"][i], np.float32)
            out[p + "post_attention_layernorm.weight"] = np.asarray(
                lp["ln1_post"][i], np.float32)
            out[p + "post_feedforward_layernorm.weight"] = np.asarray(
                lp["ln2_post"][i], np.float32)
        else:
            out[p + "post_attention_layernorm.weight"] = np.asarray(
                lp["ln2"][i], np.float32)
        for w, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj")):
            m = np.asarray(lp[w][i], np.float32)
            out[p + f"self_attn.{name}.weight"] = C(
                m if m.ndim == 2 else m.reshape(D, -1).T)
        out[p + "self_attn.o_proj.weight"] = C(np.asarray(
            lp["wo"][i], np.float32).reshape(Hq * Dh, D).T)
        out[p + "mlp.gate_proj.weight"] = C(np.asarray(lp["wg"][i], np.float32).T)
        out[p + "mlp.up_proj.weight"] = C(np.asarray(lp["wu"][i], np.float32).T)
        out[p + "mlp.down_proj.weight"] = C(np.asarray(lp["wd"][i], np.float32).T)
        if "ln_q" in lp:
            out[p + "self_attn.q_norm.weight"] = np.asarray(
                lp["ln_q"][i], np.float32)
            out[p + "self_attn.k_norm.weight"] = np.asarray(
                lp["ln_k"][i], np.float32)
        if "bq" in lp:
            out[p + "self_attn.q_proj.bias"] = C(np.asarray(
                lp["bq"][i], np.float32).reshape(-1))
            out[p + "self_attn.k_proj.bias"] = C(np.asarray(
                lp["bk"][i], np.float32).reshape(-1))
            out[p + "self_attn.v_proj.bias"] = C(np.asarray(
                lp["bv"][i], np.float32).reshape(-1))
    if "lm_head" in params:
        out["lm_head.weight"] = C(np.asarray(params["lm_head"], np.float32).T)
    save_file(out, os.path.join(path, "model.safetensors"))
