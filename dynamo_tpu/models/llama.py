"""Llama model family (Llama 2/3/3.x, DeepSeek-R1-Distill-Llama) — functional
JAX implementation built for paged-KV serving.

Design (TPU-first, not a torch translation):
- Params are a plain pytree of stacked per-layer weights; sharding is declared
  once as PartitionSpecs (tp over heads / ffn) and applied with NamedSharding —
  XLA inserts all collectives.
- The KV cache is a flat paged pool ([L, N_tokens_pool, H_kv, D_h]); sequences
  own pages via integer page tables. Writes are scatters at token indices,
  reads are gathers — both static-shaped so every step compiles once.
- One forward function serves both prefill chunks (T>1) and decode (T=1):
  write-then-gather with a causal+length mask. Static shapes everywhere
  (bucketed T and S) per XLA's compile-once model.
- One decoder layer (``layer_in`` / ``layer_out``, "The decoder layer"
  below), which every way of serving calls around its own attention:
  ``forward``, ``forward_decode``, ``forward_pp``'s stage body and the
  pager's programs (``llm/kvpage/programs.py``).
- bf16 weights/activations, fp32 norms/softmax/logits (MXU-friendly).

Reference capability equivalent: the in-engine model executed by vLLM/TRT-LLM
behind the reference's engine adapters (SURVEY §2.1, §7 step 3).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_EP, AXIS_TP


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

_GEMMA_ARCHS = ("GemmaForCausalLM", "Gemma2ForCausalLM",
                "Gemma3ForCausalLM")


_GEMMA_VLM_ARCH = "Gemma3ForConditionalGeneration"

# Gemma3TextConfig defaults (transformers): real hub checkpoints ship sparse
# text_configs that omit these entirely (e.g. google/gemma-3-4b-it's
# text_config has no vocab_size) and rely on the class defaults — without
# them from_hf_config KeyErrors at startup on a real checkpoint.
_GEMMA3_TEXT_DEFAULTS: Dict[str, Any] = {
    "vocab_size": 262208,
    "hidden_size": 2304,
    "intermediate_size": 9216,
    "num_hidden_layers": 26,
    "num_attention_heads": 8,
    "num_key_value_heads": 4,
    "head_dim": 256,
    "rope_theta": 1e6,
    "rope_local_base_freq": 10000.0,
    "query_pre_attn_scalar": 256,
    "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-6,
    # omitting sliding_window must NOT read as "no sliding attention":
    # the class default (4096) keeps layer_sliding() live
    "sliding_window": 4096,
}


def _is_gemma(cfg: Dict[str, Any]) -> bool:
    archs = cfg.get("architectures", []) or []
    # VLM Gemma3 configs are nested (text_config/vision_config) and handled
    # by from_hf_config before this runs on the flat text config
    unsupported = [a for a in archs
                   if "Gemma" in a and a not in _GEMMA_ARCHS
                   and a != _GEMMA_VLM_ARCH]
    if unsupported:
        raise ValueError(f"unsupported architecture {unsupported[0]!r} "
                         f"(text Gemma v1/v2/v3 and Gemma3 VLM are "
                         f"supported)")
    return any(a in _GEMMA_ARCHS for a in archs)


def _is_gemma2(cfg: Dict[str, Any]) -> bool:
    return "Gemma2ForCausalLM" in (cfg.get("architectures", []) or [])


def _is_gemma3(cfg: Dict[str, Any]) -> bool:
    return "Gemma3ForCausalLM" in (cfg.get("architectures", []) or [])


def _map_act(cfg: Dict[str, Any]) -> str:
    """HF activation name -> ours; exact vs tanh-approx GELU matters for
    logits parity, so unknown names raise instead of guessing."""
    if _is_gemma(cfg):
        return "gelu_tanh"
    act = str(cfg.get("hidden_activation")
              or cfg.get("hidden_act") or "silu")
    if act in ("silu", "swish"):
        return "silu"
    if act in ("gelu_pytorch_tanh", "gelu_tanh", "gelu_new",
               "gelu_fast"):
        return "gelu_tanh"
    if act == "gelu":
        return "gelu"
    raise ValueError(f"unsupported hidden_act {act!r}")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_eps: float = 1e-5
    max_position: int = 8192
    tie_embeddings: bool = False
    # q/k/v projection biases (Qwen2-style attention; Llama/Mistral: False)
    attention_bias: bool = False
    # Gemma-style family knobs: tanh-GELU gating (GeGLU), zero-centered
    # RMSNorm weights (output scales by 1+w), sqrt(D)-scaled embeddings
    hidden_act: str = "silu"            # "silu" | "gelu_tanh"
    norm_offset: bool = False
    embed_scale: bool = False
    # Gemma2-style knobs: 4 norms per layer (post-attn + post-ffn sandwich
    # norms), tanh softcapping of attention scores / final logits, sliding-
    # window attention on even layers, and an explicit attention scale
    # (rsqrt(query_pre_attn_scalar) instead of rsqrt(head_dim))
    sandwich_norms: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None
    query_pre_attn_scalar: Optional[float] = None
    # Gemma3-style knobs: every Nth layer is FULL attention, the rest
    # sliding (gemma2: 2 — alternating; gemma3: 6 — 5:1); sliding layers
    # rope at their own base frequency; per-head RMSNorm on q/k
    sliding_pattern: int = 2
    rope_local_theta: Optional[float] = None
    qk_norm: bool = False
    dtype: Any = jnp.bfloat16
    # MoE (0 experts = dense FFN). Experts shard over the ep mesh axis.
    num_experts: int = 0
    experts_per_token: int = 2
    # expert FFN width where it is not the dense width (None: the experts
    # are ``intermediate_size`` wide, Mixtral's convention)
    moe_intermediate_size: Optional[int] = None
    # learned sparse attention (DeepSeek-Sparse-Attention indexer over the
    # GQA cache; ``index_topk`` 0 = none): ``index_heads`` index query heads
    # of ``index_head_dim`` score every cached key through ONE index key
    # head; a query attends to its ``index_topk`` best keys only. The index
    # keys are cached beside K/V on the same pages (see "KV pool access").
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Gemma3 VLM: a SigLIP vision tower rides alongside the text stack
    # (HF vision_config dict; models/siglip.py builds from it). Image soft
    # tokens replace ``image_token_id`` placeholder embeddings at prefill.
    vision: Optional[Dict[str, Any]] = None
    mm_tokens_per_image: int = 256
    image_token_id: Optional[int] = None
    # A per-layer description where one law for all layers does not hold
    # (MiMo-V2-Flash; ROADMAP Design 2). ``layer_kinds[l]``: 0 full
    # attention, 1 window attention (``sliding_window`` keys with the
    # query's own), 2 a state-space mixer in place of attention (below), 3
    # a gated short convolution in its place (below);
    # ``ffn_kinds[l]``: 0 dense feed-forward, 1 routed experts. With ``layer_kinds`` the two attention kinds have parameter
    # stacks, head counts and CACHES of their own (:meth:`cache_kinds`):
    # window layers have ``window_kv_heads`` K/V heads and keep a window of
    # cache in a page pool of their own.
    layer_kinds: Optional[Tuple[int, ...]] = None
    ffn_kinds: Optional[Tuple[int, ...]] = None
    window_kv_heads: Optional[int] = None
    # V heads of a width of their own (None: ``head_dim``)
    v_head_dim: Optional[int] = None
    # rotary over the first ``rotary_dim`` dims of a q/k head, the rest
    # pass through (None: all of ``head_dim``)
    rotary_dim: Optional[int] = None
    # v is scaled by this before it is cached
    attn_value_scale: Optional[float] = None
    # a learned per-head logit that takes softmax weight and gives no
    # value, in the window layers / in the full layers
    sink_window: bool = False
    sink_full: bool = False
    # the router's law: "softmax" (softmax over all experts, top-k of the
    # probabilities, renormalised) or "sigmoid_bias" (sigmoid scores; top-k
    # of score + a learned selection bias; gates = the chosen SCORES over
    # their sum: the bias chooses and never weighs)
    router: str = "softmax"
    # a chip's share of the experts: the router is ``router_experts`` wide
    # (None: ``num_experts``), this chip holds experts ``expert_first ..
    # expert_first + num_experts - 1`` of them, routes over all and
    # computes the held part (models/moe.py)
    router_experts: Optional[int] = None
    expert_first: int = 0
    # the third law, ``softmax_group`` (DeepSeek-V2's group_limited_greedy):
    # softmax scores; the experts lie in ``router_groups[0]`` equal groups,
    # a group scores as its best expert, the ``router_groups[1]`` best
    # groups stay and the top-k is taken inside them; the gates are the
    # chosen scores x ``routed_scaling``, NOT renormalised
    router_groups: Optional[Tuple[int, int]] = None
    routed_scaling: float = 1.0
    # ``shared_experts`` experts of ``expert_width`` that EVERY token passes
    # through (one SwiGLU of their joint width), added to the routed sum
    shared_experts: int = 0
    # Latent attention (DeepSeek-V2's MLA; ``kv_lora_rank`` 0 = none): q is
    # low-rank (``q_lora_rank``, with an RMSNorm between its two matrices),
    # K and V are expanded from ONE compressed vector a token
    # (``kv_lora_rank`` wide, RMSNorm'd) and every head shares ONE rotary
    # key (``qk_rope_dim``); a head's q / k are [``qk_nope_dim`` |
    # ``qk_rope_dim``] = ``head_dim`` wide, its v ``v_head_dim``. The cache
    # keeps the normed compressed vector and the rotated key and nothing per
    # head ("Latent attention" below; :meth:`has_latent`).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    # State-space layers (Granite-4.0-H; ``layer_kinds[l] == 2``): a
    # Mamba-2 mixer in place of attention, which keeps per LANE a recurrent
    # state [ssm_heads, ssm_head_dim, ssm_state] float32 and the last
    # ``ssm_conv - 1`` inputs of its depthwise convolution, and nothing per
    # token (:meth:`cache_kinds`' ``state`` kind; "The state-space mixer"
    # below). ``ssm_heads`` 0: no such layer.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_conv_bias: bool = True
    # Gated short-convolution layers (LFM2; ``layer_kinds[l] == 3``): in
    # place of attention [B, C, z] = W_in h, u = B * z, a depthwise causal
    # convolution of ``conv_cache`` taps over u, y = C * c, W_out y. Per LANE
    # such a layer keeps the last ``conv_cache - 1`` rows of u in the
    # model's dtype and nothing else: a ``state`` cache kind that is a tail
    # alone ("The gated short convolution" below). 0: no such layer.
    conv_cache: int = 0
    # a sigmoid router's denominator is the chosen scores' sum + this
    router_norm_eps: float = 0.0
    # attention without positional encoding (``position_embedding_type``
    # "nope"): q and k go to the scores as projected
    use_rope: bool = True
    # the Granite family's four multipliers, each None where the config
    # carries none (= 1): the softmax scale itself (not 1/sqrt(head_dim)),
    # the embedding rows, both residual adds, and logits / logits_scaling
    attn_multiplier: Optional[float] = None
    embed_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    # K/V rows narrower than a 128-lane tile stored ``kv_fold`` tokens to a
    # pool row ([.., page // kv_fold, kv_fold * head_dim]; "KV pool access")
    kv_fold: int = 1
    # A layer of TWO sublayers with a routed branch across them
    # (LongCat-Flash's shortcut-connected MoE): a published layer is two
    # program layers (``num_layers`` counts the program's: mixers, cache
    # rows, dense feed-forwards), each a mixer and a DENSE feed-forward; the
    # first of a pair also computes the routed experts from the normed
    # stream its own feed-forward reads, and that branch is added to the
    # stream after the second's feed-forward (:func:`_ffn_block`
    # ``branch``). ``ffn_kinds`` is all dense; :meth:`layer_branch` says
    # which layers the branch leaves.
    shortcut_moe: bool = False
    # the last ``zero_experts`` outputs of the router (behind the
    # ``router_experts`` or ``num_experts`` routed ones) are IDENTITY
    # experts: no weights, their part of the result is gate x input
    # (models/moe.py ``zero``)
    zero_experts: int = 0
    # latent attention's two constant scales (LongCat-Flash's
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``; None: 1): the normed
    # low-rank q x sqrt(hidden / q_lora_rank), which reaches a head's whole
    # q, and the normed compressed vector x sqrt(hidden / kv_lora_rank)
    # BEFORE its expansion, which reaches k_nope and v and not the shared
    # rotary key
    latent_q_scale: Optional[float] = None
    latent_kv_scale: Optional[float] = None
    # A PARALLEL block (the Cohere2 family): a layer has ONE norm; attention
    # and the feed-forward both read the stream normed by it and land on the
    # stream together, x + attention + feed-forward. No ``ln2`` exists
    # (:func:`layer_in` hands the normed stream on, :func:`_ffn_block`
    # ``par``).
    parallel_block: bool = False
    # every norm of the stream is a bias-free LayerNorm: (x - mean) x
    # rsqrt(var + eps) x w, in float32 like :func:`rms_norm` (:func:`_normed`)
    layer_norm: bool = False
    # rotary BY KIND: a per-kind model's full layers take q and k as
    # projected, its window layers rotate (``rope_theta``)
    nope_full: bool = False
    # rotary pairs dims (2i, 2i + 1) of a head (GPT-J's pairing,
    # ``position_embedding_type rope_gptj``), not (i, i + Dh / 2). An engine
    # stores ``wq`` / ``wk`` with a head's columns de-interleaved
    # (:func:`stored_params`), so that rotate-half gives the same scores and
    # a step pays nothing for the pairing
    rope_interleaved: bool = False
    # the ``shared_experts`` are AVERAGED: the mean of their outputs is added
    # to the routed sum (``shared_expert_combination_strategy average``)
    shared_average: bool = False

    @property
    def per_kind(self) -> bool:
        """Layers described one by one, with parameter stacks by kind."""
        return self.layer_kinds is not None

    @property
    def has_window(self) -> bool:
        """Window layers that keep a window of cache in pools of their own."""
        return self.per_kind and 1 in self.layer_kinds

    @property
    def has_state(self) -> bool:
        """Layers that keep something per LANE and nothing per token: a
        state-space mixer's recurrent state and convolution tail, or a
        gated short convolution's tail alone."""
        return self.per_kind and (2 in self.layer_kinds
                                  or 3 in self.layer_kinds)

    @property
    def has_conv(self) -> bool:
        """Gated short-convolution layers: the state kind is a tail alone."""
        return self.per_kind and 3 in self.layer_kinds

    @property
    def has_latent(self) -> bool:
        """Latent attention: the cache keeps one compressed row a token."""
        return self.kv_lora_rank > 0

    @property
    def latent_k_store(self) -> int:
        """Width the shared rotary key is STORED at: zero-padded to a lane
        tile (64 -> 128), for :meth:`k_store_dim`'s reason."""
        return -(-self.qk_rope_dim // 128) * 128

    def layer_window(self, l: int) -> bool:
        """Layer ``l`` keeps its K/V in the window cache (a Python bool)."""
        return self.per_kind and self.layer_kinds[l] == 1

    def layer_state(self, l: int) -> bool:
        """Layer ``l`` keeps a state a lane: a state-space mixer or a gated
        short convolution (a Python bool)."""
        return self.per_kind and self.layer_kinds[l] in (2, 3)

    @property
    def state_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.layer_state(l))

    @property
    def stream_dtype(self):
        """The residual stream's dtype: the model's, but float32 for a model
        that DAMPS every branch (``residual_multiplier``): its 2 L branch
        outputs enter the stream at a tenth of the stream's own size, and a
        bfloat16 stream loses 2^-9 of ITSELF at every add, a fiftieth of
        what is being added (with the head's bfloat16 logits, :func:`_lm_head`,
        40 layers read 0.011-0.017 sigma rms against the float32 reference,
        int8 weights in the same range; 0.0025-0.0033 with both in float32,
        int8 0.0073-0.0197: my chip runs, PR 36, calls C and E). And float32
        for a model with GROUP-LIMITED routing (``router_groups``): what the
        stream loses reaches the router, a near-tie between two experts or
        two groups then falls the other way than in the float32 reference,
        and one such flip at a scored position moves its logits by 0.04-0.35
        sigma; a float32 stream halves the flips (PERF.md section 6, PR 42,
        has the readings). Any routed model would gain so; the two older
        ones keep their programs until a PR judges the change on their
        cells; a model with a routed branch across sublayers
        (``shortcut_moe``) and one with a parallel block (``parallel_block``)
        came with it. Norms, projections and kernels see
        the normed activations in
        the model's dtype as before; only the adds and the norms' inputs are
        wider."""
        wide = (self.residual_multiplier is not None or self.router_groups
                or self.shortcut_moe or self.parallel_block)
        return jnp.float32 if wide else self.dtype

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the convolution: X and the one group's B, C."""
        return self.ssm_inner + 2 * self.ssm_state

    def layer_routed(self, l: int) -> bool:
        if self.ffn_kinds is not None:
            return self.ffn_kinds[l] == 1
        return bool(self.num_experts)

    def layer_branch(self, l: int) -> bool:
        """Layer ``l`` computes a routed branch BESIDE its dense
        feed-forward, which layer ``l + 1`` adds (``shortcut_moe``)."""
        return self.shortcut_moe and l % 2 == 0

    @property
    def router_width(self) -> int:
        """Outputs of the router: the deployment's routed experts and the
        identity experts behind them."""
        return (self.router_experts or self.num_experts) + self.zero_experts

    def kind_layers(self, window: bool) -> Tuple[int, ...]:
        """The layers of one attention kind of a per-kind model, in order."""
        return tuple(l for l in range(self.num_layers)
                     if self.layer_kinds[l] == int(window))

    def kv_heads_of(self, window: bool) -> int:
        return (self.window_kv_heads if window and self.window_kv_heads
                else self.num_kv_heads)

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def k_store_dim(self) -> int:
        """Width a K row is STORED at: a row that is wider than a 128-lane
        tile and no multiple of it (192) is zero-padded to the next tile, so
        that what the program addresses is what the device holds (XLA pads
        such a minor dimension in HBM anyway) and the kernels contract over
        whole tiles. Rows of up to a tile, or of whole tiles, as they are."""
        d = self.head_dim
        return d if d <= 128 or d % 128 == 0 else -(-d // 128) * 128

    @property
    def routed_layers(self) -> int:
        """Layers with a router: a routed feed-forward or a routed branch."""
        return sum(self.layer_routed(l) or self.layer_branch(l)
                   for l in range(self.num_layers))

    def layer_sliding(self, layer):
        """Every ``sliding_pattern``-th layer is full attention, the rest
        sliding (gemma2: 2 — alternating, even layers slide; gemma3: 6 —
        five sliding then one full). ``layer`` is the GLOBAL layer index: a
        Python integer gives a Python bool; a traced one (a pipeline stage's
        offset + local index) gives a traced bool, or the Python False of a
        model that has no window. :func:`pick` selects by either. A model
        with a per-layer list (``layer_kinds``) reads it (Python integers
        only: such a model refuses the staged forward)."""
        if self.layer_kinds is not None:
            return self.layer_kinds[layer] == 1
        return (self.sliding_window is not None
                and (layer + 1) % self.sliding_pattern != 0)

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def has_indexer(self) -> bool:
        return self.index_topk > 0

    @property
    def attn_scale(self) -> float:
        if self.attn_multiplier is not None:
            return float(self.attn_multiplier)
        base = (self.query_pre_attn_scalar
                if self.query_pre_attn_scalar is not None else self.head_dim)
        return 1.0 / math.sqrt(base)

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any], dtype=jnp.bfloat16) -> "LlamaConfig":
        """Map a HF ``config.json`` (LlamaForCausalLM family) onto ours.
        Gemma3 VLM configs nest the text model under ``text_config``: the
        text half maps recursively; the vision tower + mm wiring land on
        the vision fields."""
        if _GEMMA_VLM_ARCH in (cfg.get("architectures", []) or []):
            if "text_config" not in cfg or "vision_config" not in cfg:
                raise ValueError(
                    f"{_GEMMA_VLM_ARCH} config must nest text_config and "
                    f"vision_config; refusing to guess a flat layout")
            text = dict(cfg["text_config"])
            # the nested text config usually omits architectures — restore
            # the family marker so the gemma3 mapping rules fire
            text.setdefault("architectures", ["Gemma3ForCausalLM"])
            base = cls.from_hf_config(text, dtype=dtype)
            return cls(**{
                **base.__dict__,
                "vision": dict(cfg["vision_config"]),
                "mm_tokens_per_image": int(cfg.get("mm_tokens_per_image",
                                                   256)),
                # the hub config spells it image_token_index (boi/eoi
                # likewise); newer transformers re-exports *_id — accept both
                "image_token_id": int(
                    cfg.get("image_token_id",
                            cfg.get("image_token_index", 262144))),
            })
        if cfg.get("model_type") == "gemma3_text":
            # sparse real-checkpoint text_config: class defaults fill the gaps
            cfg = {**_GEMMA3_TEXT_DEFAULTS, **cfg}
        hybrid = _map_hybrid(cfg)
        if hybrid:
            # the feed-forward every layer has is the SHARED one's width
            cfg = {**cfg, "intermediate_size": cfg["shared_intermediate_size"]}
        conv, cfg = _map_shortconv(cfg)
        shortcut, cfg = _map_shortcut(cfg)
        parblock, cfg = _map_parblock(cfg)
        latent = _map_latent(cfg)
        rs = cfg.get("rope_scaling") or {}
        if latent:
            # a head's q / k width, and the ONE row a token the cache keeps
            cfg = {**cfg, "head_dim": latent["qk_nope_dim"]
                   + latent["qk_rope_dim"], "num_key_value_heads": 1}
        elif rs.get("rope_type", rs.get("type")) == "yarn":
            raise ValueError("rope_scaling type 'yarn' is implemented for "
                             "latent attention alone (kv_lora_rank)")
        experts, kinds = _map_experts(cfg), _map_layer_kinds(cfg)
        if shortcut and not (experts and latent):
            raise ValueError(
                "a layer of two sublayers with a routed branch across them "
                "(num_layers / ffn_hidden_size / moe_topk) is implemented "
                "with latent attention and routed experts (kv_lora_rank, "
                "n_routed_experts)")
        if "ffn_kinds" in experts and not (
                {"layer_kinds"} & {*kinds, *hybrid, *latent, *conv}):
            raise ValueError(
                "dense layers among routed ones (moe_layer_freq / "
                "first_k_dense_replace) are implemented for a model whose "
                "layers are described one by one (hybrid_layer_pattern, "
                "layer_types, latent attention)")
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim",
                             cfg["hidden_size"] // cfg["num_attention_heads"]),
            intermediate_size=cfg["intermediate_size"],
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=cfg.get("rope_scaling"),
            rms_eps=cfg.get("rms_norm_eps", cfg.get("layernorm_epsilon",
                                                    1e-5)),
            max_position=cfg.get("max_position_embeddings", 8192),
            tie_embeddings=cfg.get("tie_word_embeddings", False),
            # Qwen2 has qkv bias baked into the architecture; HF encodes it
            # via model class, newer configs carry attention_bias explicitly
            attention_bias=bool(cfg.get(
                "attention_bias",
                any("Qwen2" in a for a in cfg.get("architectures", []) or []))),
            hidden_act=_map_act(cfg),
            norm_offset=_is_gemma(cfg),
            embed_scale=_is_gemma(cfg),
            sandwich_norms=_is_gemma2(cfg) or _is_gemma3(cfg),
            attn_logit_softcap=(cfg.get("attn_logit_softcapping")
                                if _is_gemma2(cfg) else None),
            final_logit_softcap=(cfg.get("final_logit_softcapping")
                                 if _is_gemma2(cfg) else None),
            sliding_window=(cfg.get("sliding_window")
                            if _is_gemma2(cfg) or _is_gemma3(cfg)
                            or "layer_kinds" in kinds else None),
            query_pre_attn_scalar=(cfg.get("query_pre_attn_scalar")
                                   if _is_gemma2(cfg) or _is_gemma3(cfg)
                                   else None),
            sliding_pattern=(2 if hybrid or "layer_kinds" in kinds
                             else _sliding_pattern(cfg)),
            rope_local_theta=(cfg.get("rope_local_base_freq", 10000.0)
                              if _is_gemma3(cfg)
                              else cfg.get("swa_rope_theta")),
            qk_norm=(_is_gemma3(cfg) or _is_qwen3_family(cfg)
                     or bool(conv)),
            dtype=dtype,
            **{**experts, **conv, **shortcut, **parblock},
            **_map_indexer(cfg),
            **kinds,
            **_map_multipliers(cfg),
            **hybrid,
            **latent,
        )


# model_type / architecture markers of the Qwen3(-MoE) family: per-head
# RMSNorm on q and k after projection, before rope
_QWEN3_MODEL_TYPES = ("qwen3", "qwen3_moe", "KeyeVL2")

# every published expert key from_hf_config honours, and the training-only
# router keys that say nothing of the forward pass
_EXPERT_KEYS = ("num_experts", "num_local_experts", "num_experts_per_tok",
                "moe_intermediate_size", "norm_topk_prob",
                "decoder_sparse_step", "mlp_only_layers",
                # the DeepSeek-V3 family's spelling (MiMo-V2-Flash)
                "n_routed_experts", "n_shared_experts", "scoring_func",
                "topk_method", "n_group", "topk_group",
                "routed_scaling_factor", "moe_layer_freq",
                "first_k_dense_replace",
                # a chip's share of the experts (not a published key: a
                # deployment's, see _map_experts)
                "expert_shard")
_EXPERT_KEYS_IGNORED = ("router_aux_loss_coef", "output_router_logits",
                        "router_jitter_noise")
_INDEXER_KEYS = ("indexer_num_heads", "indexer_head_dim",
                 "indexer_num_kv_heads", "topk", "q_chunk_size",
                 "kv_chunk_size")


def _is_qwen3_family(cfg: Dict[str, Any]) -> bool:
    return (cfg.get("model_type") in _QWEN3_MODEL_TYPES
            or any("Qwen3" in a for a in cfg.get("architectures", []) or []))


def _looks_like_expert_key(k: str) -> bool:
    return ("expert" in k or k.startswith("moe_") or "router" in k
            or k in ("norm_topk_prob", "decoder_sparse_step",
                     "mlp_only_layers", "scoring_func", "topk_method",
                     "n_group", "topk_group", "routed_scaling_factor"))


def _map_experts(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Published routed-expert keys -> ours. A config that carries an
    expert key this does not honour RAISES: a sparse model must never be
    served as the dense model of its ``intermediate_size``."""
    seen = [k for k in cfg if _looks_like_expert_key(k)
            and k not in _EXPERT_KEYS_IGNORED]
    if not seen:
        return {}
    unknown = [k for k in seen if k not in _EXPERT_KEYS]
    if unknown:
        raise ValueError(
            f"config carries expert keys this engine does not implement: "
            f"{sorted(unknown)} (known: {', '.join(_EXPERT_KEYS)}); refusing "
            f"to serve a sparse model as a dense one")
    E = cfg.get("num_experts", cfg.get("num_local_experts",
                                       cfg.get("n_routed_experts")))
    if (not E and cfg.get("num_local_experts") == 0
            and not cfg.get("num_experts_per_tok")
            and "shared_intermediate_size" in cfg):
        # Granite-4.0-H: "no routed experts; shared_intermediate_size is the
        # feed-forward width" (a dense model that says so in expert keys)
        return {}
    if E and "shared_intermediate_size" in cfg:
        raise ValueError(
            f"num_local_experts {E} beside shared_intermediate_size: routed "
            f"experts beside a shared feed-forward in every layer of a "
            f"hybrid stack are not implemented")
    if not E:
        raise ValueError(f"expert keys {sorted(seen)} without num_experts / "
                         f"num_local_experts / n_routed_experts")
    if cfg.get("num_local_experts", E) != E:
        raise ValueError(f"num_experts {E} != num_local_experts "
                         f"{cfg['num_local_experts']}")
    if "num_experts_per_tok" not in cfg:
        raise ValueError("num_experts without num_experts_per_tok")
    if "norm_topk_prob" not in cfg and ("num_experts" in cfg
                                        or "n_routed_experts" in cfg):
        # the Qwen-MoE and DeepSeek families' class default is False
        raise ValueError("num_experts without norm_topk_prob: the family's "
                         "default is false, which this engine does not "
                         "implement")
    law = (cfg.get("scoring_func", "softmax"),
           cfg.get("topk_method", "greedy"))
    routers = {("softmax", "greedy"): "softmax",
               ("sigmoid", "noaux_tc"): "sigmoid_bias",
               # (no family publishes this pair: _map_parblock's spelling of
               # the Cohere2 family's router, sigmoid scores, the k best
               # among all, no selection bias)
               ("sigmoid", "among_all"): "sigmoid",
               ("softmax", "group_limited_greedy"): "softmax_group",
               # (no family publishes this pair: _map_shortcut's spelling
               # of the LongCat-Flash router)
               ("softmax", "bias"): "softmax_bias"}
    if law not in routers:
        raise ValueError(
            f"scoring_func {law[0]!r} with topk_method {law[1]!r} is not "
            f"implemented (softmax with greedy or group_limited_greedy; "
            f"sigmoid with noaux_tc)")
    grouped = routers[law] == "softmax_group"
    # the two laws whose gates are the chosen scores x routed_scaling_factor
    scaled = grouped or routers[law] == "softmax_bias"
    if bool(cfg.get("norm_topk_prob", True)) == scaled:
        # each law as its family publishes it: the two that choose among
        # all experts renormalise the chosen gates, the grouped one and the
        # bias-selected softmax scale them by routed_scaling_factor and do
        # not
        raise ValueError(
            f"norm_topk_prob {cfg.get('norm_topk_prob')!r} with topk_method "
            f"{law[1]!r} is not implemented: greedy / noaux_tc renormalise "
            f"the chosen gates, group_limited_greedy does not "
            f"(models/moe.route_topk)")
    if cfg.get("mlp_only_layers"):
        raise ValueError(f"mlp_only_layers {cfg['mlp_only_layers']} is not "
                         f"implemented: the leading dense layers of a model "
                         f"are named by moe_layer_freq")
    if cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError(f"decoder_sparse_step "
                         f"{cfg['decoder_sparse_step']} is not implemented: "
                         f"every layer is a routed-expert layer")
    shared = cfg.get("n_shared_experts") or 0
    if shared and not (cfg.get("moe_intermediate_size") and (
            grouped or routers[law] == "sigmoid")):
        raise ValueError(f"n_shared_experts {shared} is implemented beside "
                         f"routed experts of a width of their own "
                         f"(moe_intermediate_size) that are group-limited "
                         f"(softmax) or chosen among all by sigmoid scores "
                         f"without a bias")
    shard = cfg.get("expert_shard")
    R = int(shard.get("router_experts", E)) if shard else int(E)
    out = {"num_experts": int(E),
           "experts_per_token": int(cfg["num_experts_per_tok"]),
           "moe_intermediate_size": (
               int(cfg["moe_intermediate_size"])
               if cfg.get("moe_intermediate_size") else None),
           "router": routers[law]}
    if grouped:
        G, Gk = cfg.get("n_group"), cfg.get("topk_group")
        if not G or not Gk or R % int(G) or not 0 < int(Gk) <= int(G):
            raise ValueError(
                f"group_limited_greedy needs n_group that divides the "
                f"router's {R} experts and 0 < topk_group <= n_group (got "
                f"{G!r}, {Gk!r})")
        if int(Gk) * (R // int(G)) < int(cfg["num_experts_per_tok"]):
            raise ValueError(f"topk_group {Gk} groups of {R // int(G)} hold "
                             f"fewer experts than num_experts_per_tok")
        out.update(router_groups=(int(G), int(Gk)),
                   routed_scaling=float(cfg.get("routed_scaling_factor", 1)),
                   shared_experts=int(shared))
    else:
        for k in ("n_group", "topk_group"):
            if cfg.get(k) not in (None, 1):
                raise ValueError(f"{k} {cfg[k]} is not implemented with "
                                 f"topk_method {law[1]!r}: the experts are "
                                 f"chosen among all, not by group")
        if shared:
            out["shared_experts"] = int(shared)
        if routers[law] == "softmax_bias":
            out["routed_scaling"] = float(cfg.get("routed_scaling_factor", 1))
        elif cfg.get("routed_scaling_factor") not in (None, 1, 1.0):
            raise ValueError(
                f"routed_scaling_factor {cfg['routed_scaling_factor']} is "
                f"not implemented with topk_method {law[1]!r}")
    freq = cfg.get("moe_layer_freq")
    first_dense = cfg.get("first_k_dense_replace")
    if isinstance(freq, int) and not isinstance(freq, bool):
        # the DeepSeek-V2 spelling: ``first_k_dense_replace`` leading dense
        # layers, then every ``moe_layer_freq``-th layer routed
        L = cfg["num_hidden_layers"]
        k0 = int(first_dense or 0)
        if freq < 1 or not 0 <= k0 < L:
            raise ValueError(f"moe_layer_freq {freq} / first_k_dense_replace "
                             f"{first_dense!r}: no routed layer among {L}")
        kinds = tuple(int(l >= k0 and l % freq == 0) for l in range(L))
        if not any(kinds):
            raise ValueError("moe_layer_freq names no routed layer")
        if not all(kinds):
            out["ffn_kinds"] = kinds
    elif freq is not None:
        L = cfg["num_hidden_layers"]
        if (not isinstance(freq, (list, tuple)) or len(freq) < L
                or any(f not in (0, 1) for f in freq)):
            raise ValueError(
                f"moe_layer_freq must list 0 (dense) or 1 (routed) for "
                f"each of the {L} layers, got {freq!r}")
        if not any(freq[:L]):
            raise ValueError("moe_layer_freq names no routed layer")
        if not all(freq[:L]):
            out["ffn_kinds"] = tuple(int(f) for f in freq[:L])
    elif first_dense:
        raise ValueError(f"first_k_dense_replace {first_dense} without "
                         f"moe_layer_freq: refusing to guess the layers "
                         f"behind the dense ones")
    if shard is not None:
        # a chip's share of a deployment's experts: the published router
        # width, and where this chip's ``n_routed_experts`` begin
        unknown = sorted(set(shard) - {"router_experts", "first_expert"})
        if unknown or "router_experts" not in shard:
            raise ValueError(f"expert_shard takes router_experts and "
                             f"first_expert, got {sorted(shard)}")
        R, first = int(shard["router_experts"]), int(
            shard.get("first_expert", 0))
        if not 0 <= first <= R - E:
            raise ValueError(f"expert_shard: experts {first} .. "
                             f"{first + E - 1} are not among {R}")
        out.update(router_experts=R, expert_first=first)
    return out


# the keys of a model whose window and full layers differ in more than
# their mask (MiMo-V2-Flash), and the ones that say the same thing twice
_LAYER_KIND_KEYS = ("hybrid_layer_pattern", "swa_num_key_value_heads",
                    "swa_num_attention_heads", "swa_head_dim",
                    "swa_v_head_dim", "swa_rope_theta",
                    "add_swa_attention_sink_bias",
                    "add_full_attention_sink_bias", "sliding_window_size",
                    "attention_chunk_size")


def _map_layer_kinds(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Per-layer attention kinds and what comes with them -> ours; the
    three keys any family may carry (``v_head_dim``,
    ``partial_rotary_factor``, ``attention_value_scale``) too. A value this
    engine cannot honour RAISES."""
    out: Dict[str, Any] = {}
    Dh = cfg.get("head_dim",
                 cfg["hidden_size"] // cfg["num_attention_heads"])
    if cfg.get("v_head_dim") not in (None, Dh):
        out["v_head_dim"] = int(cfg["v_head_dim"])
    f = cfg.get("partial_rotary_factor")
    if f is not None and f != 1:
        rot = int(f * Dh)
        rot -= rot % 2
        if rot <= 0:
            raise ValueError(f"partial_rotary_factor {f} of head_dim {Dh} "
                             f"leaves nothing to rotate")
        out["rotary_dim"] = rot
    if cfg.get("attention_value_scale") not in (None, 1, 1.0):
        out["attn_value_scale"] = float(cfg["attention_value_scale"])
    pattern = cfg.get("hybrid_layer_pattern")
    lt = cfg.get("layer_types") or ()
    if (pattern is None and not _is_gemma(cfg)
            and {"sliding_attention", "full_attention"} <= set(lt)):
        # the same thing in the newer spelling: a model (not Gemma, whose
        # window layers keep the whole context under a mask) that names
        # window and full layers one by one keeps two caches
        bad = sorted(set(lt) - {"sliding_attention", "full_attention"})
        if bad:
            raise ValueError(f"layer_types names {bad} beside "
                             f"sliding_attention / full_attention")
        pattern = [int(t == "sliding_attention") for t in lt]
    if pattern is None:
        stray = [k for k in _LAYER_KIND_KEYS if cfg.get(k)]
        if stray and not (_is_gemma(cfg) or cfg.get("layer_types")):
            raise ValueError(f"config carries {stray} without "
                             f"hybrid_layer_pattern: refusing to guess "
                             f"which layers they describe")
        return out
    L = cfg["num_hidden_layers"]
    if (not isinstance(pattern, (list, tuple)) or len(pattern) < L
            or any(p not in (0, 1) for p in pattern)):
        raise ValueError(f"hybrid_layer_pattern must list 0 (full) or 1 "
                         f"(window) (layer_types: full_attention or "
                         f"sliding_attention) for each of the {L} layers, "
                         f"got {pattern!r}")
    kinds = tuple(int(p) for p in pattern[:L])
    if not 0 < sum(kinds) < L:
        raise ValueError("hybrid_layer_pattern needs layers of both kinds "
                         "among the served ones (a model of one kind is the "
                         "one-law description)")
    W = cfg.get("sliding_window")
    if not W or cfg.get("sliding_window_size", W) != W:
        raise ValueError(f"hybrid_layer_pattern needs ONE sliding_window "
                         f"(got {W!r} / {cfg.get('sliding_window_size')!r})")
    same = {"swa_num_attention_heads": cfg["num_attention_heads"],
            "swa_head_dim": Dh, "swa_v_head_dim": cfg.get("v_head_dim", Dh)}
    for k, want in same.items():
        if cfg.get(k, want) != want:
            raise ValueError(f"{k} {cfg[k]} differs from the full layers' "
                             f"{want}: only the K/V head COUNT may differ "
                             f"between the two kinds")
    if cfg.get("rope_scaling") and (cfg["rope_scaling"].get(
            "rope_type", cfg["rope_scaling"].get("type", "default"))
            != "default"):
        raise ValueError("scaled rotary with per-kind layers is not "
                         "implemented")
    # attention_chunk_size tiles the window layers' computation (it equals
    # the window) and masks nothing: accepted, not read
    out.update(
        layer_kinds=kinds,
        window_kv_heads=int(cfg.get("swa_num_key_value_heads",
                                    cfg.get("num_key_value_heads",
                                            cfg["num_attention_heads"]))),
        sink_window=bool(cfg.get("add_swa_attention_sink_bias", False)),
        sink_full=bool(cfg.get("add_full_attention_sink_bias", False)))
    return out


def _map_multipliers(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The Granite family's four multipliers, for any model that carries
    them; a key that is absent means 1 and changes no program."""
    names = {"attention_multiplier": "attn_multiplier",
             "embedding_multiplier": "embed_multiplier",
             "residual_multiplier": "residual_multiplier",
             "logits_scaling": "logits_scaling"}
    return {ours: float(cfg[k]) for k, ours in names.items()
            if cfg.get(k) is not None}


# every ``mamba_*`` key of a published hybrid config, and what this engine
# implements of each
_HYBRID_KEYS = ("mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv",
                "mamba_d_head", "mamba_d_state", "mamba_expand",
                "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias")


def _map_hybrid(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``layer_types`` with state-space layers (Granite-4.0-H,
    ``granitemoehybrid``) -> ours: ``layer_kinds`` 2 (mamba) / 0
    (attention), the mixer's sizes, rotary on or off, and the K/V rows'
    fold. Every key the engine cannot honour RAISES; a config without a
    ``mamba`` layer is none of this function's business."""
    lt = cfg.get("layer_types")
    stray = [k for k in cfg if k.startswith("mamba_")]
    if not lt or "mamba" not in lt:
        if stray and cfg.get("model_type") == "granitemoehybrid":
            raise ValueError(f"config carries {sorted(stray)} and no "
                             f"layer_types that names a mamba layer")
        return {}
    L = cfg["num_hidden_layers"]
    bad = sorted({t for t in lt if t not in ("mamba", "attention")})
    if bad or len(lt) < L:
        raise ValueError(f"layer_types must list 'mamba' or 'attention' for "
                         f"each of the {L} layers (got {bad or len(lt)})")
    unknown = sorted(set(stray) - set(_HYBRID_KEYS))
    if unknown:
        raise ValueError(f"config carries mamba keys this engine does not "
                         f"implement: {unknown}")
    pos = cfg.get("position_embedding_type", "rope")
    if pos not in ("nope", "rope"):
        raise ValueError(f"position_embedding_type {pos!r} is not "
                         f"implemented (nope, rope)")
    if cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError(f"normalization_function "
                         f"{cfg['normalization_function']!r} is not "
                         f"implemented (rmsnorm)")
    if cfg.get("mamba_n_groups", 1) != 1:
        raise ValueError(f"mamba_n_groups {cfg['mamba_n_groups']} is not "
                         f"implemented: all heads share one B and one C")
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if H * P != int(cfg.get("mamba_expand", 2)) * cfg["hidden_size"]:
        raise ValueError(
            f"mamba_d_head x mamba_n_heads = {H * P} is not mamba_expand x "
            f"hidden_size = {cfg.get('mamba_expand', 2) * cfg['hidden_size']}")
    if cfg.get("mamba_proj_bias", False):
        raise ValueError("mamba_proj_bias true is not implemented")
    if "shared_intermediate_size" not in cfg:
        raise ValueError("a hybrid config names its feed-forward width in "
                         "shared_intermediate_size")
    return {"layer_kinds": tuple(2 if t == "mamba" else 0 for t in lt[:L]),
            "ssm_heads": H, "ssm_head_dim": P,
            "ssm_state": int(cfg["mamba_d_state"]),
            "ssm_conv": int(cfg.get("mamba_d_conv", 4)),
            "ssm_conv_bias": bool(cfg.get("mamba_conv_bias", True)),
            "use_rope": pos == "rope", "kv_fold": _kv_fold(cfg)}


def _kv_fold(cfg: Dict[str, Any]) -> int:
    """Tokens stored to a 128-lane pool row: K/V rows narrower than a lane
    tile are folded to whole tiles (``LlamaConfig.kv_fold``)."""
    Dh = cfg.get("head_dim",
                 cfg["hidden_size"] // cfg["num_attention_heads"])
    return max(1, 128 // Dh) if 128 % Dh == 0 else 1


# the keys of a gated short-convolution model (``model_type lfm2_moe``) that
# no other family spells so, and what :func:`_map_shortconv` makes of each
_SHORTCONV_KEYS = ("conv_L_cache", "conv_bias", "num_dense_layers",
                   "use_expert_bias", "norm_eps", "rope_parameters")


def _plain_rope_theta(cfg: Dict[str, Any]):
    """The ONE ``rope_theta`` of a config that may give it flat, under
    ``rope_parameters`` or both; anything but plain rotary RAISES."""
    rp = cfg.get("rope_parameters")
    if rp is not None:
        unknown = sorted(set(rp) - {"rope_theta", "rope_type"})
        if unknown or rp.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters {rp!r}: plain rotary "
                             f"(rope_type default, rope_theta) is what is "
                             f"implemented")
    thetas = {(rp or {}).get("rope_theta"), cfg.get("rope_theta")} - {None}
    if len(thetas) > 1:
        raise ValueError("rope_theta is given twice and differs")
    if not thetas:
        raise ValueError("no rope_theta (flat or under rope_parameters)")
    theta, = thetas
    return theta


def _map_shortconv(cfg: Dict[str, Any]):
    """``model_type lfm2_moe`` (LFM2: gated short-convolution layers beside
    GQA layers with per-head q / k norms, under bias-selected sigmoid-routed
    experts behind ``num_dense_layers`` dense layers) -> (ours, the config
    with this family's keys re-spelt as the keys the other mappers read). A
    key or value the engine cannot honour RAISES; ``use_expert_bias`` or a
    ``conv`` layer under another model type raises too (the family's keys
    mean what its modeling file says, nowhere else)."""
    lt = cfg.get("layer_types") or ()
    if cfg.get("model_type") != "lfm2_moe":
        stray = [k for k in ("conv_L_cache", "conv_bias", "use_expert_bias",
                             "num_dense_layers") if k in cfg]
        if stray or "conv" in lt:
            raise ValueError(
                f"config carries {stray or ['layer_types: conv']} without "
                f"model_type 'lfm2_moe': refusing to guess what they mean")
        return {}, cfg
    L = cfg["num_hidden_layers"]
    bad = sorted({t for t in lt if t not in ("conv", "full_attention")})
    if bad or len(lt) < L:
        raise ValueError(f"layer_types must list 'conv' or 'full_attention' "
                         f"for each of the {L} layers (got {bad or len(lt)})")
    if cfg.get("conv_bias", False):
        raise ValueError("conv_bias true is not implemented: the two "
                         "projections and the taps carry no bias")
    K = int(cfg.get("conv_L_cache", 3))
    if K < 2:
        raise ValueError(f"conv_L_cache {K}: a convolution of one tap keeps "
                         f"no tail")
    if not cfg.get("use_expert_bias", False):
        raise ValueError("use_expert_bias false is not implemented: the "
                         "experts are chosen by score + expert_bias")
    if not cfg.get("norm_topk_prob", False):
        raise ValueError("norm_topk_prob false is not implemented: the "
                         "chosen scores are divided by their sum + 1e-6")
    theta = _plain_rope_theta(cfg)
    nd = int(cfg.get("num_dense_layers", 0))
    if not 0 <= nd < L:
        raise ValueError(f"num_dense_layers {nd}: no routed layer among {L}")
    ours = {"layer_kinds": tuple(3 if t == "conv" else 0 for t in lt[:L]),
            "conv_cache": K, "router_norm_eps": 1e-6,
            "routed_scaling": float(cfg.get("routed_scaling_factor", 1)),
            "kv_fold": _kv_fold(cfg)}
    rest = {k: v for k, v in cfg.items()
            if k not in _SHORTCONV_KEYS + ("layer_types",
                                           "routed_scaling_factor")}
    rest.update(rope_theta=theta,
                rms_norm_eps=cfg.get("norm_eps", cfg.get("rms_norm_eps", 1e-5)),
                # the family ties its head to the embedding (``tie_embedding``
                # in its dense models' files): the default where the file
                # carries neither spelling
                tie_word_embeddings=bool(cfg.get(
                    "tie_word_embeddings", cfg.get("tie_embedding", True))),
                # sigmoid scores, the bias chooses and never weighs: the
                # law the DeepSeek-V3 family spells so (route_topk)
                scoring_func="sigmoid", topk_method="noaux_tc",
                moe_layer_freq=[0] * nd + [1] * (L - nd))
    rest.pop("tie_embedding", None)
    return ours, rest


# the keys by which the LongCat-Flash family (``model_type longcat_flash``)
# spells what no other family has or spells otherwise, and every other key
# a file of the family may carry: a key outside both lists is refused, so
# that a sibling with a mechanism of its own (an indexer, n-gram embeddings,
# multi-token prediction) is never served as this model
_SHORTCUT_KEYS = ("num_layers", "ffn_hidden_size", "expert_ffn_hidden_size",
                  "moe_topk", "zero_expert_num", "zero_expert_type",
                  "mla_scale_q_lora", "mla_scale_kv_lora", "attention_method")
_SHORTCUT_ALSO = ("architectures", "model_type", "torch_dtype",
                  "transformers_version", "auto_map", "use_cache",
                  "initializer_range", "attention_dropout", "bos_token_id",
                  "eos_token_id", "pad_token_id", "attention_bias",
                  "vocab_size", "hidden_size", "num_attention_heads",
                  "num_key_value_heads", "kv_lora_rank", "q_lora_rank",
                  "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim",
                  "routed_scaling_factor", "n_routed_experts",
                  "max_position_embeddings", "rms_norm_eps", "rope_theta",
                  "rope_scaling", "hidden_act", "tie_word_embeddings",
                  "router_bias", "norm_topk_prob", "expert_shard")


def _map_shortcut(cfg: Dict[str, Any]):
    """The LongCat-Flash family (``num_layers`` published layers, each TWO
    latent-attention sublayers with a dense feed-forward each and a routed
    branch that leaves after the first and lands after the second; a router
    over ``n_routed_experts`` + ``zero_expert_num`` outputs of which the last
    are identity experts) -> (ours, the config with the family's keys
    re-spelt as the keys the other mappers read). Every key of the family is
    mapped or RAISES; a config that carries none of them is none of this
    function's business."""
    have = [k for k in _SHORTCUT_KEYS if k in cfg]
    if not have:
        return {}, cfg
    if cfg.get("model_type", "longcat_flash") != "longcat_flash":
        raise ValueError(
            f"config carries {have} under model_type "
            f"{cfg['model_type']!r}: they mean what the LongCat-Flash "
            f"modeling file says under 'longcat_flash' alone")
    unknown = sorted(set(cfg) - set(_SHORTCUT_KEYS) - set(_SHORTCUT_ALSO))
    if unknown:
        raise ValueError(
            f"a LongCat-Flash config carries keys this engine does not "
            f"implement: {unknown}")
    missing = [k for k in ("num_layers", "ffn_hidden_size",
                           "expert_ffn_hidden_size", "moe_topk")
               if not cfg.get(k)]
    if missing or "num_hidden_layers" in cfg:
        raise ValueError(
            f"a LongCat-Flash config names its layers in num_layers (two "
            f"sublayers each) and its widths in ffn_hidden_size / "
            f"expert_ffn_hidden_size / moe_topk (missing: {missing})")
    if cfg.get("attention_method", "MLA") != "MLA":
        raise ValueError(f"attention_method {cfg['attention_method']!r} is "
                         f"not implemented (MLA)")
    if cfg.get("kv_lora_rank") is None:
        raise ValueError("attention_method MLA without kv_lora_rank")
    Z = int(cfg.get("zero_expert_num") or 0)
    if Z and cfg.get("zero_expert_type") != "identity":
        raise ValueError(
            f"zero_expert_type {cfg.get('zero_expert_type')!r} is not "
            f"implemented: a zero-computation expert is the identity")
    if (Z or "zero_expert_type" in cfg) and not cfg.get("n_routed_experts"):
        raise ValueError("zero_expert_num without n_routed_experts: identity "
                         "experts lie behind the routed ones in the router")
    if cfg.get("rope_scaling"):
        raise ValueError(
            f"rope_scaling {cfg['rope_scaling']!r} with a LongCat-Flash "
            f"config is not implemented: plain rotary (rope_theta)")
    for k in ("router_bias", "norm_topk_prob", "attention_bias"):
        if cfg.get(k):
            raise ValueError(
                f"{k} true is not implemented for a LongCat-Flash config: "
                f"the router is a matrix alone, its chosen scores x "
                f"routed_scaling_factor are the gates as they are, and no "
                f"projection carries a bias")
    L, D = int(cfg["num_layers"]), cfg["hidden_size"]

    def scale(flag: str, rank: str) -> Optional[float]:
        # a flag that is false (or absent) is HONOURED: no scale
        on = cfg.get(flag) and cfg.get(rank)     # (no rank: _map_latent's)
        return math.sqrt(D / cfg[rank]) if on else None

    ours = {"shortcut_moe": True, "zero_experts": Z,
            "ffn_kinds": (0,) * (2 * L),
            "latent_q_scale": scale("mla_scale_q_lora", "q_lora_rank"),
            "latent_kv_scale": scale("mla_scale_kv_lora", "kv_lora_rank")}
    rest = {k: v for k, v in cfg.items() if k not in _SHORTCUT_KEYS
            and k not in ("router_bias", "rope_scaling")}
    rest.update(
        num_hidden_layers=2 * L,
        intermediate_size=cfg["ffn_hidden_size"],
        moe_intermediate_size=cfg["expert_ffn_hidden_size"],
        num_experts_per_tok=cfg["moe_topk"],
        # softmax scores, a selection bias that chooses and never weighs,
        # gates x routed_scaling_factor, not renormalised (route_topk)
        scoring_func="softmax", topk_method="bias", norm_topk_prob=False,
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)))
    return ours, rest


# the keys by which the Cohere2-MoE family (``model_type cohere2_moe``)
# spells what no other family has or spells otherwise; each is mapped or
# refused by name in :func:`_map_parblock`
_PARBLOCK_KEYS = ("use_parallel_block", "shared_expert_combination_strategy",
                  "expert_selection_fn", "num_shared_experts", "layer_switch",
                  "order_of_interleaved_layers",
                  "prefix_dense_intermediate_size",
                  "prefix_dense_sliding_window_pattern", "rotary_pct",
                  "logit_scale", "use_gated_activation",
                  "use_embedding_sharing", "use_parallel_embedding",
                  "use_qk_norm", "layer_norm_eps", "tf_legacy_loss",
                  "position_embedding_type", "rope_parameters",
                  "first_k_dense_replace")


def _map_parblock(cfg: Dict[str, Any]):
    """The Cohere2-MoE family (Command A+: a PARALLEL block on one bias-free
    LayerNorm, interleaved rotary in the window layers and none in the full
    ones, sigmoid-routed experts chosen among all beside shared experts
    that are averaged) -> (ours, the config with the family's keys re-spelt
    as the keys the other mappers read). Every key of the family is mapped
    or RAISES; under another model type the family's own keys raise (they
    mean what its modelling code says, nowhere else)."""
    own = ("use_parallel_block", "shared_expert_combination_strategy",
           "expert_selection_fn", "layer_switch",
           "order_of_interleaved_layers")
    if cfg.get("model_type") != "cohere2_moe":
        stray = [k for k in own if k in cfg]
        if stray:
            raise ValueError(
                f"config carries {stray} without model_type 'cohere2_moe': "
                f"refusing to guess what they mean")
        return {}, cfg

    def refuse(key, why):
        raise ValueError(f"{key} {cfg.get(key)!r} is not implemented for a "
                         f"cohere2_moe config: {why}")

    if not cfg.get("use_parallel_block", False):
        refuse("use_parallel_block", "the family's layer has ONE norm, which "
               "attention and feed-forward both read; a sequential block "
               "would need a second norm the family does not have")
    if cfg.get("shared_expert_combination_strategy", "average") != "average":
        refuse("shared_expert_combination_strategy",
               "the shared experts' outputs are averaged")
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        refuse("expert_selection_fn", "the router scores by sigmoid")
    if cfg.get("first_k_dense_replace"):
        refuse("first_k_dense_replace", "leading dense layers "
               "(prefix_dense_*) are not implemented: every layer is routed")
    if cfg.get("use_qk_norm", False):
        refuse("use_qk_norm", "q and k go to rotary as projected")
    if cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj":
        refuse("position_embedding_type", "interleaved rotary (rope_gptj) in "
               "the window layers, none in the full ones")
    if cfg.get("rotary_pct", 1) != 1:
        refuse("rotary_pct", "all of a head's dims rotate")
    if cfg.get("rope_scaling"):
        refuse("rope_scaling", "plain rotary (rope_theta)")
    if not cfg.get("use_gated_activation", True):
        refuse("use_gated_activation", "every expert is a gated (SwiGLU) "
               "feed-forward")
    if cfg.get("use_parallel_embedding", False):
        refuse("use_parallel_embedding", "one embedding table, read by token")
    tied = {bool(cfg[k]) for k in ("use_embedding_sharing",
                                   "tie_word_embeddings") if k in cfg}
    if tied != {True}:
        refuse("use_embedding_sharing", "the head is the embedding "
               "(use_embedding_sharing / tie_word_embeddings true, and "
               "agreeing)")
    if cfg.get("rms_norm_eps") is not None or "layer_norm_eps" not in cfg:
        refuse("layer_norm_eps", "the family's norm is a LayerNorm "
               "(layer_norm_eps given, rms_norm_eps null)")
    theta = _plain_rope_theta(cfg)
    L = cfg["num_hidden_layers"]
    lt = list(cfg.get("layer_types") or ())[:L]
    n = cfg.get("layer_switch")
    if n is not None:
        first = cfg.get("order_of_interleaved_layers", "local_attn_first")
        if first != "local_attn_first":
            refuse("order_of_interleaved_layers", "window layers come first "
                   "in a period")
        want = ["full_attention" if (l + 1) % int(n) == 0
                else "sliding_attention" for l in range(L)]
        if lt and lt != want:
            refuse("layer_switch", f"layer_types says otherwise ({lt})")
        lt = want
    if len(lt) < L or not cfg.get("sliding_window"):
        refuse("layer_types", f"a kind for each of the {L} layers (or "
               f"layer_switch) and a sliding_window")
    if not cfg.get("num_shared_experts"):
        refuse("num_shared_experts", "shared experts beside the routed ones")
    scale = cfg.get("logit_scale", 1)
    ours = {"parallel_block": True, "layer_norm": True, "nope_full": True,
            "rope_interleaved": True, "shared_average": True}
    rest = {k: v for k, v in cfg.items() if k not in _PARBLOCK_KEYS
            and k not in ("rms_norm_eps", "use_embedding_sharing")}
    rest.update(
        layer_types=lt, rope_theta=theta,
        rms_norm_eps=cfg["layer_norm_eps"], tie_word_embeddings=True,
        # the experts are ``intermediate_size`` wide, the shared ones too
        moe_intermediate_size=cfg["intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        # sigmoid scores, the k best among all, gates renormalised
        scoring_func="sigmoid", topk_method="among_all")
    if scale != 1:
        # logits x logit_scale (ours divides)
        rest["logits_scaling"] = 1.0 / float(scale)
    return ours, rest


# the keys of latent attention (DeepSeek-V2's MLA)
_LATENT_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim")
_YARN_KEYS = ("type", "rope_type", "factor", "beta_fast", "beta_slow",
              "mscale", "mscale_all_dim", "original_max_position_embeddings")


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term (``yarn_get_mscale`` of the
    published modeling file): 1 up to a factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _map_latent(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The latent-attention keys -> ours (``kv_lora_rank`` names the
    mechanism; without it the other three must be absent too), YaRN's
    softmax-scale correction with them: the scores are scaled by
    ``(nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2`` and the
    rotary tables by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, which has to be 1. Every layer is of ONE attention
    kind, described layer by layer (``layer_kinds`` all 0) so that the
    feed-forwards may differ (``ffn_kinds``)."""
    have = [k for k in _LATENT_KEYS if cfg.get(k) is not None]
    if cfg.get("kv_lora_rank") is None:
        if have:
            raise ValueError(f"config carries {have} without kv_lora_rank: "
                             f"refusing to guess an attention")
        return {}
    missing = [k for k in _LATENT_KEYS if cfg.get(k) is None]
    if missing:
        raise ValueError(
            f"latent attention (kv_lora_rank) without {missing}: a "
            f"full-rank q (q_lora_rank null) is not implemented")
    if "v_head_dim" not in cfg:
        raise ValueError("latent attention (kv_lora_rank) without v_head_dim")
    if cfg.get("num_key_value_heads",
               cfg["num_attention_heads"]) != cfg["num_attention_heads"]:
        raise ValueError(
            f"latent attention expands K and V for every query head: "
            f"num_key_value_heads {cfg['num_key_value_heads']} != "
            f"num_attention_heads {cfg['num_attention_heads']}")
    stray = [k for k in ("attention_bias", "partial_rotary_factor",
                         "hybrid_layer_pattern", "layer_types", "sa_config",
                         "attention_multiplier", "sliding_window")
             if cfg.get(k)]
    if stray:
        raise ValueError(f"{stray} with latent attention is not implemented")
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    if rope <= 0 or rope % 2 or nope <= 0:
        raise ValueError(f"qk_nope_head_dim {nope} / qk_rope_head_dim {rope}")
    scale = 1.0 / math.sqrt(nope + rope)
    rs = cfg.get("rope_scaling")
    if rs:
        kind = rs.get("rope_type", rs.get("type"))
        unknown = sorted(set(rs) - set(_YARN_KEYS))
        if kind != "yarn" or unknown:
            raise ValueError(
                f"rope_scaling {kind!r} {unknown or ''} with latent "
                f"attention is not implemented (yarn: "
                f"{', '.join(_YARN_KEYS[2:])})")
        f = float(rs["factor"])
        m, m_all = rs.get("mscale", 1), rs.get("mscale_all_dim", 0)
        if not m_all or yarn_mscale(f, m) != yarn_mscale(f, m_all):
            raise ValueError(
                f"yarn with mscale {m!r} != mscale_all_dim {m_all!r} scales "
                f"the rotary tables by their ratio: not implemented")
        scale *= yarn_mscale(f, m_all) ** 2
    L = cfg["num_hidden_layers"]
    return {"q_lora_rank": int(cfg["q_lora_rank"]),
            "kv_lora_rank": int(cfg["kv_lora_rank"]),
            "qk_nope_dim": nope, "qk_rope_dim": rope, "rotary_dim": rope,
            # (v_head_dim: _map_layer_kinds, as for every family)
            "attn_multiplier": scale, "layer_kinds": (0,) * L}


def _map_indexer(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``sa_config`` (learned top-k selection of the cache) -> ours."""
    sa = cfg.get("sa_config")
    if not sa:
        return {}
    unknown = [k for k in sa if k not in _INDEXER_KEYS]
    if unknown:
        raise ValueError(f"sa_config carries keys this engine does not "
                         f"implement: {sorted(unknown)}")
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError(f"sa_config.indexer_num_kv_heads "
                         f"{sa['indexer_num_kv_heads']}: one index key head "
                         f"is what is implemented")
    rs = cfg.get("rope_scaling") or {}
    if rs.get("rope_type", rs.get("type", "default")) != "default":
        raise ValueError("an indexer with scaled rotary is not implemented")
    # q_chunk_size / kv_chunk_size tile the indexer's computation and select
    # nothing: accepted, not read. mrope_section: with text-only input the
    # three position axes are equal and sectioned rotary IS ordinary rotary.
    return {"index_heads": int(sa["indexer_num_heads"]),
            "index_head_dim": int(sa["indexer_head_dim"]),
            "index_topk": int(sa["topk"])}


def _sliding_pattern(cfg: Dict[str, Any]) -> int:
    """Period of the full-attention layers: from ``layer_types`` when the
    config carries it (position of the first 'full_attention' + 1), else
    the family default (gemma2: 2, gemma3: 6)."""
    lt = cfg.get("layer_types")
    if lt:
        period = None
        for i, t in enumerate(lt):
            if t == "full_attention":
                period = i + 1
                break
        if period is None:
            return len(lt) + 1   # all sliding
        # refuse rather than mis-serve: the whole list must actually
        # follow the "(period-1) sliding, then full" repetition
        for i, t in enumerate(lt):
            want = ("full_attention" if (i + 1) % period == 0
                    else "sliding_attention")
            if t != want:
                raise ValueError(
                    f"layer_types is not periodic with full every "
                    f"{period} layers (index {i} is {t!r})")
        return period
    return 6 if _is_gemma3(cfg) else 2


# test/bench presets (shapes only; weights are random or loaded)
PRESETS: Dict[str, Dict[str, Any]] = {
    # tiny model over the byte tokenizer vocab — the hermetic test model
    "tiny-byte": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, intermediate_size=128,
                      rope_theta=10000.0, max_position=1024),
    # tiny MoE over the byte vocab: 4 experts, top-2 routing (EP tests)
    "tiny-moe": dict(vocab_size=259, hidden_size=64, num_layers=2, num_heads=4,
                     num_kv_heads=2, head_dim=16, intermediate_size=96,
                     rope_theta=10000.0, max_position=1024, num_experts=4,
                     experts_per_token=2),
    # tiny Keye-VL-2.0-style language model: 8 routed experts of their own
    # width (2 a token), q/k norm, an indexer (2 index heads, top-8) whose
    # selection binds at the tests' contexts of 24-48
    "tiny-keye": dict(vocab_size=259, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, head_dim=128,
                      intermediate_size=128, moe_intermediate_size=48,
                      rope_theta=10000.0, max_position=1024, rms_eps=1e-6,
                      num_experts=8, experts_per_token=2, qk_norm=True,
                      index_heads=2, index_head_dim=16, index_topk=8),
    "llama-3.2-1b": dict(vocab_size=128256, hidden_size=2048, num_layers=16,
                         num_heads=32, num_kv_heads=8, head_dim=64,
                         intermediate_size=8192, rope_theta=500000.0,
                         max_position=131072, tie_embeddings=True),
    "llama-3-8b": dict(vocab_size=128256, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=8, head_dim=128,
                       intermediate_size=14336, rope_theta=500000.0,
                       max_position=8192),
    "llama-3-70b": dict(vocab_size=128256, hidden_size=8192, num_layers=80,
                        num_heads=64, num_kv_heads=8, head_dim=128,
                        intermediate_size=28672, rope_theta=500000.0,
                        max_position=8192),
    # tiny Qwen2-style model (qkv bias) over the byte vocab
    "tiny-qwen": dict(vocab_size=259, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, head_dim=16,
                      intermediate_size=128, rope_theta=10000.0,
                      max_position=1024, attention_bias=True,
                      tie_embeddings=True),
    "qwen2-1.5b": dict(vocab_size=151936, hidden_size=1536, num_layers=28,
                       num_heads=12, num_kv_heads=2, head_dim=128,
                       intermediate_size=8960, rope_theta=1000000.0,
                       max_position=32768, attention_bias=True,
                       tie_embeddings=True, rms_eps=1e-6),
    "qwen2-7b": dict(vocab_size=152064, hidden_size=3584, num_layers=28,
                     num_heads=28, num_kv_heads=4, head_dim=128,
                     intermediate_size=18944, rope_theta=1000000.0,
                     max_position=32768, attention_bias=True, rms_eps=1e-6),
    "mistral-7b": dict(vocab_size=32000, hidden_size=4096, num_layers=32,
                       num_heads=32, num_kv_heads=8, head_dim=128,
                       intermediate_size=14336, rope_theta=10000.0,
                       max_position=32768, rms_eps=1e-5),
    # tiny Gemma-style model (GeGLU, offset norms, scaled embed)
    "tiny-gemma": dict(vocab_size=259, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=1, head_dim=16,
                       intermediate_size=128, rope_theta=10000.0,
                       max_position=1024, tie_embeddings=True,
                       hidden_act="gelu_tanh", norm_offset=True,
                       embed_scale=True, rms_eps=1e-6),
    # tiny Gemma2-style model: sandwich norms, softcaps, sliding window
    "tiny-gemma2": dict(vocab_size=259, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=1, head_dim=16,
                        intermediate_size=128, rope_theta=10000.0,
                        max_position=1024, tie_embeddings=True,
                        hidden_act="gelu_tanh", norm_offset=True,
                        embed_scale=True, rms_eps=1e-6,
                        sandwich_norms=True, attn_logit_softcap=50.0,
                        final_logit_softcap=30.0, sliding_window=8,
                        query_pre_attn_scalar=24.0),
    "gemma2-9b": dict(vocab_size=256000, hidden_size=3584, num_layers=42,
                      num_heads=16, num_kv_heads=8, head_dim=256,
                      intermediate_size=14336, rope_theta=10000.0,
                      max_position=8192, tie_embeddings=True,
                      hidden_act="gelu_tanh", norm_offset=True,
                      embed_scale=True, rms_eps=1e-6,
                      sandwich_norms=True, attn_logit_softcap=50.0,
                      final_logit_softcap=30.0, sliding_window=4096,
                      query_pre_attn_scalar=256.0),
    "gemma2-27b": dict(vocab_size=256000, hidden_size=4608, num_layers=46,
                       num_heads=32, num_kv_heads=16, head_dim=128,
                       intermediate_size=36864, rope_theta=10000.0,
                       max_position=8192, tie_embeddings=True,
                       hidden_act="gelu_tanh", norm_offset=True,
                       embed_scale=True, rms_eps=1e-6,
                       sandwich_norms=True, attn_logit_softcap=50.0,
                       final_logit_softcap=30.0, sliding_window=4096,
                       query_pre_attn_scalar=144.0),
    # tiny Gemma3-style model: qk-norm, dual-base rope, 5:1 sliding
    "tiny-gemma3": dict(vocab_size=259, hidden_size=64, num_layers=6,
                        num_heads=4, num_kv_heads=2, head_dim=16,
                        intermediate_size=128, rope_theta=1000000.0,
                        max_position=1024, tie_embeddings=True,
                        hidden_act="gelu_tanh", norm_offset=True,
                        embed_scale=True, rms_eps=1e-6,
                        sandwich_norms=True, sliding_window=8,
                        sliding_pattern=3, rope_local_theta=10000.0,
                        qk_norm=True, query_pre_attn_scalar=24.0),
    "gemma3-4b": dict(vocab_size=262208, hidden_size=2560, num_layers=34,
                      num_heads=8, num_kv_heads=4, head_dim=256,
                      intermediate_size=10240, rope_theta=1000000.0,
                      rope_scaling={"rope_type": "linear", "factor": 8.0},
                      max_position=131072, tie_embeddings=True,
                      hidden_act="gelu_tanh", norm_offset=True,
                      embed_scale=True, rms_eps=1e-6, sandwich_norms=True,
                      sliding_window=1024, sliding_pattern=6,
                      rope_local_theta=10000.0, qk_norm=True,
                      query_pre_attn_scalar=256.0),
    "gemma3-12b": dict(vocab_size=262208, hidden_size=3840, num_layers=48,
                       num_heads=16, num_kv_heads=8, head_dim=256,
                       intermediate_size=15360, rope_theta=1000000.0,
                       rope_scaling={"rope_type": "linear", "factor": 8.0},
                       max_position=131072, tie_embeddings=True,
                       hidden_act="gelu_tanh", norm_offset=True,
                       embed_scale=True, rms_eps=1e-6, sandwich_norms=True,
                       sliding_window=1024, sliding_pattern=6,
                       rope_local_theta=10000.0, qk_norm=True,
                       query_pre_attn_scalar=256.0),
    # tiny Gemma3 VLM: text stack of tiny-gemma3 + a 2-layer SigLIP tower
    # (56x56 images, 14px patches -> 16 patches -> 4 soft tokens/image)
    "tiny-gemma3-vlm": dict(vocab_size=259, hidden_size=64, num_layers=6,
                            num_heads=4, num_kv_heads=2, head_dim=16,
                            intermediate_size=128, rope_theta=1000000.0,
                            max_position=1024, tie_embeddings=True,
                            hidden_act="gelu_tanh", norm_offset=True,
                            embed_scale=True, rms_eps=1e-6,
                            sandwich_norms=True, sliding_window=8,
                            sliding_pattern=3, rope_local_theta=10000.0,
                            qk_norm=True, query_pre_attn_scalar=24.0,
                            mm_tokens_per_image=4, image_token_id=250,
                            vision=dict(hidden_size=32, num_hidden_layers=2,
                                        num_attention_heads=4,
                                        intermediate_size=48, image_size=56,
                                        patch_size=14)),
    "gemma-2b": dict(vocab_size=256000, hidden_size=2048, num_layers=18,
                     num_heads=8, num_kv_heads=1, head_dim=256,
                     intermediate_size=16384, rope_theta=10000.0,
                     max_position=8192, tie_embeddings=True,
                     hidden_act="gelu_tanh", norm_offset=True,
                     embed_scale=True, rms_eps=1e-6),
    "gemma-7b": dict(vocab_size=256000, hidden_size=3072, num_layers=28,
                     num_heads=16, num_kv_heads=16, head_dim=256,
                     intermediate_size=24576, rope_theta=10000.0,
                     max_position=8192, tie_embeddings=True,
                     hidden_act="gelu_tanh", norm_offset=True,
                     embed_scale=True, rms_eps=1e-6),
}


def preset(name: str, **overrides) -> LlamaConfig:
    d = dict(PRESETS[name])
    d.update(overrides)
    return LlamaConfig(**d)


# ---------------------------------------------------------------------------
# Params: init + shardings
# ---------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Random-init params (testing/benching without checkpoint files).

    Two laws, chosen by the model's geometry. The first: stacked tensors
    N(0, 1 / L) (the scale is taken from the STACKED tensor's first
    dimension) and the embedding N(0, 1 / V), which every dense
    configuration's readings and the Mixtral-style presets' tests (experts
    as wide as the dense FFN; the benchmark's rehearsal among them, whose
    files a later PR may not edit) were taken with. The second, for a model
    of many FINE-GRAINED experts (experts of a width of their own,
    ``moe_intermediate_size``), makes every activation of unit rms, as a
    trained model's roughly are: the embedding N(0, 1), EVERY matrix of a
    layer (attention, indexer, router, experts) N(0, 1 / fan-in), the two
    projections that write to the residual stream (``wo``, the experts'
    ``wd``) N(0, 1 / (2 L x fan-in)) (GPT-2's scaled residual init: the 2 L
    branches add up to the embedding's size, whatever the depth), and the
    per-head q/k norm weights 1.4. What each is for (my chip and CPU runs,
    PR 28):

    - router logits of spread 1, where 1 / L gave sqrt(D / L) (18.5 at
      D 2048, L 6): a softmax that is an argmax, whose slope multiplies
      every rounding of its input, so that the bfloat16 path and a float32
      reference of the same weights chose other experts within three layers;
    - a token's own embedding carries its residual stream (with N(0, 1 / V)
      it is 0.003 beside layer outputs of 0.03-0.2, every position's stream
      is then the same average of thousands of values, and its norm turns
      the few keys by which two selections differ into a 14 % change);
    - the experts' output and the attention's are of one size, neither a
      perturbation of the other in the logits, and each a fraction of the
      stream it is added to: top-k routing is discontinuous, the bfloat16
      path and a float32 reference part at near-ties with nothing wrong (3 %
      of tokens a layer), and where a layer's output is as large as the
      stream itself every such parting moves the next router's input and
      parts more tokens (six layers read ``rel_rms`` 0.09-0.14 sound and 0.15
      with every weight in int8: nothing to tell apart; 0.016 and 0.04 with
      the scaled projections);
    - attention logits of spread 1.4 x 1.4 put a query's weight on a few
      dozen keys, not evenly on thousands: with even weights, attention over
      the selected 2048 keys or over all 14,000 is the same small average and
      the selection cannot be seen in the logits. Not more, and the same for
      every dimension: the selection of keys is discontinuous too, the
      bfloat16 path and the reference part on a few keys in a thousand at
      the k-th score, and the heavier single keys weigh, the more of the
      sound runs' noise that is (1.5 with N(0, 1 / L) around it read twice
      the noise of 1.4 flat)."""
    if cfg.per_kind:
        return _init_per_kind(cfg, key)
    D, Hq, Hkv, Dh, F, L, V = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                               cfg.head_dim, cfg.intermediate_size,
                               cfg.num_layers, cfg.vocab_size)
    ks = jax.random.split(key, 10)
    E = cfg.num_experts
    unit = bool(E) and cfg.expert_width != F       # the second law
    s = lambda *shape: 1.0 / math.sqrt(shape[0])

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * s(*shape)).astype(cfg.dtype)

    def stack(k, fan_in, *shape, to_residual=False):
        """A layer's matrix [*shape], stacked on L."""
        if not unit:
            return norm(k, L, *shape)
        return (jax.random.normal(k, (L, *shape), jnp.float32) / math.sqrt(
            fan_in * (2 * L if to_residual else 1))).astype(cfg.dtype)

    if E:
        Fe = cfg.expert_width

        def experts(k, *shape, to_residual=False):
            # a layer at a time, cast inside the program: the float32
            # temporary is one layer's experts (128 x 2048 x 768 x 4 B =
            # 0.8 GB), never the stacked tensor's (4.8 GB at six layers)
            if not unit:
                return norm(k, L, E, *shape)
            scale = s(*shape) / math.sqrt(2 * L if to_residual else 1)

            def one(kl):
                return (jax.random.normal(kl, (E, *shape), jnp.float32)
                        * scale).astype(cfg.dtype)
            return jax.jit(lambda k: jax.lax.map(one, jax.random.split(k, L))
                           )(k)

        ffn = {
            "wr": stack(ks[9], D, D, E),
            "wg": experts(ks[5], D, Fe),
            "wu": experts(ks[6], D, Fe),
            "wd": experts(ks[7], Fe, D, to_residual=True),
        }
    else:
        ffn = {
            "wg": norm(ks[5], L, D, F),
            "wu": norm(ks[6], L, D, F),
            "wd": norm(ks[7], L, F, D),
        }
    params = {
        "embed": (norm(ks[0], V, D) if not unit else jax.random.normal(
            ks[0], (V, D), jnp.float32).astype(cfg.dtype)),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln2": jnp.ones((L, D), jnp.float32),
            "wq": stack(ks[1], D, D, Hq * Dh).reshape(L, D, Hq, Dh),
            "wk": stack(ks[2], D, D, Hkv * Dh).reshape(L, D, Hkv, Dh),
            "wv": stack(ks[3], D, D, Hkv * cfg.v_dim).reshape(
                L, D, Hkv, cfg.v_dim),
            "wo": stack(ks[4], Hq * cfg.v_dim, Hq * cfg.v_dim, D,
                        to_residual=True).reshape(L, Hq, cfg.v_dim, D),
            **ffn,
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }
    if cfg.sandwich_norms:
        # random (not ones) so parity tests catch a dropped/ misplaced norm
        kn = jax.random.split(ks[8], 2)
        params["layers"]["ln1_post"] = norm(kn[0], L, D).astype(jnp.float32)
        params["layers"]["ln2_post"] = norm(kn[1], L, D).astype(jnp.float32)
    if cfg.qk_norm:
        kq = jax.random.split(ks[6], 2)
        for name, k in (("ln_q", kq[0]), ("ln_k", kq[1])):
            params["layers"][name] = (
                jnp.full((L, Dh), 1.4, jnp.float32) if unit
                else norm(k, L, Dh).astype(jnp.float32))
    if cfg.has_indexer:
        Hi, Di = cfg.index_heads, cfg.index_head_dim
        ki = jax.random.split(ks[4], 5)
        # index queries, the ONE index key head with its LayerNorm (random
        # weight and bias, so a dropped norm shows), and the per-head score
        # weights, all read from the layer's normed input
        params["layers"]["wiq"] = stack(ki[0], D, D, Hi * Di).reshape(
            L, D, Hi, Di)
        params["layers"]["wik"] = stack(ki[1], D, D, Di)
        params["layers"]["wiw"] = stack(ki[2], D, D, Hi)
        params["layers"]["ln_ik_w"] = (
            1.0 + norm(ki[3], L, Di).astype(jnp.float32))
        params["layers"]["ln_ik_b"] = norm(ki[4], L, Di).astype(jnp.float32)
    if cfg.attention_bias:
        kb = jax.random.split(ks[9], 3)
        # non-zero random biases so parity tests would catch a dropped bias
        params["layers"]["bq"] = norm(kb[0], L, Hq, Dh)
        params["layers"]["bk"] = norm(kb[1], L, Hkv, Dh)
        params["layers"]["bv"] = norm(kb[2], L, Hkv, Dh)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(ks[8], D, V)
    return params


STACKS = "stacks"       # params[STACKS][kind]: a per-kind model's layers


def _init_per_kind(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded weights of a model whose layers are described one by one
    (``cfg.per_kind``): no ``layers`` tree but ``stacks``, one stack of
    stacked tensors a kind, each layer at its index among its kind
    (:func:`layer_stacks`): ``full`` and ``window`` (the attention of a
    layer: ln1, wq, wk, wv, wo, and ``sink`` [n, Hq] float32 where the kind
    has one) and ``dense`` and ``routed`` (its feed-forward: ln2, wg, wu,
    wd, and the router ``wr`` [n, D, R] with its selection bias ``rbias``
    [n, R] float32).

    The law is the second of :func:`init_params` (every activation of unit
    rms) with two seeded terms of this family, each of a size that shows in
    the logits when it is left out: sink logits 4 + N(0, 1) (a window's 128
    keys of score spread 1 sum to e^0.5 x 128 = 211; e^4 = 55 takes a fifth
    of a full window's weight and most of a short one's) and a selection
    bias N(0, 0.02^2) beside sigmoid scores of spread 0.2. The 8th and 9th
    of 256 scores lie 0.007 apart in the mean, so that bias still reorders
    the top-k of most tokens, and it leaves the experts' loads within a
    factor 1.5 of each other, as a trained ``noaux_tc`` bias exists to do
    (N(0, 0.1^2), this PR's first choice, gave a held expert between a
    hundredth and four times its even load: how many of a chip's experts a
    step hit, and so the step's time, then swung with the seed). The held
    experts keep the scale every expert has: what a chip's share computes
    is a partial sum of the layer, E / R of it in variance, and goes into
    the stream as that."""
    D, Hq, Dh, Dv, F, L, V = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                              cfg.v_dim, cfg.intermediate_size,
                              cfg.num_layers, cfg.vocab_size)
    E, Fe, R = cfg.num_experts, cfg.expert_width, cfg.router_width
    ks = iter(jax.random.split(key, 32))

    def mat(n, fan_in, *shape, scale=1.0):
        w = jax.random.normal(next(ks), (n, *shape), jnp.float32)
        return (w * (scale / math.sqrt(fan_in))).astype(cfg.dtype)

    def experts(n, fan_in, *shape, scale=1.0):
        # a layer at a time, cast inside the program (init_params)
        sc = scale / math.sqrt(fan_in)

        def one(kl):
            return (jax.random.normal(kl, (E, *shape), jnp.float32)
                    * sc).astype(cfg.dtype)
        return jax.jit(lambda k: jax.lax.map(one, jax.random.split(k, n))
                       )(next(ks))

    # projections into the stream: GPT-2's 1 / sqrt(2 L), or the model's
    # own damping of both residual adds where it has one
    res = (1.0 / math.sqrt(2 * L) if cfg.residual_multiplier is None
           else 1.0)
    # A parallel block under a TIED head (PR 51, read on the chip): greedy
    # decoding of seeded weights is a map from a token to the next (attention
    # over thousands of keys of score spread 1 is a plain average and adds
    # next to nothing), the token's own embedding in the stream gives its own
    # logit a head start of sqrt(D) |row|^2 / |stream| sigma (2.8 with rows of
    # norm 1 and damped branches), so every sample fell into a token that
    # predicts itself within a step or two and its 64 scored positions were
    # ONE token in one state; a near-tie of that token's routing then flips
    # the same way at all 64 (one run in five read 0.12 sigma rms where the
    # others read 0.02 and int8 weights 0.05). So: branches undamped (the
    # stream is theirs, of unit rms after four layers) and embedding rows of
    # norm 1/2, which leaves the head start at half a sigma; and the routed
    # experts' down-projection a quarter of the shared ones', so that ONE
    # assignment of a held expert, which is all a token gives this chip, is
    # a fortieth of the stream and not an eighth
    # What the first check of the cell taught (PR 51, tpot_p90_ms spread
    # 3.7 % over seeds of equal arrivals and sizes): an attention branch
    # hands on the part of the stream that ALL positions share with its
    # full gain (its weights sum to 1 whatever the scores are), while what
    # is a token's own comes out of the feed-forward branch at a third of
    # the stream's size. At an output projection of gain 1 under scores of
    # spread 1 that shared part grew tenfold a layer (0.8 % / 5 % / 21 % /
    # 46 % of the stream's energy behind the four layers; a CPU reading at
    # a hidden size of 512, window 1,024): the head then favours a few
    # tokens whatever the last one was, greedy decoding locks into one of
    # them within a few steps, the deeper layers' routers lean one way for
    # a whole seed (0.66 held assignments a token in the fourth layer where
    # 1 is even), and how many held experts a decode step reads, so the
    # step's time, is a draw of the seed. So: the attention's output
    # projection at a THIRD of the feed-forward's (the shared part then
    # stays under 1 % of the stream) and scores of spread 3 (a query reads
    # a handful of keys, not their average: the branch is a sixth of what a
    # layer adds and shows in the logits). 200 decoded tokens are then
    # 197-200 different ones and every layer's router is even (0.92-1.09)
    routed_res, attn_res, qk_par = res, res, 1.0
    if cfg.parallel_block:
        res, routed_res, attn_res = 1.0, 0.25, 1.0 / 3.0
        qk_par = math.sqrt(3.0)     # on wq AND wk: scores of spread 3
    stacks: Dict[str, Any] = {}
    if cfg.has_latent:
        stacks["full"] = _init_latent(cfg, ks, mat, res)
    for name, window in (("full", False), ("window", True)):
        n, Hkv = len(cfg.kind_layers(window)), cfg.kv_heads_of(window)
        if not n or cfg.has_latent:
            continue
        qk = qk_par
        if cfg.attn_multiplier is not None:
            # scores of spread 1.5 under the model's OWN scale, as
            # init_params' second law sets it and for its reasons: at N(0,
            # 1 / fan-in) a scale of 1 / head_dim leaves the scores a spread
            # of 1 / 8, attention is a plain average of hundreds of values,
            # and neither the scale nor a rotary switched on shows in the
            # logits (rel_rms 0.0146 / 0.0138 beside a sound 0.0123; my
            # chip run, PR 36, call B)
            qk = math.sqrt(1.5 / (math.sqrt(Dh) * cfg.attn_scale))
        # (the ONE norm of a parallel block: weights U(0.6, 1.4), so that
        # a feed-forward that norms its input a second time shows)
        st = {"ln1": (jax.random.uniform(next(ks), (n, D), jnp.float32,
                                         0.6, 1.4) if cfg.parallel_block
                      else jnp.ones((n, D), jnp.float32)),
              "wq": mat(n, D, D, Hq, Dh, scale=qk),
              "wk": mat(n, D, D, Hkv, Dh, scale=qk),
              "wv": mat(n, D, D, Hkv, Dv),
              "wo": mat(n, Hq * Dv, Hq, Dv, D, scale=attn_res)}
        if cfg.sink_window if window else cfg.sink_full:
            st["sink"] = 4.0 + jax.random.normal(next(ks), (n, Hq),
                                                 jnp.float32)
        if cfg.qk_norm:
            # one weight vector for all heads of q, one for k: sqrt(1.5) x
            # U(0.6, 1.4), so that the scores' spread under 1 / sqrt(Dh) is
            # about 1.5 (the normed q and k are of unit rms whatever wq and
            # wk are) and a dropped norm shows in the logits
            for w in ("ln_q", "ln_k"):
                st[w] = math.sqrt(1.5) * jax.random.uniform(
                    next(ks), (n, Dh), jnp.float32, 0.6, 1.4)
        stacks[name] = st
    nd = sum(not cfg.layer_routed(l) for l in range(L))
    nr = L - nd
    if nd:
        stacks["dense"] = {"ln2": jnp.ones((nd, D), jnp.float32),
                           "wg": mat(nd, D, D, F), "wu": mat(nd, D, D, F),
                           "wd": mat(nd, F, F, D, scale=res)}
    if cfg.shortcut_moe:
        # (behind the dense stack: ``mat`` makes a stacked tensor through
        # float32 temporaries six times its size, 7 GB for eight layers'
        # 6144 x 12288, which fit while the experts are not there yet)
        stacks["routed"] = _init_branch(cfg, ks, mat, experts)
    if nr:
        # (a parallel block has ONE norm, the attention stack's ``ln1``)
        st = {} if cfg.parallel_block else {
            "ln2": jnp.ones((nr, D), jnp.float32)}
        st.update(wr=mat(nr, D, D, R),
                  wg=experts(nr, D, D, Fe), wu=experts(nr, D, D, Fe),
                  wd=experts(nr, Fe, Fe, D, scale=routed_res))
        if cfg.router == "sigmoid_bias":
            st["rbias"] = 0.02 * jax.random.normal(next(ks), (nr, R),
                                                   jnp.float32)
        if cfg.shared_experts:
            # the expert every token passes through, by the law of the
            # routed ones: its output is of the size of ONE routed expert's
            # (and of the attention's), beside a routed sum of 6 gates of a
            # few tenths x 16 each, of which this chip computes its share
            # Shared experts that are AVERAGED: each by the routed ones' law
            # (fan-in Fe), so that their mean is half one expert's size and
            # their sum, the reading this model does not have, four times it
            Fs = cfg.shared_experts * Fe
            st.update(ws_g=mat(nr, D, D, Fs), ws_u=mat(nr, D, D, Fs),
                      ws_d=mat(nr, Fe if cfg.shared_average else Fs, Fs, D,
                               scale=res))
        stacks["routed"] = st
    if cfg.has_conv:
        stacks["conv"] = _init_conv(cfg, ks, mat, res)
    elif cfg.has_state:
        stacks["mamba"] = _init_mamba(cfg, ks, mat, res)
    # a head TIED to the embedding with no multiplier of the family's to
    # set its size: rows of norm 1, so that the logits of the normed stream
    # are of unit spread (rows of N(0, 1) give them a spread of sqrt(D), 45
    # at 2048: a softmax that is an argmax, whose best token's
    # log-probability is 0 whatever the weights are, and the comparison
    # with the reference then sees nothing). The stream starts that small
    # and is the layers' outputs from the first layer on
    small = math.sqrt(D) if (cfg.tie_embeddings and cfg.embed_multiplier
                             is None and cfg.logits_scaling is None) else 1.0
    if cfg.parallel_block:
        small *= 2.0            # rows of norm 1/2 (the law's text above)
    # a model of mean-centred norms: every row carries a MEAN of half its
    # spread, which a LayerNorm removes and an RMSNorm would hand to every
    # matrix (the tied head sees none of it: its input is centred)
    mean = 0.5 if cfg.layer_norm else 0.0
    params = {"embed": ((jax.random.normal(next(ks), (V, D), jnp.float32)
                         + mean) / ((cfg.embed_multiplier or 1.0) * small)
                        ).astype(cfg.dtype),
              STACKS: stacks,
              "final_norm": jnp.ones((D,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["lm_head"] = mat(D, D, V)
    return params


def _init_branch(cfg: LlamaConfig, ks, mat, experts) -> Dict[str, Any]:
    """The routed branches of a model whose layer is two sublayers
    (``shortcut_moe``), one a PUBLISHED layer, by the law of
    :func:`_init_per_kind` with three terms of this family, each of a size
    that shows in the logits when it is left out. No norm of their own: a
    branch reads the normed stream its sublayer's dense feed-forward reads.

    - ``wr`` [n, D, R + Z] N(0, 1.5^2 / D): router logits of spread 1.5,
      where the other families have 1. The gates are softmax scores x
      ``routed_scaling`` and are NOT renormalised: with 768 outputs of
      spread 1 the 12 chosen scores are 0.01 each and the whole branch a
      twentieth of the stream; at 1.5 they are 0.011-0.04, the 12 gates (x
      6) sum to about 1.3, and a third of them (256 of 768 outputs) are
      identity experts': 0.4 x the normed input, the size of the other
      branches.
    - ``rbias`` [n, R + Z] float32 N(0, 0.002^2), the selection bias, beside
      scores whose 12th and 13th lie 0.0005 apart in the mean: it reorders
      the top-k of most tokens and weighs nothing; identity and routed
      outputs alike, so a third of the assignments stay identity experts'.
    - the experts' ``wd`` N(0, 1 / fan-in), NOT damped by 1 / sqrt(2 L): an
      expert's output is of unit rms and enters the stream x a gate of a
      tenth. A deployment's 8 routed assignments a token then sum to about
      0.3, one branch's size; this chip's share (16 of 512) sees a token in
      five, to which it adds a tenth of the stream's size: small, as a
      thirty-second of the experts is, and visible at that token."""
    n, D, Fe = cfg.num_layers // 2, cfg.hidden_size, cfg.expert_width
    R = cfg.router_width
    return {"wr": mat(n, D, D, R, scale=1.5),
            "rbias": 0.002 * jax.random.normal(next(ks), (n, R), jnp.float32),
            "wg": experts(n, D, D, Fe), "wu": experts(n, D, D, Fe),
            "wd": experts(n, Fe, Fe, D)}


def _init_latent(cfg: LlamaConfig, ks, mat, res: float) -> Dict[str, Any]:
    """The attention stack of a model with latent attention, by the law of
    :func:`_init_per_kind` (every activation of unit rms): ``w_dq`` [D, Rq]
    and, around the RMSNorm ``ln_dq``, the published ``W_uq`` [Rq, Hq x
    (nope + rope)] held as its two column sets, each TRANSPOSED: ``w_uq``
    [Hq x nope, Rq] and ``w_uqr`` [Hq x rope, Rq], the heads flat;
    ``w_dkv`` [D, Rkv + rope] = [compressed vector | the ONE rotary key];
    ``ln_kv`` the compressed vector's RMSNorm; the published fused
    expansion [Rkv, Hq, nope + v] held as its two halves, each head-major as
    the absorbed form multiplies by them, a batch of matrices a head:
    ``w_uk`` [Hq, nope, Rkv] (q^ = q_nope w_uk) and ``w_uv`` [Hq, Rkv, v];
    ``wo``. (Stored as the published [Rq, Hq, 192] and [Rkv, Hq, .], XLA
    re-laid all three stacks whole at every decode dispatch's entry; as
    stored here it still copies the two q matrices once a dispatch and the
    step reads the same, PERF.md section 6, PR 42: the order is the one
    every measurement of that PR was made with, not a gain.) A head's
    score is a sum of nope + rope = 192 products of unit-rms terms under the
    model's own scale (0.1147 with YaRN's correction): a spread of 1.6, as
    the other families' inits set theirs.
    The two inner norms' weights are U(0.5, 1.5), not 1: at unit-rms inputs
    an RMSNorm of weight 1 is nearly the identity and a dropped one would
    not show in the logits. Where the model scales the two normed vectors
    (``latent_q_scale`` 2, ``latent_kv_scale`` 3.46 at the published ranks:
    they stand in for what a trained low-rank pair would have grown to) the
    matrices BEHIND each scale are seeded that much smaller, so that q,
    k_nope and v are of unit rms as above and the scores keep their spread;
    a scale that is dropped, or reaches the rotary key too, then shows."""
    n, D, Hq = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim

    def weight(width):
        return jax.random.uniform(next(ks), (n, width), jnp.float32,
                                  0.5, 1.5)

    sq = 1.0 / (cfg.latent_q_scale or 1.0)
    skv = 1.0 / (cfg.latent_kv_scale or 1.0)
    return {"ln1": jnp.ones((n, D), jnp.float32),
            "w_dq": mat(n, D, D, Rq), "ln_dq": weight(Rq),
            "w_uq": mat(n, Rq, Hq * Dn, Rq, scale=sq),
            "w_uqr": mat(n, Rq, Hq * Dr, Rq, scale=sq),
            "w_dkv": mat(n, D, D, Rkv + Dr), "ln_kv": weight(Rkv),
            "w_uk": mat(n, Rkv, Hq, Dn, Rkv, scale=skv),
            "w_uv": mat(n, Rkv, Hq, Rkv, Dv, scale=skv),
            "wo": mat(n, Hq * Dv, Hq, Dv, D, scale=res)}


def _init_mamba(cfg: LlamaConfig, ks, mat, res: float) -> Dict[str, Any]:
    """The state-space layers' stack, seeded as Mamba-2 is published to be
    initialised, so that the state's memory spans a few tokens to a few
    thousand and a lost carry shows in the logits: ``A_log`` = log U(1, 16),
    ``dt_bias`` = softplus^-1 of a log-uniform step in (0.001, 0.1), ``D``
    = 1, the depthwise convolution and its bias U(-1/sqrt(k), 1/sqrt(k))
    (PyTorch's Conv1d default at fan-in k), the gated norm's weight 1; the
    two projections by the law of :func:`_init_per_kind`. The published
    fused input projection [z | X, B, C | dt] is held as two matrices:
    ``w_in`` [D, I + Cd] = [z | X, B, C], 8448 columns at the published
    widths, whole 128-lane tiles, and ``w_dt`` [D, H], the step logits'
    64 columns: a stack 8512 columns wide is no whole number of tiles, and
    XLA re-laid all of it (1.25 GB) at every decode dispatch's entry (my
    chip run, PR 36)."""
    n, D, K = len(cfg.state_layers), cfg.hidden_size, cfg.ssm_conv
    H, I, Cd = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    dt = jnp.exp(uniform((n, H), math.log(1e-3), math.log(1e-1)))
    b = 1.0 / math.sqrt(K)
    st = {"ln1": jnp.ones((n, D), jnp.float32),
          "w_in": mat(n, D, D, I + Cd),
          "w_dt": mat(n, D, D, H),
          "conv_w": uniform((n, K, Cd), -b, b).astype(cfg.dtype),
          "A_log": jnp.log(uniform((n, H), 1.0, 16.0)),
          "D": jnp.ones((n, H), jnp.float32),
          "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
          "norm": jnp.ones((n, I), jnp.float32),
          "w_out": mat(n, I, I, D, scale=res)}
    if cfg.ssm_conv_bias:
        st["conv_b"] = uniform((n, Cd), -b, b).astype(cfg.dtype)
    return st


def _init_conv(cfg: LlamaConfig, ks, mat, res: float) -> Dict[str, Any]:
    """The gated short-convolution layers' stack: ``w_in`` [D, 3 D] = [B | C
    | z] in the published order, the taps ``conv_w`` [K, D] U(-1 / sqrt(K),
    1 / sqrt(K)) (PyTorch's Conv1d default at fan-in K; tap K - 1 meets the
    token itself), ``w_out`` [D, D]. With unit-rms B, C, z the convolved
    product is of rms 1 / sqrt(3) and so is y; ``w_out`` is scaled by sqrt(3)
    beside the other projections into the stream, so that the branch is of
    the size of the attention layers' and a lost tail or a dropped gate
    shows in the logits."""
    n = sum(k == 3 for k in cfg.layer_kinds)
    D, K = cfg.hidden_size, cfg.conv_cache
    b = 1.0 / math.sqrt(K)
    return {"ln1": jnp.ones((n, D), jnp.float32),
            "w_in": mat(n, D, D, 3 * D),
            "conv_w": jax.random.uniform(next(ks), (n, K, D), jnp.float32,
                                         -b, b).astype(cfg.dtype),
            "w_out": mat(n, D, D, D, scale=res * math.sqrt(3.0))}


def layer_stacks(params: Dict[str, Any], cfg: LlamaConfig, l: int):
    """-> (attention stack, index in it, feed-forward stack, index in it)
    of layer ``l``: the one ``layers`` tree at ``l`` twice for a model of
    one law, its kinds' stacks for a per-kind model."""
    if not cfg.per_kind:
        return params["layers"], l, params["layers"], l
    st = params[STACKS]
    kind, routed = cfg.layer_kinds[l], cfg.layer_routed(l)
    la = sum(k == kind for k in cfg.layer_kinds[:l])
    lf = sum(cfg.layer_routed(i) == routed for i in range(l))
    return (st[("full", "window", "mamba", "conv")[kind]], la,
            st["routed" if routed else "dense"], lf)


def param_specs(cfg: LlamaConfig, tp_size: int = 1,
                pp: int = 1, stored: bool = False) -> Dict[str, Any]:
    """PartitionSpecs: tp shards attention heads, the ffn dimension, and —
    when the model is untied and the vocab divides tp — the LM head's vocab
    dim. KV projections replicate when GQA kv_heads aren't divisible by tp;
    the embedding stays replicated (token gathers need the full table).
    With ``pp > 1`` the stacked layer dim of every per-layer param shards
    over the pipeline axis (each stage materializes only its layers).
    ``stored``: of the tree :func:`stored_params` makes (``pp`` 1), whose
    q / k / v matrices carry the heads' sharding on their merged axis."""
    from ..parallel.mesh import AXIS_EP, AXIS_PP

    if cfg.per_kind:
        # one chip (validate_tp): every tensor replicated, whatever its rank
        init = partial(_init_per_kind, cfg)
        shapes = jax.eval_shape(
            (lambda k: stored_params(init(k), cfg=cfg)) if stored else init,
            jax.random.PRNGKey(0))
        return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)
    st = AXIS_PP if pp > 1 else None     # the [L, ...] stack dim
    tp = AXIS_TP
    kv = tp if cfg.num_kv_heads % max(tp_size, 1) == 0 else None
    if cfg.num_experts:
        # experts shard over ep ([L, E, D, F] / [L, E, F, D]); router
        # replicated; the FFN intermediate dim additionally shards over tp
        # when divisible (matching moe_ffn's shard_map specs)
        ftp = tp if cfg.expert_width % max(tp_size, 1) == 0 else None
        ffn = {
            "wr": P(st, None, None),
            "wg": P(st, AXIS_EP, None, ftp),
            "wu": P(st, AXIS_EP, None, ftp),
            "wd": P(st, AXIS_EP, ftp, None),
        }
    else:
        ffn = {
            "wg": P(st, None, tp),
            "wu": P(st, None, tp),
            "wd": P(st, tp, None),
        }
    specs = {
        "embed": P(None, None),
        "layers": {
            "ln1": P(st, None),
            "ln2": P(st, None),
            "wq": P(st, None, tp, None),
            "wk": P(st, None, kv, None),
            "wv": P(st, None, kv, None),
            "wo": P(st, tp, None, None),
            **ffn,
        },
        "final_norm": P(None),
    }
    if cfg.sandwich_norms:
        specs["layers"]["ln1_post"] = P(st, None)
        specs["layers"]["ln2_post"] = P(st, None)
    if cfg.qk_norm:
        specs["layers"]["ln_q"] = P(st, None)
        specs["layers"]["ln_k"] = P(st, None)
    if cfg.has_indexer:
        # small next to the experts: replicated (validate_tp holds such a
        # model to tp == 1 anyway)
        specs["layers"]["wiq"] = P(st, None, None, None)
        for k in ("wik", "wiw", "ln_ik_w", "ln_ik_b"):
            specs["layers"][k] = P(st, None)
    if cfg.attention_bias:
        specs["layers"]["bq"] = P(st, tp, None)
        specs["layers"]["bk"] = P(st, kv, None)
        specs["layers"]["bv"] = P(st, kv, None)
    if not cfg.tie_embeddings:
        # vocab-sharded head: the [B,D]x[D,V] logits matmul partitions over
        # tp (each chip computes V/tp columns); GSPMD all-gathers the row
        # only where sampling consumes it. Weight memory drops V*D/tp too.
        head_tp = tp if cfg.vocab_size % max(tp_size, 1) == 0 else None
        specs["lm_head"] = P(None, head_tp)
    if stored:
        for w, heads, width in (("wq", cfg.num_heads, cfg.head_dim),
                                ("wk", cfg.num_kv_heads, cfg.head_dim),
                                ("wv", cfg.num_kv_heads, cfg.v_dim)):
            _, d_ax, h_ax, _ = specs["layers"][w]
            if _cut_out((cfg.num_layers, cfg.hidden_size, heads, width),
                        cfg.dtype):
                specs["layers"][w] = (P(h_ax, d_ax),) * cfg.num_layers
    return specs


# the three projections into attention, which an engine stores as its
# programs' matmuls read them (stored_params)
ATTN_IN = ("wq", "wk", "wv")

# ... up to this many bytes a layer. A matmul operand of 8-32 MB the compiler
# wants standing alone (it prefetches it whole into fast memory), and cuts
# it out of a stack to have it so; one of 100 MB and more (mimo's ``wq``,
# 12,288 x 4,096; every feed-forward matrix) it streams from where it lies
# in the stack, and handed it as a buffer of its own the chunk program was
# 6 % slower (mimo-v2-flash-7l.mixedqueue, PERF.md section 6, PR 50)
CUT_OUT_BYTES = 64 << 20


# a stored window stack's key that says its ``wq`` / ``wk`` columns are
# de-interleaved (a leaf of no elements: a tree's STRUCTURE says it, so a
# program that is handed the published tree still rotates as published)
ROPE_HALVES = "rope_halves"


def stored_params(params: Dict[str, Any], donate: bool = False,
                  cfg: Optional[LlamaConfig] = None) -> Dict[str, Any]:
    """The tree an engine hands its bucket programs, from the published one
    (what :func:`init_params` returns and a loader builds): the same values,
    ``wq`` / ``wk`` / ``wv`` (:data:`ATTN_IN`) of every attention stack a
    TUPLE of its layers' matrices [H x width, D] (heads merged, head-major,
    so a tensor-parallel shard of the merged axis is whole heads; the
    contraction dimension minor: a checkpoint's own ``[out, in]``) where a
    layer's matrix is no larger than :data:`CUT_OUT_BYTES`, every other
    leaf the very object it was. :func:`layer_in` reads either form,
    by the weight's rank.

    Why not the published stack [n, D, H, width]: at 7B widths the compiler
    re-laid it for the matmuls inside every program (the whole of ``wq``
    once a decode dispatch, a layer's three at every prefill chunk) and cut
    each layer out of it into a buffer of its own besides, a tenth of the
    device time of ``mistral-7b-16l.longprompt``. Why not a stack [n, H x
    width, D]: nothing is re-laid, but a layer is still cut out of the stack
    before it is multiplied by, 0.5 GB a decode STEP, and the dispatch is
    slower than the published form's (PERF.md section 6, PR 50, has the
    three forms' readings). A matrix that is a buffer of its own is read in
    place, through the compiler's asynchronous prefetch. What indexes the
    stack by a traced layer (a pipeline stage's ``shard_map``, the pager's
    one program a layer class) keeps the published tree.

    ``cfg`` of a model whose rotary pairs dims (2i, 2i + 1)
    (``rope_interleaved``): ``wq`` / ``wk`` of the stacks that rotate come
    with each head's columns de-interleaved (:func:`deinterleaved`; the
    stack then carries the key :data:`ROPE_HALVES`), so that the programs
    rotate halves like every other model's and the pairing costs a step
    nothing. The cache then holds K rows in that column order: the same
    scores, and nothing else reads a K row.

    The leaves may be device arrays (cut on the device a leaf at a time, as
    sharded as the source: ``donate`` deletes each source once its program
    is under way, so the peak is the tree and ONE leaf), host arrays (views)
    or tracers; a tree that is stored already comes back as it is."""
    if cfg is not None and cfg.rope_interleaved and cfg.use_rope:
        params = _rope_halves(params, cfg, donate)

    def relaid(_, w):
        if isinstance(w, tuple) or not _cut_out(w.shape, w.dtype):
            return w
        n, d = w.shape[:2]

        def cut(a):
            return tuple(a[i].reshape(d, -1).T for i in range(n))
        if not isinstance(w, jax.Array) or isinstance(w, jax.core.Tracer):
            return cut(w)
        sh = w.sharding
        if isinstance(sh, NamedSharding):
            _, d_ax, h_ax, _ = (*sh.spec, None, None, None, None)[:4]
            sh = NamedSharding(sh.mesh, P(h_ax, d_ax))
        out = jax.jit(cut, out_shardings=(sh,) * n)(w)
        if donate:
            w.delete()
        return out

    return map_attn_in(relaid, params)


def _rope_halves(params: Dict[str, Any], cfg: LlamaConfig,
                 donate: bool) -> Dict[str, Any]:
    """``params`` with ``wq`` / ``wk`` of every stack that rotates
    de-interleaved and marked (:func:`stored_params`); a stack that is
    marked already, or does not rotate (``nope_full``), as it is."""
    def turned(w):
        if not isinstance(w, jax.Array) or isinstance(w, jax.core.Tracer):
            return deinterleaved(w)
        out = jax.jit(deinterleaved, out_shardings=w.sharding)(w)
        if donate:
            w.delete()
        return out

    def stack(kind, st):
        if ROPE_HALVES in st or "wq" not in st or (
                cfg.nope_full and kind != "window"):
            return st
        return {**st, "wq": turned(st["wq"]), "wk": turned(st["wk"]),
                ROPE_HALVES: jnp.zeros((0,), jnp.float32)}

    if STACKS in params:
        return {**params, STACKS: {kind: stack(kind, st) for kind, st
                                   in params[STACKS].items()}}
    return {**params, "layers": stack("layers", params["layers"])}


def _cut_out(stack_shape, dtype) -> bool:
    """Whether a projection stack [n, D, H, width] is stored a matrix a
    layer (:data:`CUT_OUT_BYTES`)."""
    return (math.prod(stack_shape[1:]) * jnp.dtype(dtype).itemsize
            <= CUT_OUT_BYTES)


def map_attn_in(fn, params: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with ``fn(name, leaf)`` in place of ``wq`` / ``wk`` / ``wv``
    of every attention stack (the ``layers`` tree, or a per-kind model's
    ``full`` and ``window`` stacks; a latent stack has none), every other
    leaf the very object it was."""
    def stack(st):
        return {k: fn(k, w) if k in ATTN_IN else w for k, w in st.items()}

    if STACKS in params:
        return {**params, STACKS: {kind: stack(st) for kind, st
                                   in params[STACKS].items()}}
    return {**params, "layers": stack(params["layers"])}


def validate_tp(cfg: LlamaConfig, tp: int, ep: int = 1) -> None:
    if cfg.num_heads % tp:
        raise ValueError(f"num_heads {cfg.num_heads} not divisible by tp={tp}")
    if not cfg.num_experts and cfg.intermediate_size % tp:
        raise ValueError(f"ffn {cfg.intermediate_size} not divisible by tp={tp}")
    if cfg.has_state and (tp > 1 or ep > 1):
        raise ValueError(
            "a model with state-space layers runs on one chip: its stacks, "
            "its per-lane state pool and its K/V pool are not sharded "
            f"(got tp={tp}, ep={ep})")
    if cfg.has_latent and (tp > 1 or ep > 1):
        raise ValueError(
            "a model with latent attention runs on one chip: every head "
            "reads the ONE row a token its cache keeps, which a head-sharded "
            "mesh would replicate, and its stacks are not sharded "
            f"(got tp={tp}, ep={ep})")
    if cfg.per_kind and (tp > 1 or ep > 1):
        raise ValueError(
            "a model with window and full layers of their own head counts "
            "and caches runs on one chip: its stacks and its two page pools "
            f"are not sharded (got tp={tp}, ep={ep})")
    if cfg.has_indexer and (tp > 1 or ep > 1):
        raise ValueError(
            "a model with an indexer (learned top-k attention) runs on one "
            "chip: the index-key pool and the selection are not sharded "
            f"(got tp={tp}, ep={ep})")
    if ep > 1:
        if not cfg.num_experts:
            raise ValueError("ep > 1 needs an MoE model (num_experts > 0)")
        if cfg.num_experts % ep:
            raise ValueError(f"num_experts {cfg.num_experts} not divisible "
                             f"by ep={ep}")


def validate_pp(cfg: LlamaConfig, pp: int, tp: int = 1) -> None:
    """Pipeline-parallel constraints for the staged serving path."""
    if pp <= 1:
        return
    if cfg.has_latent:
        raise ValueError(f"pp={pp}: {NO_LATENT}")
    if cfg.per_kind:
        raise ValueError(f"pp={pp}: "
                         f"{NO_STATE if cfg.has_state else NO_SECOND_CACHE}")
    if cfg.num_layers % pp:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp={pp}")
    if tp > 1 and cfg.num_kv_heads % tp:
        raise ValueError(
            f"pp > 1 with tp={tp} needs kv heads divisible by tp "
            f"(got {cfg.num_kv_heads}): the staged path shards the KV pool")


def kv_block_bytes(cfg: LlamaConfig, page_size: int) -> int:
    """Bytes of one KV block (k+v, and the index keys of a model with an
    indexer, all layers) at device precision — the
    ONE unit the byte-honest planes price in (engine residency gauges,
    paged-lane admission, router bytes scoring): a page of the GLOBAL cache
    kind as ``engine/cache.CacheKind`` describes it (every layer of a model
    of one law; a per-kind model's full layers, whose window cache is its
    second kind's ``token_bytes``). ml_dtypes registers bfloat16 with numpy,
    so np.dtype resolves every served precision."""
    from ..engine.cache import cache_kinds

    return page_size * cache_kinds(cfg)[0].token_bytes(
        np.dtype(cfg.dtype).itemsize)


def kv_cache_spec(cfg: LlamaConfig, tp: int, pp: int = 1) -> P:
    """KV pool sharding ([L, Hkv, n_pages, page, Dh]): shard kv heads over tp
    when divisible, else replicate (GQA with kv_heads < tp). With ``pp > 1``
    the layer dim additionally shards over the pipeline axis — each stage
    holds only its layers' pages (the memory win that fits 70B on slices)."""
    from ..parallel.mesh import AXIS_PP

    st = AXIS_PP if pp > 1 else None
    if cfg.per_kind:
        return P(None, None, None, None, None)    # one chip (validate_tp)
    if cfg.num_kv_heads % tp == 0:
        return P(st, AXIS_TP, None, None, None)
    return P(st, None, None, None, None)


# ---------------------------------------------------------------------------
# Ops
# ---------------------------------------------------------------------------

# The parts of a bucket program. Every operation that ``forward`` /
# ``forward_decode`` and the sampler behind them trace lies under exactly one
# INNERMOST of these ``jax.named_scope``s, and the compiled instruction says
# so in its ``op_name`` (``jit(step)/while/body/dynamo.attn_in/dot_general``;
# ``jaxenv.PROGRAM_LOCATIONS`` keeps the name stack there), which a device
# trace hands on as the operation's ``tf_op``: a step's device time is read
# by scope, whatever XLA fuses and however it names the fusion. A scope may
# nest (``moe_ffn`` inside ``ffn``, a per-kind model's ``attn_full`` /
# ``attn_window`` inside ``attn``): the last ``dynamo.*`` component of the
# name wins. A fusion that spans two scopes carries ONE name, that of the
# instruction XLA made its root. ``tests/test_step_scopes.py`` holds the
# programs to the list and the benchmark's reader
# (``benchmarks/harness/scopes.py`` ``GROUPS``) to it.
SCOPES = tuple("dynamo." + s for s in (
    "embed",         # token embedding (and its multipliers, image overrides)
    "attn_in",       # layer_in: norm, q/k/v and index projections, rotary
    "kv_write",      # new K/V and index-key rows scattered into the pools
    "attn",          # context gather, masks, attention of a one-law model
    "attn_full",     # ... of a per-kind model's full layers
    "attn_window",   # ... of its window layers
    "index_select",  # an indexer's scores and exact top-k (ops/attention.py)
    "attn_out",      # out-projection and residual
    "ssm_in",        # a state layer's norm and input projections
    "ssm_step",      # its recurrence, one token (decode)
    "ssm_scan",      # its recurrence over a chunk (prefill)
    "ssm_out",       # its out-projection and residual
    "ffn",           # norm, dense feed-forward, residual
    "moe_ffn",       # router and routed experts (models/moe.py)
    "head",          # final norm and vocabulary projection
    "sample",        # penalties, argmax, the top-k window (engine/sampling.py)
))


def scope(name: str):
    """``jax.named_scope`` of ``dynamo.<name>``, one of :data:`SCOPES`."""
    return jax.named_scope(SCOPES[SCOPES.index("dynamo." + name)])


def _inner(name: str, on: bool = True):
    """A name INSIDE one of :data:`SCOPES` (no ``dynamo.`` in front, so the
    benchmark's reader still files the operation under the scope around
    it): what a trace's viewer tells apart within one scope, the one norm
    of a parallel block (``block_norm``) and its shared experts
    (``moe_shared``). Off: nothing, and an older model's programs carry the
    names they carried."""
    return jax.named_scope(name) if on else contextlib.nullcontext()


def _attn_scope(cfg: LlamaConfig, window: bool):
    """The scope of a layer's attention itself: by kind for a per-kind model
    (``attn_window`` / ``attn_full``: the per-layer roofline metrics read the
    device time under each), ``attn`` for every other."""
    if not cfg.has_window:
        return scope("attn")
    return scope("attn_window" if window else "attn_full")


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             offset: bool = False) -> jax.Array:
    """RMSNorm; ``offset=True`` = Gemma convention (weights stored
    zero-centered, output scales by 1 + w)."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    if offset:
        wf = 1.0 + wf
    return (xf * scale * wf).astype(x.dtype)


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Bias-free LayerNorm: (x - mean) x rsqrt(var + eps) x w, in float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (xc * scale * w.astype(jnp.float32)).astype(x.dtype)


def _normed(x: jax.Array, w: jax.Array, cfg: "LlamaConfig") -> jax.Array:
    """The model's norm of the stream ``x`` (RMSNorm, or the mean-centred
    LayerNorm of a model that says so: ``cfg.layer_norm``) as the layer's
    matrices take it: as it comes for every model whose stream is in the
    model's dtype, in the model's dtype where the stream is wider
    (``LlamaConfig.stream_dtype``)."""
    h = (layer_norm(x, w, cfg.rms_eps) if cfg.layer_norm
         else rms_norm(x, w, cfg.rms_eps, cfg.norm_offset))
    return h if cfg.stream_dtype == cfg.dtype else h.astype(cfg.dtype)


def _act(cfg: "LlamaConfig"):
    if cfg.hidden_act == "gelu_tanh":
        return partial(jax.nn.gelu, approximate=True)
    if cfg.hidden_act == "gelu":
        return partial(jax.nn.gelu, approximate=False)
    return jax.nn.silu


def _embed(params: Dict[str, Any], cfg: "LlamaConfig",
           tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        # Gemma scales inputs by sqrt(D), rounded through the embed dtype
        x = x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)
    if cfg.embed_multiplier is not None:
        x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)
    return (x if cfg.stream_dtype == cfg.dtype
            else x.astype(cfg.stream_dtype))


def _rope_inv_freq(cfg: LlamaConfig, local: bool = False) -> np.ndarray:
    Dh = cfg.rotary_dim or cfg.head_dim
    if local:
        # gemma3 sliding layers: own base frequency, NO scaling (HF builds
        # the local rotary with default rope_type regardless of
        # config.rope_scaling)
        theta = cfg.rope_local_theta or cfg.rope_theta
        return (1.0 / (theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh))
                ).astype(np.float32)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh))
    rs = cfg.rope_scaling or {}
    if rs.get("rope_type") == "linear" or rs.get("type") == "linear":
        # linear position scaling (gemma3 4b+): frequencies divide by factor
        inv = inv / rs.get("factor", 1.0)
    if rs.get("rope_type") == "ggml_factors":
        # llama.cpp exports llama3-style scaling as a rope_freqs tensor of
        # per-frequency divisors (ggml applies inv_freq / factor[i])
        factors = np.asarray(rs["factors"], dtype=np.float64)
        if factors.shape != inv.shape:
            raise ValueError(
                f"rope_freqs tensor has {factors.shape[0]} factors but "
                f"head_dim {Dh} needs {inv.shape[0]}")
        inv = inv / factors
    if rs.get("rope_type") == "yarn" or rs.get("type") == "yarn":
        # YaRN: each frequency between extrapolation (as trained) and
        # interpolation (/ factor), by where its wavelength lies in the
        # correction range of beta_fast .. beta_slow turns over the
        # original context (``_yarn_find_correction_range`` of the source)
        factor = float(rs["factor"])
        orig = rs.get("original_max_position_embeddings", 4096)

        def correction(rot):
            return (Dh * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))
        low = max(math.floor(correction(rs.get("beta_fast", 32))), 0)
        high = min(math.ceil(correction(rs.get("beta_slow", 1))), Dh - 1)
        ramp = np.clip((np.arange(Dh // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        keep = 1.0 - ramp                 # 1: extrapolate, 0: interpolate
        inv = inv / factor * (1.0 - keep) + inv * keep
    if rs.get("rope_type") == "llama3" or rs.get("type") == "llama3":
        # llama3 frequency-dependent NTK-style scaling
        factor = rs.get("factor", 8.0)
        lo = rs.get("low_freq_factor", 1.0)
        hi = rs.get("high_freq_factor", 4.0)
        orig = rs.get("original_max_position_embeddings", 8192)
        wavelen = 2 * np.pi / inv
        ratio = orig / wavelen
        smooth = np.clip((ratio - lo) / (hi - lo), 0.0, 1.0)
        scaled = np.where(ratio < lo, inv / factor,
                          np.where(ratio > hi, inv,
                                   (1 - smooth) * inv / factor + smooth * inv))
        inv = scaled
    return inv.astype(np.float32)


def rope_tables(cfg: LlamaConfig, positions: jax.Array,
                local: bool = False) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions [...]: -> [..., Dh/2].
    ``local=True`` = the sliding layers' table (gemma3 dual-base rope)."""
    inv = jnp.asarray(_rope_inv_freq(cfg, local=local))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def rope_pair(cfg: LlamaConfig, positions: jax.Array):
    """-> ((cos, sin) at the model's base, (cos, sin) at the sliding layers'
    own base or None for a model with one base): what :func:`pick` takes."""
    return rope_tables(cfg, positions), (
        rope_tables(cfg, positions, local=True)
        if cfg.rope_local_theta is not None else None)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x: [..., H, Dh]; cos/sin: [..., Dr/2] (broadcast over H). Tables
    narrower than the head rotate its first Dr dims and pass the rest.
    ``interleaved``: dims (2i, 2i + 1) are a pair (GPT-J's pairing), not (i,
    i + Dr / 2): what a model of that pairing costs on weights as published
    (:func:`deinterleaved` columns make it the plain form)."""
    Dr = 2 * cos.shape[-1]
    if Dr < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :Dr], cos, sin, interleaved), x[..., Dr:]],
            axis=-1)
    # (the order of these lines is the order of the traced operations: an
    # older model's program text is held to what it was, lowered_same.py)
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], Dr // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    turned = [x1 * c - x2 * s, x2 * c + x1 * s]
    if interleaved:
        return jnp.stack(turned, axis=-1).reshape(x.shape).astype(x.dtype)
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def deinterleaved(w: jax.Array) -> jax.Array:
    """A q or k projection [..., Dh] with each head's columns reordered (0,
    2, 4, ..., 1, 3, 5, ...): rotate-half on what it projects pairs the
    dims the interleaved rotary pairs on the published columns, and q . k is
    the same sum in another order."""
    Dh = w.shape[-1]
    return jnp.concatenate([w[..., 0:Dh:2], w[..., 1:Dh:2]], axis=-1)


NEG_INF = -1e30


def attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
           scale: Optional[float] = None,
           softcap: Optional[float] = None,
           sink: Optional[jax.Array] = None) -> jax.Array:
    """GQA attention. q: [B,T,Hq,Dh]; k: [B,S,Hkv,Dh]; v: [B,S,Hkv,Dv];
    mask: [B,T,S] bool (True = attend). Returns [B,T,Hq,Dv]. fp32 softmax.
    ``softcap`` applies Gemma2's tanh capping to the scores BEFORE masking
    (HF order). ``sink`` [Hq] float32: a logit a head that takes softmax
    weight and gives no value (one more key whose value is zero)."""
    B, T, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, T, Hkv, G, Dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (scale if scale is not None else 1.0 / math.sqrt(Dh))
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    if sink is None:
        w = jax.nn.softmax(scores, axis=-1)
    else:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, G, 1, 1),
            (*scores.shape[:-1], 1))
        w = jax.nn.softmax(jnp.concatenate([scores, col], -1),
                           axis=-1)[..., :-1]
    out = jnp.einsum("bhgts,bshd->bthgd", w.astype(v.dtype), v)
    return out.reshape(B, T, Hq, v.shape[-1])


def attend_ctx(cfg: LlamaConfig, q: jax.Array, k_ctx: jax.Array,
               v_ctx: jax.Array, mask: jax.Array,
               keep: Optional[jax.Array] = None,
               sink: Optional[jax.Array] = None) -> jax.Array:
    """:func:`attend` over a gathered context with the model's scale and
    softcap; ``keep`` (an indexer's selection) narrows the mask."""
    return attend(q, k_ctx, v_ctx, mask if keep is None else mask & keep,
                  scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
                  sink=sink)


@scope("head")
def _lm_head(x: jax.Array, params: Dict[str, Any],
             cfg: LlamaConfig) -> jax.Array:
    """Final norm + vocab projection (+ Gemma2 final logit softcap), fp32;
    ``x`` [..., D] with leading dimensions as they come."""
    x = _normed(x, params["final_norm"], cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.logits_scaling is None and cfg.stream_dtype == cfg.dtype:
        logits = jnp.einsum("...d,dv->...v", x, head.astype(x.dtype))
        logits = logits.astype(jnp.float32)
    else:
        # a model that keeps a float32 stream: float32 straight from the
        # accumulator. A bfloat16 result rounds the LARGEST logits hardest
        # (a spacing of 0.03 sigma at the top of a 100k vocabulary; 0.007
        # sigma rms at the greedy token of 25,600), which was most of such
        # a model's distance from its float32 reference
        # (LlamaConfig.stream_dtype has the numbers)
        logits = jnp.einsum("...d,dv->...v", x, head.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.logits_scaling is not None:
            logits = logits / cfg.logits_scaling
    if cfg.final_logit_softcap:
        cap = cfg.final_logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    return logits


def _require_xla_attn(cfg: LlamaConfig, attn_impl: str) -> None:
    """Ring attention is the one path left without softcap/sliding support
    (cross-shard windows don't compose with the ring schedule); the Pallas
    flash/paged kernels take window+softcap+scale natively (round 5 —
    Gemma2/3 no longer forfeit the fast path)."""
    if attn_impl == "ring" and (cfg.attn_logit_softcap
                                or cfg.sliding_window is not None):
        raise ValueError(
            "attn_impl='ring' does not support score softcapping / sliding "
            "windows (Gemma2/3); use attn_impl='pallas' or 'xla'")


# ---------------------------------------------------------------------------
# KV pool access
#
# The pool is stored once, as [L, Hkv, n_pages, page, Dh] in XLA's default
# tiled layout, and every program reads and writes it AS STORED. That holds
# only while each scatter's and gather's window is contiguous in that layout:
# the natural ``pool[l, :, page, off]`` has the window [Hkv, ·, ·, Dh], for
# which XLA's layout assignment re-lays the WHOLE pool to [L, pages, page,
# Hkv, Dh] at the program's entry and back at its exit, and once more per
# layer for whatever wants the stored order (the paged kernel). With the
# head index spelt out the window is one [Dh] row (or one [page, Dh] page)
# and the donated pool is updated in place. Measured on the chip: PERF.md §6,
# PR 26. Every program that touches a pool goes through these four.
# ---------------------------------------------------------------------------

#
# A FOLDED pool (``LlamaConfig.kv_fold`` f > 1: rows narrower than a 128-lane
# tile) is [L, Hkv, n_pages, page // f, f * Dh]: f consecutive tokens of a
# page share one pool row, token ``off`` in row ``off // f`` at lanes ``(off
# % f) * Dh ..``, which is the order the paged dma kernel's copies take. XLA
# keeps a pool of 64-lane rows pages-minor and re-lays it whole at a decode
# program's entry and exit; whole-tile rows it leaves as stored (PERF.md §7
# b, and the index keys' fold below). The four accessors take either.

def kv_write(pool: jax.Array, layer, w_page: jax.Array, w_off: jax.Array,
             rows: jax.Array, mode: Optional[str] = None) -> jax.Array:
    """Write ``rows`` [n, Hkv, Dh] into layer ``layer`` at token slots
    (``w_page``, ``w_off``), both [n]. Into a folded pool (its rows f times
    as wide as ``rows``) whole pool rows are written: each token's row is
    read, overlaid with every token of this call that shares it, and
    scattered back (:func:`index_write` says why)."""
    heads = jnp.arange(pool.shape[1])[None, :]
    f = pool.shape[-1] // rows.shape[-1]
    if f == 1:
        return pool.at[layer, heads, w_page[:, None], w_off[:, None]].set(
            rows, mode=mode)
    n, Hkv, Dh = rows.shape
    r, slot = w_off // f, w_off % f
    at = pool.at[layer, heads, w_page[:, None], r[:, None]]
    old = at.get(mode="clip").reshape(n, Hkv, f, Dh)
    fills = _fold_fills(w_page, r, slot, f)                      # [n,m,f]
    new = rows.astype(pool.dtype)[jnp.argmax(fills, axis=1)]     # [n,f,H,Dh]
    merged = jnp.where(jnp.any(fills, axis=1)[:, None, :, None],
                       new.transpose(0, 2, 1, 3), old)
    return at.set(merged.reshape(n, Hkv, f * Dh), mode=mode)


def _fold_fills(w_page, r, slot, f: int) -> jax.Array:
    """[n, m, f] bool: token m of the call fills slot f of token n's row."""
    shares = (w_page[:, None] == w_page[None]) & (r[:, None] == r[None])
    return shares[:, :, None] & (slot[None, :, None]
                                 == jnp.arange(f)[None, None, :])


def kv_write_pages(pool: jax.Array, layer, pages: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """Write ``rows`` [n, Hkv, Dh] a page at a time: the rows are ``pages``
    [B, P]'s runs in order, run (b, j) the ``n // (B * P)`` tokens that
    start on page ``pages[b, j]``'s first slot (a whole page, or a chunk
    shorter than one). One [run, Dh] window a (head, run) where
    :func:`kv_write` issues one a (head, token); a folded pool takes the
    run's folded rows as they lie, with nothing read back. Runs of padding
    name scratch page 0, and a run writes its slots past the lane's last
    real token too (its own unsealed page: nothing reads them before a
    later write)."""
    Hkv, f = pool.shape[1], pool.shape[-1] // rows.shape[-1]
    run = rows.shape[0] // pages.size
    if run % f or run * pages.size != rows.shape[0]:
        raise ValueError(f"{rows.shape[0]} rows do not fill {pages.size} "
                         f"page runs of rows of {f} token(s)")
    upd = rows.astype(pool.dtype).reshape(pages.size, run, Hkv, -1)
    upd = upd.transpose(2, 0, 1, 3).reshape(Hkv, pages.size, run // f, -1)
    at = (jnp.arange(Hkv)[:, None], pages.reshape(1, -1))
    if run // f < pool.shape[3]:
        at += (slice(run // f),)
    return pool.at[(layer, *at)].set(upd)


def kv_rows(pool: jax.Array, layer, r_page: jax.Array,
            r_off: jax.Array, fold: int = 1) -> jax.Array:
    """The rows at token slots (``r_page``, ``r_off``), both [...]:
    [..., Hkv, Dh]."""
    heads = jnp.arange(pool.shape[1])
    if fold == 1:
        return pool[layer, heads, r_page[..., None], r_off[..., None]]
    wide = pool[layer, heads, r_page[..., None], (r_off // fold)[..., None]]
    wide = wide.reshape(*wide.shape[:-1], fold, -1)
    return jnp.take_along_axis(
        wide, (r_off % fold)[..., None, None, None], axis=-2)[..., 0, :]


def kv_pages(pool: jax.Array, layer, pages: jax.Array,
             fold: int = 1) -> jax.Array:
    """Whole pages in order — ``pages`` [B, P] page ids — as a context
    [B, P * page, Hkv, Dh]: one [page, Dh] window per (head, page) instead
    of ``page`` rows (a folded page's rows are its tokens in order)."""
    Hkv = pool.shape[1]
    page, Dh = pool.shape[3] * fold, pool.shape[4] // fold
    B, P = pages.shape
    ctx = pool[layer, jnp.arange(Hkv)[None, :, None], pages[:, None, :]]
    return ctx.reshape(B, Hkv, P * page, Dh).transpose(0, 2, 1, 3)


# The index keys of a model with an indexer (one head, ``index_head_dim``
# wide) live in a third pool on the SAME pages: [L, 1, n_pages, page // f,
# f * Di] with f = 128 // Di tokens folded into one 128-lane row (Di = 64:
# two tokens a row), because a pool whose rows are narrower than a lane tile
# is re-laid whole at every program's entry and exit on a v5e (PERF.md §7 b).
# A token's slot is row ``off // f``, lanes ``(off % f) * Di ..``.

def index_pool_shape(cfg: LlamaConfig, num_pages: int,
                     page: int) -> Tuple[int, ...]:
    f = index_fold(cfg)
    if page % f:
        raise ValueError(f"page size {page} does not fold by {f} "
                         f"(index_head_dim {cfg.index_head_dim})")
    return (cfg.num_layers, 1, num_pages, page // f, f * cfg.index_head_dim)


def index_fold(cfg: LlamaConfig) -> int:
    return max(1, 128 // cfg.index_head_dim)


def index_write(pool: jax.Array, layer, w_page: jax.Array, w_off: jax.Array,
                rows: jax.Array) -> jax.Array:
    """Write index keys ``rows`` [n, Di] at token slots (``w_page``,
    ``w_off``), both [n], as WHOLE 128-lane rows: each token's row is read,
    overlaid with the new key of every token of this call that shares it
    (its neighbours in the fold, so that rows written twice are written
    alike), and scattered back like a K/V row. A scatter of [Di]-wide
    windows at a lane offset runs as a serial loop of a dozen operations a
    token on a v5e (4.7 us a token, 7 ms of a 256-token chunk's six layers;
    my chip run, PR 28)."""
    n, Di = rows.shape
    f = pool.shape[-1] // Di
    rows = rows.astype(pool.dtype)
    if f == 1:
        return pool.at[layer, 0, w_page, w_off].set(rows)
    r, slot = w_off // f, w_off % f
    old = pool[layer, 0, w_page, r].reshape(n, f, Di)
    fills = _fold_fills(w_page, r, slot, f)                        # [n,m,f]
    new = rows[jnp.argmax(fills, axis=1)]                          # [n,f,Di]
    merged = jnp.where(jnp.any(fills, axis=1)[..., None], new, old)
    return pool.at[layer, 0, w_page, r].set(merged.reshape(n, f * Di))


def index_pages(pool: jax.Array, layer, pages: jax.Array,
                Di: int) -> jax.Array:
    """Whole pages in order — ``pages`` [B, P] — as the lane's index keys
    in logical order [B, P * page, Di]."""
    B, P = pages.shape
    return pool[layer, 0, pages].reshape(B, -1, Di)


def _index_rope(cfg: LlamaConfig, positions: jax.Array):
    """Rotary tables over ALL ``index_head_dim`` dims, the model's theta."""
    Di = cfg.index_head_dim
    inv = jnp.asarray((1.0 / (cfg.rope_theta ** (
        np.arange(0, Di, 2, dtype=np.float64) / Di))).astype(np.float32))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def _index_project(h: jax.Array, lp: Dict[str, Any], l: int,
                   cfg: LlamaConfig, cos_i: jax.Array, sin_i: jax.Array):
    """The indexer's three projections of the layer's normed input ``h``
    [B,T,D]: index queries [B,T,Hi,Di] and THE index key [B,T,Di]
    (LayerNorm, then rope, as the queries), score weights [B,T,Hi]."""
    qi = jnp.einsum("btd,dhk->bthk", h, lp["wiq"][l])
    ki = jnp.einsum("btd,dk->btk", h, lp["wik"][l]).astype(jnp.float32)
    mu = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
    ki = ((ki - mu) * jax.lax.rsqrt(var + cfg.rms_eps) * lp["ln_ik_w"][l]
          + lp["ln_ik_b"][l]).astype(h.dtype)
    w = jnp.einsum("btd,dh->bth", h, lp["wiw"][l])
    qi = apply_rope(qi, cos_i, sin_i)
    ki = apply_rope(ki[:, :, None, :], cos_i, sin_i)[:, :, 0]
    return qi, ki, w


def _index_step(h: jax.Array, lp: Dict[str, Any], l: int, cfg: LlamaConfig,
                rope_i, i_pool: jax.Array, w_page: jax.Array,
                w_off: jax.Array, pages: jax.Array,
                visible: Optional[jax.Array],
                w_pages: Optional[jax.Array] = None):
    """One layer's indexer, prefill chunk and decode step alike: write the
    new tokens' index keys (by page where ``w_pages`` names the chunk's
    page runs: :func:`kv_write_pages`, the pool's one head), then
    (``visible`` [B,T,S] given: the context bucket is longer than
    ``index_topk``) score the lane's cached index keys and keep each
    query's exact top-k. -> (i_pool, keep [B,T,S] or None)."""
    from ..ops.attention import index_scores, topk_keep

    qi, ki, wi = _index_project(h, lp, l, cfg, *rope_i)
    with scope("kv_write"):
        ki = ki.reshape(-1, ki.shape[-1])
        i_pool = (index_write(i_pool, l, w_page, w_off, ki)
                  if w_pages is None
                  else kv_write_pages(i_pool, l, w_pages, ki[:, None]))
    if visible is None:
        return i_pool, None
    ctx = index_pages(i_pool, l, pages, cfg.index_head_dim)
    return i_pool, topk_keep(index_scores(qi, ctx, wi), visible,
                             cfg.index_topk)


# ---------------------------------------------------------------------------
# The decoder layer
#
# One layer's mathematics, written once, in two halves around the attention
# itself. A forward owns what differs between the ways of serving: how the
# new rows are addressed into the cache (token slots, page tables, a stage's
# slice, the pager's write index), what attention runs over (rows or pages
# gathered, the pool in place, a shard, a ring, the pager's segments), and
# whether ``l`` is a Python integer or traced (a pipeline stage's offset, the
# pager's one program for every layer of a class). Everything else is here:
# a term added to the layer is added here, and a forward that does not hand
# over what the term needs refuses the model from here.
# ---------------------------------------------------------------------------

# the one refusal of a model with an indexer by a forward whose cache I/O
# was never given the third pool (forward_pp, the pager: ROADMAP Design 4)
# ... and of a per-kind model (window and full layers with caches of their
# own) by a forward that was given one cache (forward_pp, the pager, verify)
NO_SECOND_CACHE = ("this path carries one K/V cache: a model whose window "
                   "layers keep a window of cache in a page pool of their "
                   "own, with their own head count, needs both")

NO_STATE = ("this path carries K/V blocks alone: a model with state-space "
            "layers keeps a recurrent state a lane beside them, which no "
            "block holds, and a block re-entered or moved without the "
            "state at its boundary would decode from the wrong state")

NO_LATENT = ("this path projects K and V a head and caches them: a model "
             "with latent attention keeps one compressed row a token for "
             "all heads and attends in a form of its own")

NO_INDEX_KEYS = ("this cache carries no index-key pool: a model with an "
                 "indexer (learned top-k attention) writes its index keys "
                 "beside K/V and selects from them")


def pick(sliding, if_sliding, if_full):
    """The sliding or the full variant of a layer's operand (rotary tables,
    mask), by ``sliding`` = ``cfg.layer_sliding(l)``: a Python bool picks; a
    traced one (a pipeline stage) selects between the two, leaf by leaf.
    ``if_sliding`` is None where the model has one variant only."""
    if sliding is False or if_sliding is None:
        return if_full
    if sliding is True:
        return if_sliding
    return jax.tree.map(partial(jnp.where, sliding), if_sliding, if_full)


def _rope_of(cfg: LlamaConfig, l, rope_sl, rope):
    """The (cos, sin) layer ``l`` rotates by (:func:`pick` of the sliding
    layers' tables and the model's), or None for a layer that takes q and k
    as projected: a full layer of a model whose window layers alone rotate
    (``nope_full``)."""
    if cfg.nope_full and not cfg.layer_window(l):
        return None
    return pick(cfg.layer_sliding(l), rope_sl, rope)


@scope("attn_in")
def layer_in(x: jax.Array, lp: Dict[str, Any], l, cfg: LlamaConfig,
             rope: Tuple[jax.Array, jax.Array], pools: Tuple[jax.Array, ...],
             w_page: jax.Array, w_off: jax.Array, mode: Optional[str] = None,
             index: Optional[Tuple[Any, ...]] = None,
             stats: Optional[Dict[str, Any]] = None,
             hold: Optional[List[jax.Array]] = None,
             w_pages: Optional[jax.Array] = None,
             normed: Optional[List[jax.Array]] = None):
    """The layer before attention: input norm, the three projections (bias,
    q/k norm), rotary, and the new rows into the cache.

    ``lp`` holds the stacked layer parameters (the whole stack or a stage's
    slice) and ``l`` indexes them and ``pools`` = (k_pool, v_pool[, i_pool]);
    ``rope`` is the (cos, sin) the caller chose for this layer
    (:func:`_rope_of`; None: a layer without rotary). ``normed``: the caller
    of a PARALLEL block passes a list, which receives the normed stream the
    projections read (the feed-forward reads it too: :func:`layer_out`).
    The rows of ``x`` [B,T,D] go to token slots (``w_page``, ``w_off``), both
    [B*T]; ``mode`` as :func:`kv_write`'s; or, ``w_pages`` [B, P] given, to
    those pages a run each (:func:`kv_write_pages`: a prefill chunk that
    starts on a page's first slot). For a model with an indexer
    ``index`` = (rope_i, pages, visible) is what :func:`_index_step` takes
    beside the slots, and ``stats["keep"]``, where the caller put a list,
    receives the layer's keep mask. ``hold``: a caller whose attention
    kernel writes the new rows itself (:func:`kernel_writes`) passes a list,
    which receives the K and V rows [B*T, Hkv, D] as stored; the two pools
    then come back as they went in.
    -> (q [B,T,Hq,Dh], pools, keep [B,T,S] or None). A model with latent
    attention: :func:`_latent_in`, whose q is a pair."""
    if cfg.has_latent:
        return _latent_in(x, lp, l, cfg, rope, pools, w_page, w_off, mode,
                          hold, w_pages)
    with _inner("block_norm", cfg.parallel_block):
        h = _normed(x, lp["ln1"][l], cfg)
    if normed is not None:
        normed.append(h)
    q = _project(h, lp["wq"][l], cfg.head_dim)
    k = _project(h, lp["wk"][l], cfg.head_dim)
    v = _project(h, lp["wv"][l], cfg.v_dim)
    if cfg.attention_bias:
        q = q + lp["bq"][l]
        k = k + lp["bk"][l]
        v = v + lp["bv"][l]
    if cfg.qk_norm:
        # gemma3: per-head RMSNorm on q/k AFTER projection, BEFORE rope
        q = rms_norm(q, lp["ln_q"][l], cfg.rms_eps, cfg.norm_offset)
        k = rms_norm(k, lp["ln_k"][l], cfg.rms_eps, cfg.norm_offset)
    if cfg.use_rope and rope is not None:
        # (a stored tree's columns are de-interleaved already)
        gptj = cfg.rope_interleaved and ROPE_HALVES not in lp
        q = apply_rope(q, *rope, gptj)
        k = apply_rope(k, *rope, gptj)
    if cfg.attn_value_scale:
        v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
    k_pool, v_pool, *i_pool = pools
    pad = k_pool.shape[-1] // cfg.kv_fold - k.shape[-1]
    if pad:
        # K rows are stored a whole number of lane tiles wide
        # (LlamaConfig.k_store_dim); zeros beyond head_dim add nothing to
        # a score, and q goes to attention as wide as the rows it meets
        q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) for a in (q, k))
    # write, then attend: the new rows are part of their own context
    rows = [a.reshape(-1, *a.shape[2:]) for a in (k, v)]
    if hold is None:
        k_pool, v_pool = _write_rows((k_pool, v_pool), l, w_page, w_off,
                                     rows, mode, w_pages)
    else:
        hold.extend(rows)
    keep = None
    if cfg.has_indexer:
        if index is None or not i_pool:
            raise ValueError(NO_INDEX_KEYS)
        rope_i, pages, visible = index
        i_pool[0], keep = _index_step(h, lp, l, cfg, rope_i, i_pool[0],
                                      w_page, w_off, pages, visible, w_pages)
    if stats is not None and "keep" in stats:
        stats["keep"].append(keep)
    return q, (k_pool, v_pool, *i_pool), keep


def _project(h: jax.Array, w: jax.Array, width: int) -> jax.Array:
    """``h`` [B,T,D] through one layer's q, k or v projection -> [B,T,H,
    width], in whichever form the weight comes, which its rank says: [H x
    width, D] as an engine stores it (:func:`stored_params`: a plain matmul
    with the contraction dimension minor, then a reshape of the small
    result), or the published [D, H, width]."""
    if w.ndim == 3:
        return jnp.einsum("btd,dhk->bthk", h, w)
    y = jnp.einsum("btd,fd->btf", h, w)
    return y.reshape(*y.shape[:2], -1, width)


@scope("kv_write")
def _write_rows(pools, l, w_page, w_off, rows, mode, w_pages):
    """A layer's new ``rows`` (one array a pool) into ``pools``: a window a
    token, or a window a page run where the caller named them."""
    return tuple(kv_write(p, l, w_page, w_off, r, mode) if w_pages is None
                 else kv_write_pages(p, l, w_pages, r)
                 for p, r in zip(pools, rows))


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2's MLA, ``cfg.has_latent``)
#
# For the layer's normed input h: q = W_uq RMSNorm(W_dq h), a head's q =
# [q_nope | q_pe]; [c, k_pe] = W_dkv h; c~ = RMSNorm(c); rotary on q_pe and
# on the ONE k_pe every head shares. The cache keeps c~ (``kv_lora_rank``
# wide, in the V pool) and the rotated k_pe (in the K pool, zero-padded to a
# lane tile) a token, and nothing per head. Published, a head's k = [W_uk c~
# | k_pe] and v = W_uv c~ (the float32 reference expands them so). Served,
# decode steps and prefill chunks alike run ABSORBED: q^ = W_uk^T q_nope
# (``kv_lora_rank`` wide), score = (q^ . c~ + q_pe . k_pe) x scale, o = W_uv
# (sum p c~): the kernels read the cached rows as they lie, all heads against
# ONE row a key, and no K or V per head ever exists. (Expanding a chunk's
# context to the published head widths for the flash kernel lost at every
# context measured: PERF.md section 6, PR 42.)
# ---------------------------------------------------------------------------

def _latent_in(x: jax.Array, lp: Dict[str, Any], l, cfg: LlamaConfig,
               rope, pools, w_page, w_off, mode, hold, w_pages=None):
    """:func:`layer_in` of a model with latent attention. The new rows: the
    rotated shared key [B*T, 1, rope -> a lane tile] into the K pool, the
    normed compressed vector [B*T, 1, Rkv] into the V pool. -> (q, pools,
    None) with q = (q_pe [B,T,Hq,K-pool row], q^ [B,T,Hq,Rkv]). The model's
    two constant scales (``latent_q_scale``, ``latent_kv_scale``) ride the
    two inner norms' weights, in the norm's float32: the scaled compressed
    vector is what the cache keeps, the shared rotary key is not scaled."""
    Rkv = cfg.kv_lora_rank
    h = _normed(x, lp["ln1"][l], cfg)
    cq = rms_norm(jnp.einsum("btd,dr->btr", h, lp["w_dq"][l]),
                  _scaled(lp["ln_dq"][l], cfg.latent_q_scale), cfg.rms_eps)
    q_nope, q_pe = (
        jnp.einsum("btr,kr->btk", cq, lp[w][l]).reshape(
            *cq.shape[:2], cfg.num_heads, -1) for w in ("w_uq", "w_uqr"))
    ckv = jnp.einsum("btd,dr->btr", h, lp["w_dkv"][l])
    c = rms_norm(ckv[..., :Rkv], _scaled(lp["ln_kv"][l], cfg.latent_kv_scale),
                 cfg.rms_eps)
    q_pe = apply_rope(q_pe, *rope)
    k_pe = apply_rope(ckv[..., None, Rkv:], *rope)              # [B,T,1,rope]
    k_pool, v_pool = pools
    pad = ((0, 0),) * 3 + ((0, k_pool.shape[-1] - k_pe.shape[-1]),)
    rows = [jnp.pad(k_pe, pad).reshape(-1, 1, k_pool.shape[-1]),
            c.reshape(-1, 1, Rkv)]
    if hold is None:
        k_pool, v_pool = _write_rows((k_pool, v_pool), l, w_page, w_off,
                                     rows, mode, w_pages)
    else:
        hold.extend(rows)
    q_lat = jnp.einsum("bthn,hnr->bthr", q_nope, lp["w_uk"][l])
    return (jnp.pad(q_pe, pad), q_lat), (k_pool, v_pool), None


def _scaled(w: jax.Array, scale: Optional[float]) -> jax.Array:
    """A norm's weight x a model's constant scale (None: as it is)."""
    return w if scale is None else w * scale


def latent_attend(cfg: LlamaConfig, q, k_ctx: jax.Array, c_ctx: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """The absorbed form over a gathered context, dense: q = (q_pe, q^) of
    :func:`_latent_in`, ``k_ctx`` [B,S,1,K-pool row] the shared keys and
    ``c_ctx`` [B,S,1,Rkv] the compressed vectors as the pools hold them,
    ``mask`` [B,T,S]. -> [B,T,Hq,Rkv], float32 softmax."""
    q_pe, q_lat = q
    s = (jnp.einsum("bthk,bsk->bhts", q_pe, k_ctx[:, :, 0],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthr,bsr->bhts", q_lat, c_ctx[:, :, 0],
                      preferred_element_type=jnp.float32)) * cfg.attn_scale
    w = jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhts,bsr->bthr", w.astype(c_ctx.dtype), c_ctx[:, :, 0])


@scope("attn_out")
def layer_out(x: jax.Array, attn: jax.Array, lp: Dict[str, Any], l,
              cfg: LlamaConfig, mesh=None,
              stats: Optional[Dict[str, Any]] = None,
              inside: Optional[Dict[str, int]] = None,
              ffn: Optional[Tuple[Dict[str, Any], Any]] = None,
              active: Optional[jax.Array] = None,
              branch: Optional[Tuple[Any, List[jax.Array]]] = None,
              normed: Optional[List[jax.Array]] = None
              ) -> jax.Array:
    """The layer after attention: out-projection of ``attn`` [B,T,Hq,Dh] and
    residual (Gemma2 norms the branch output first), then the feed-forward
    (``active``, ``branch``: :func:`_ffn_block`'s). A PARALLEL block
    (``normed``: the list :func:`layer_in` left the normed stream in) adds
    nothing here: the feed-forward reads that same normed stream, and the
    two branches land on ``x`` in ONE add (:func:`_ffn_block` ``par``).

    ``inside``: a caller that is ALREADY inside manual SPMD (``forward_pp``'s
    stage body; shard_maps do not nest) names the mesh axes it is inside of
    with their sizes (> 1); ``lp`` is then this shard's slice, the two
    contractions over the sharded dimension leave partial sums, and they are
    reduced here. Without it the reductions are GSPMD's. ``ffn``: the
    feed-forward's (stack, index) where it is not (``lp``, ``l``)
    (:func:`layer_stacks`). Latent attention hands over the heads' weighted
    sums of compressed vectors [B,T,Hq,Rkv], and their values are expanded
    here (``w_uv``)."""
    if cfg.has_latent:
        attn = jnp.einsum("bthr,hrv->bthv", attn, lp["w_uv"][l])
    o = jnp.einsum("bthk,hkd->btd", attn, lp["wo"][l])
    if inside and AXIS_TP in inside:
        o = jax.lax.psum(o, AXIS_TP)
    if cfg.sandwich_norms:
        o = rms_norm(o, lp["ln1_post"][l], cfg.rms_eps, cfg.norm_offset)
    if cfg.parallel_block:
        if not normed:
            raise ValueError("a parallel block's feed-forward reads the "
                             "normed stream its attention read (layer_in "
                             "normed=)")
        return _ffn_block(x, *(ffn or (lp, l)), cfg, mesh=mesh, stats=stats,
                          inside=inside, active=active,
                          par=(normed.pop(), o))
    return _ffn_block(_residual(x, o, cfg), *(ffn or (lp, l)), cfg,
                      mesh=mesh, stats=stats, inside=inside, active=active,
                      branch=branch)


def _residual(x: jax.Array, branch: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """``x`` + the branch's output, damped where the model says so
    (``residual_multiplier``)."""
    if cfg.residual_multiplier is not None:
        # (in the stream's float32: LlamaConfig.stream_dtype)
        branch = branch.astype(x.dtype) * cfg.residual_multiplier
    return x + branch


@scope("ffn")
def _ffn_block(x: jax.Array, lp: Dict[str, Any], l, cfg: LlamaConfig,
               mesh=None, stats: Optional[Dict[str, Any]] = None,
               inside: Optional[Dict[str, int]] = None,
               active: Optional[jax.Array] = None,
               branch: Optional[Tuple[Any, List[jax.Array]]] = None,
               par: Optional[Tuple[jax.Array, jax.Array]] = None
               ) -> jax.Array:
    """Pre-norm FFN (dense or MoE) + residual; Gemma2 adds a post-norm on
    the branch output (sandwich norms). A routed layer adds its experts hit
    to ``stats["experts_hit"]`` (see :func:`forward`). ``inside`` as
    :func:`layer_out`'s. ``active`` [B] bool (a decode step: the rows the
    dispatch serves): a routed layer dispatches the busy rows' assignments
    alone and counts theirs alone (``moe.moe_ffn``), and adds 1 to
    ``stats["sorted"]`` if it was dispatched sorted.

    ``branch`` (:func:`_branch_of`) = ((routed stack, index) or None, the
    forward's list) for a model whose routed experts are a branch ACROSS two
    sublayers
    (``cfg.shortcut_moe``): the first of a pair computes them from the
    normed stream its dense feed-forward reads and leaves the result in the
    list; the second adds it to the stream after its own feed-forward's
    residual.

    ``par`` = (the layer's normed stream, its attention's output) of a
    PARALLEL block: no norm here (the layer has one, and ``lp`` no ``ln2``),
    the feed-forward reads what the projections read, and ``x`` takes both
    branches in one add."""
    h2 = _normed(x, lp["ln2"][l], cfg) if par is None else par[0]
    if branch is not None and branch[0] is not None:
        branch[1].append(_routed_ffn(h2, *branch[0], cfg, mesh, stats,
                                     active))
    routed = cfg.num_experts and "wr" in lp   # a per-kind model's dense layers
    if routed and inside is not None:
        # router replicated, experts sharded over ep and their width over tp
        # where it divides (param_specs): dense dispatch of this shard's
        # experts, one psum over both axes
        from .moe import moe_ffn_in_stage
        axes = [AXIS_EP] if AXIS_EP in inside else []
        if AXIS_TP in inside and cfg.expert_width % inside[AXIS_TP] == 0:
            axes.append(AXIS_TP)
        out = moe_ffn_in_stage(h2, lp["wr"][l], lp["wg"][l], lp["wu"][l],
                               lp["wd"][l], cfg.experts_per_token,
                               ep=inside.get(AXIS_EP, 1),
                               psum_axes=tuple(axes))
    elif routed:
        out = _routed_ffn(h2, lp, l, cfg, mesh, stats, active)
    else:
        g = jnp.einsum("btd,df->btf", h2, lp["wg"][l])
        u = jnp.einsum("btd,df->btf", h2, lp["wu"][l])
        out = jnp.einsum("btf,fd->btd", _act(cfg)(g) * u, lp["wd"][l])
        if inside and AXIS_TP in inside:
            out = jax.lax.psum(out, AXIS_TP)
    if cfg.sandwich_norms:
        out = rms_norm(out, lp["ln2_post"][l], cfg.rms_eps, cfg.norm_offset)
    x = _residual(x, out if par is None else par[1] + out, cfg)
    if branch is not None and branch[0] is None:
        x = _residual(x, branch[1].pop(), cfg)
    return x


def _routed_ffn(h2: jax.Array, lp: Dict[str, Any], l, cfg: LlamaConfig,
                mesh, stats: Optional[Dict[str, Any]],
                active: Optional[jax.Array]) -> jax.Array:
    """The routed experts of ``lp`` at ``l`` on the normed stream ``h2``, by
    the model's router law (``moe.moe_ffn``); counts into ``stats`` as
    :func:`_ffn_block` says."""
    from .moe import moe_ffn, shared_ffn
    share = bool(cfg.router_experts or cfg.zero_experts)
    law = {}
    if cfg.router != "softmax" or cfg.router_experts:
        law = {"router": cfg.router,
               "first": cfg.expert_first if share else None,
               "bias": lp["rbias"][l] if "rbias" in lp else None}
    if cfg.router_groups:
        law.update(groups=cfg.router_groups, scaling=cfg.routed_scaling)
    if cfg.router_norm_eps:
        law.update(norm_eps=cfg.router_norm_eps,
                   scaling=cfg.routed_scaling)
    if cfg.router == "softmax_bias":
        law.update(scaling=cfg.routed_scaling, zero=cfg.zero_experts)
    shared = (tuple(lp[k][l] for k in ("ws_g", "ws_u", "ws_d"))
              if cfg.shared_experts else None)
    if shared and not cfg.shared_average:
        law["shared"] = shared
    out, hit, chosen = moe_ffn(h2, lp["wr"][l], lp["wg"], lp["wu"], lp["wd"],
                       cfg.experts_per_token, mesh=mesh, layer=l,
                       active=active, stats=stats, **law)
    if shared and cfg.shared_average:
        # outside the routed experts' scope, so that a trace tells router +
        # routed experts (``dynamo.moe_ffn``) from the shared experts
        # (``dynamo.ffn``, ``moe_shared`` inside it)
        with _inner("moe_shared"):
            out = out + shared_ffn(h2, *shared,
                                   scale=1.0 / cfg.shared_experts)
    if stats is not None:
        if share:
            # (experts hit, assignments to held experts) of this call
            hit, held = hit
            stats["held"] = stats.get("held", 0) + held
        stats["experts_hit"] = stats.get("experts_hit", 0) + hit
        if "chosen" in stats:
            stats["chosen"].append(chosen)
    return out


# ---------------------------------------------------------------------------
# The state-space mixer (Mamba-2, ``layer_kinds[l] == 2``)
#
# In place of attention such a layer keeps, per LANE and not per token, a
# recurrent state H [heads, head_dim, state] float32 and the last ``ssm_conv
# - 1`` inputs of its depthwise causal convolution. For the normed input v_t:
# [z | c | d] = W_in v_t; c' = silu(b + sum_k w[k] * c_{t-K+1+k}); c' = [X |
# B | C]; per head h: dt = softplus(d[h] + dt_bias[h]), a = exp(-dt
# exp(A_log[h])), H <- a H + dt X[h] (x) B, y[h] = H C + D[h] X[h]; out =
# W_out RMSNorm_w(y * silu(z)). Two forms that agree: a CHUNK form (state
# in, T tokens, state out: the structured-matrix form over the whole chunk,
# T <= a prefill chunk) and a one-TOKEN form for decode. A position that is
# not valid (padding behind a lane's tokens) has dt = 0: a = 1 and nothing
# is added, so state and convolution tail are what they were, bit for bit.
# The pools are [state layers, lanes, ...]; a run of such layers between two
# attention layers is ONE ``lax.scan`` whose carry holds both pools, indexed
# by layer inside it and updated in place (a program holds a handful of
# layer bodies, whatever the depth: set-up time).
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssm_conv(window: jax.Array, lp: Dict[str, Any], l, T: int) -> jax.Array:
    """Depthwise causal convolution + silu over ``window`` [B, K - 1 + T,
    Cd] (the tail, then the new inputs): [B, T, Cd] float32."""
    w = lp["conv_w"][l].astype(jnp.float32)                       # [K, Cd]
    acc = sum(w[k] * window[:, k:k + T].astype(jnp.float32)
              for k in range(w.shape[0]))
    if "conv_b" in lp:
        acc = acc + lp["conv_b"][l].astype(jnp.float32)
    return jax.nn.silu(acc)


def _ssm_split(xbc: jax.Array, d: jax.Array, lp: Dict[str, Any], l,
               cfg: LlamaConfig):
    """-> (X [..., H, P], B [..., N], C [..., N], dt [..., H], A [H] < 0)."""
    I, N, H = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    X = xbc[..., :I].reshape(*xbc.shape[:-1], H, cfg.ssm_head_dim)
    dt = jax.nn.softplus(d.astype(jnp.float32) + lp["dt_bias"][l])
    return (X, xbc[..., I:I + N], xbc[..., I + N:], dt,
            -jnp.exp(lp["A_log"][l]))


def ssm_chunk(c: jax.Array, d: jax.Array, lp: Dict[str, Any], l,
              cfg: LlamaConfig, state: jax.Array, tail: jax.Array,
              n_valid: jax.Array):
    """The chunk form. ``c`` [B,T,Cd], ``d`` [B,T,H]: the projected
    convolution inputs and step logits; ``state`` [B,H,P,N] float32 and
    ``tail`` [B,K-1,Cd]: what the lane's previous chunk left (zeros at a
    sequence's start); ``n_valid`` [B]: the lane's real tokens, the first of
    the chunk. -> (y [B,T,H,P] float32, state, tail)."""
    B, T, _ = c.shape
    K1 = tail.shape[1]
    window = jnp.concatenate([tail, c.astype(tail.dtype)], axis=1)
    X, Bm, Cm, dt, A = _ssm_split(_ssm_conv(window, lp, l, T), d, lp, l, cfg)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]             # [B,T]
    dt = jnp.where(valid[..., None], dt, 0.0)
    cum = jnp.cumsum(dt * A, axis=1)                              # [B,T,H] <= 0
    # y_t = sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t . B_s) X_s
    #       + exp(cum_t) H_0 C_t + D X_t
    G = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=_HIGHEST)
    diff = cum[:, :, None, :] - cum[:, None, :, :]                # [B,T,S,H]
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    W = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
    W = W * (G[..., None] * dt[:, None, :, :])
    y = jnp.einsum("btsh,bshp->bthp", W, X, precision=_HIGHEST)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bhpn,btn->bthp", state, Cm, precision=_HIGHEST)
    y = y + lp["D"][l][:, None] * X
    # H_T = exp(cum_T) H_0 + sum_s exp(cum_T - cum_s) dt_s X_s (x) B_s
    last = cum[:, -1]                                             # [B,H]
    w_in = jnp.exp(last[:, None, :] - cum) * dt                   # [B,S,H]
    state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
        "bsh,bshp,bsn->bhpn", w_in, X, Bm, precision=_HIGHEST)
    # the last K - 1 real inputs: window rows n_valid .. n_valid + K - 2
    rows = n_valid[:, None] + jnp.arange(K1)[None, :]
    tail = jnp.take_along_axis(window, rows[..., None], axis=1)
    return y, state, tail


def ssm_step(c: jax.Array, d: jax.Array, lp: Dict[str, Any], l,
             cfg: LlamaConfig, state: jax.Array, tail: jax.Array,
             active: jax.Array, kernel=None):
    """The one-token form. ``c`` [B,Cd], ``d`` [B,H]; ``state`` [B,H,P,N],
    ``tail`` [B,K-1,Cd]; a lane that is not ``active`` [B] keeps both bit
    for bit (its y is computed and discarded with the lane's token).
    -> (y [B,H,P] float32, state, tail).

    With ``kernel`` (:func:`forward_decode`'s ``state_kernel``) ``state`` is
    the WHOLE pool [layers,B,H,P,N] and comes back as such: update and
    read-out of layer ``l`` are the kernel's one pass over the served lanes'
    blocks, in place (``ops.state.state_step``); a lane that is not active
    keeps its state because nothing touches it, and its y is 0."""
    window = jnp.concatenate([tail, c.astype(tail.dtype)[:, None]], axis=1)
    X, Bm, Cm, dt, A = _ssm_split(_ssm_conv(window, lp, l, 1)[:, 0], d, lp,
                                  l, cfg)
    if kernel is None:
        new = (jnp.exp(dt * A)[..., None, None] * state
               + (dt[..., None] * X)[..., None] * Bm[:, None, None, :])
        y = (jnp.sum(new * Cm[:, None, None, :], axis=-1)
             + lp["D"][l][:, None] * X)
        state = jnp.where(active[:, None, None, None], new, state)
    else:
        y, state = kernel(state, l, jnp.exp(dt * A), dt[..., None] * X, Bm,
                          Cm)
        # (the kernel writes no row of y for a lane it does not serve)
        y = jnp.where(active[:, None, None], y + lp["D"][l][:, None] * X,
                      0.0)
    tail = jnp.where(active[:, None, None], window[:, 1:], tail)
    return y, state, tail


# ---------------------------------------------------------------------------
# The gated short convolution (LFM2, ``layer_kinds[l] == 3``)
#
# For the normed input h_t: [B | C | z] = W_in h_t; u_t = B * z; c_t = sum_k
# w[k] * u_{t-K+1+k} (depthwise, causal, K = ``conv_cache`` taps, u before a
# sequence's start = 0); y_t = C * c_t; out = W_out y_t. No activation: both
# gates are plain products. Per LANE a layer keeps the last K - 1 rows of u
# in the model's dtype (u is rounded to it once, before the taps, in a chunk
# and in a step alike: the tail IS the window's earlier rows) and nothing
# else: the ``state`` cache kind with a tail alone, one pool [conv layers,
# lanes, (K - 1) x D], read and written inside :func:`_state_run`'s scan as
# the state-space mixer's two are.
# ---------------------------------------------------------------------------

def conv_mix(bcz: jax.Array, lp: Dict[str, Any], l, tail: jax.Array,
             gate: jax.Array, decode: bool):
    """``bcz`` [B,T,3 D] the projected input, ``tail`` [B,K-1,D] what the
    lane's previous tokens left (zeros at a sequence's start). ``gate``: a
    chunk's ``n_valid`` [B] (the row's real tokens, the first of the chunk:
    the new tail is the K - 1 rows before position ``n_valid``) or decode's
    ``active`` [B] (a lane that is not keeps its tail bit for bit).
    -> (y [B,T,D] in the input's dtype, tail)."""
    T, D = bcz.shape[1], tail.shape[-1]
    Bg, Cg, z = (bcz[..., i * D:(i + 1) * D].astype(jnp.float32)
                 for i in range(3))
    u = (Bg * z).astype(tail.dtype)
    window = jnp.concatenate([tail, u], axis=1)               # [B,K-1+T,D]
    w = lp["conv_w"][l].astype(jnp.float32)                   # [K, D]
    c = sum(w[k] * window[:, k:k + T].astype(jnp.float32)
            for k in range(w.shape[0]))
    y = (Cg * c).astype(bcz.dtype)
    if decode:
        new = jnp.where(gate[:, None, None], window[:, 1:], tail)
    else:
        rows = gate[:, None] + jnp.arange(tail.shape[1])[None, :]
        new = jnp.take_along_axis(window, rows[..., None], axis=1)
    return y, new


def _state_run(x: jax.Array, params: Dict[str, Any], cfg: LlamaConfig,
               l0: int, n: int, pools: Tuple[jax.Array, ...],
               lanes: Optional[jax.Array], reset: Optional[jax.Array],
               gate: jax.Array, mesh=None,
               stats: Optional[Dict[str, Any]] = None, state_kernel=None):
    """Layers ``l0 .. l0 + n - 1``, all of ONE mixer kind that keeps a state
    a lane (state-space or gated short convolution) and ONE feed-forward
    kind, as one scan over x [B,T,D]: mixer and feed-forward of each, the
    layer's slice of the state pools read and written inside the body.
    ``pools``: (state_pool, conv_pool) of a state-space model, (tail_pool,)
    of a gated short convolution. ``lanes`` [B]: the pool lane of each row
    (a prefill chunk; a row past the pool is dropped) or None: row b IS lane
    b (decode, T == 1). ``reset`` [B] bool: the row's sequence starts here,
    from a zero state. ``gate``: the chunk's ``n_valid`` [B], or decode's
    ``active`` [B], which a routed feed-forward takes too. Its experts
    hit, held assignments, sorted calls (decode) and chosen ids leave the
    scan as its outputs and are added to ``stats`` as :func:`_ffn_block`
    adds them outside a scan. ``state_kernel``: :func:`forward_decode`'s,
    which a state-space layer's decode step hands to :func:`ssm_step`.
    -> (x, pools)."""
    st = params[STACKS]
    conv, routed = cfg.layer_kinds[l0] == 3, cfg.layer_routed(l0)
    mp = st["conv" if conv else "mamba"]
    fp = st["routed" if routed else "dense"]
    m0 = sum(k == cfg.layer_kinds[l0] for k in cfg.layer_kinds[:l0])
    f0 = sum(cfg.layer_routed(i) == routed for i in range(l0))
    I, Cd = cfg.ssm_inner, cfg.ssm_conv_dim
    decode = lanes is None
    B, D = x.shape[0], cfg.hidden_size
    # what a routed layer of the run adds to ``stats``, as scan outputs
    want = []
    if stats is not None and routed:
        want = (["experts_hit"] + ["held"] * bool(cfg.router_experts)
                + ["sorted"] * decode + ["chosen"] * ("chosen" in stats))

    def conv_body(x, c_pool, lm):
        with scope("ssm_in"):
            h = _normed(x, mp["ln1"][lm], cfg)
            bcz = jnp.einsum("btd,de->bte", h, mp["w_in"][lm])
        # the scope holds the recurrence: the lane's tail in, both gates,
        # the taps, the tail out; the two projections are outside it
        with scope("ssm_step" if decode else "ssm_scan"):
            if decode:
                tail = jax.lax.dynamic_index_in_dim(
                    c_pool, lm, keepdims=False).reshape(B, -1, D)
            else:
                tail = c_pool.at[lm, lanes].get(mode="clip").reshape(B, -1, D)
                tail = jnp.where(reset[:, None, None], jnp.zeros_like(tail),
                                 tail)
            y, tail = conv_mix(bcz, mp, lm, tail, gate, decode)
            if decode:
                c_pool = jax.lax.dynamic_update_index_in_dim(
                    c_pool, tail.reshape(B, -1), lm, 0)
            else:
                c_pool = c_pool.at[lm, lanes].set(tail.reshape(B, -1),
                                                  mode="drop")
        with scope("ssm_out"):
            o = jnp.einsum("bti,id->btd", y, mp["w_out"][lm])
            x = _residual(x, o, cfg)
        return x, (c_pool,)

    def ssm_body(x, s_pool, c_pool, lm):
        with scope("ssm_in"):
            h = _normed(x, mp["ln1"][lm], cfg)
            zc = jnp.einsum("btd,de->bte", h, mp["w_in"][lm])
            z, c = zc[..., :I], zc[..., I:]
            d = jnp.einsum("btd,dh->bth", h, mp["w_dt"][lm])
        # the scope holds what the recurrence is: the layer's slice of both
        # pools in, convolution, state update, read-out and gated norm, the
        # slice out; the two projections are outside it
        with scope("ssm_step" if decode else "ssm_scan"):
            # (a lane's convolution tail is ONE flat pool row: [K - 1, Cd]
            # rows made XLA re-lay the whole pool twice a chunk)
            if decode:
                tail = jax.lax.dynamic_index_in_dim(
                    c_pool, lm, keepdims=False).reshape(B, -1, Cd)
                if state_kernel is None:
                    state = jax.lax.dynamic_index_in_dim(s_pool, lm,
                                                         keepdims=False)
                    y, state, tail = ssm_step(c[:, 0], d[:, 0], mp, lm, cfg,
                                              state, tail, gate)
                    s_pool = jax.lax.dynamic_update_index_in_dim(
                        s_pool, state, lm, 0)
                else:
                    # the kernel reads and writes the pool in place, the
                    # served lanes' blocks of layer ``lm`` alone
                    y, s_pool, tail = ssm_step(c[:, 0], d[:, 0], mp, lm, cfg,
                                               s_pool, tail, gate,
                                               state_kernel)
                y = y[:, None]
                c_pool = jax.lax.dynamic_update_index_in_dim(
                    c_pool, tail.reshape(B, -1), lm, 0)
            else:
                state = s_pool.at[lm, lanes].get(mode="clip")
                tail = c_pool.at[lm, lanes].get(mode="clip").reshape(
                    B, -1, Cd)
                state = jnp.where(reset[:, None, None, None], 0.0, state)
                tail = jnp.where(reset[:, None, None], jnp.zeros_like(tail),
                                 tail)
                y, state, tail = ssm_chunk(c, d, mp, lm, cfg, state, tail,
                                           gate)
                s_pool = s_pool.at[lm, lanes].set(state, mode="drop")
                c_pool = c_pool.at[lm, lanes].set(tail.reshape(B, -1),
                                                  mode="drop")
            g = y.reshape(*y.shape[:2], I) * jax.nn.silu(
                z.astype(jnp.float32))
            g = rms_norm(g, mp["norm"][lm], cfg.rms_eps).astype(cfg.dtype)
        with scope("ssm_out"):
            o = jnp.einsum("bti,id->btd", g, mp["w_out"][lm])
            x = _residual(x, o, cfg)
        return x, (s_pool, c_pool)

    def body(carry, i):
        x, *pools = carry
        with scope("ssm_in"):
            lm, lf = m0 + i, f0 + i
        x, pools = (conv_body if conv else ssm_body)(x, *pools, lm)
        seen: Optional[Dict[str, Any]] = None
        if want:
            seen = {"chosen": []} if "chosen" in want else {}
        x = _ffn_block(x, fp, lf, cfg, mesh=mesh, stats=seen,
                       active=gate if decode else None)
        out = None
        if want:
            out = tuple(seen["chosen"][0] if c == "chosen"
                        else jnp.asarray(seen[c], jnp.int32) for c in want)
        return (x, *pools), out

    with scope("ssm_in"):
        layers = jnp.arange(n)
    (x, *pools), out = jax.lax.scan(body, (x, *pools), layers)
    for c, v in zip(want, out or ()):
        if c == "chosen":
            stats["chosen"].extend(v[i] for i in range(n))
        else:
            with scope("moe_ffn"):
                stats[c] = stats.get(c, 0) + jnp.sum(v)
    return x, tuple(pools)


def _branch_of(params: Dict[str, Any], cfg: LlamaConfig, l: int,
               carried: List[jax.Array]):
    """:func:`layer_out`'s ``branch`` of layer ``l``: ((routed stack, index
    in it) where the layer computes a routed branch beside its dense
    feed-forward, None where it adds the one before it; the forward's
    list), or None for a model without a branch across sublayers."""
    if not cfg.shortcut_moe:
        return None
    at = (params[STACKS]["routed"], l // 2) if cfg.layer_branch(l) else None
    return at, carried


def _segments(cfg: LlamaConfig):
    """-> [(first layer, layers)]: a run of layers that keep a state a lane,
    of one mixer kind and one feed-forward kind, is one segment (one scan),
    every other layer a segment of its own."""
    out, l = [], 0
    while l < cfg.num_layers:
        n = 1
        if cfg.layer_state(l):
            same = (cfg.layer_kinds[l], cfg.layer_routed(l))
            while l + n < cfg.num_layers and (
                    cfg.layer_kinds[l + n], cfg.layer_routed(l + n)) == same:
                n += 1
        out.append((l, n))
        l += n
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Dict[str, Any], cfg: LlamaConfig,
            tokens: jax.Array,           # [B, T] int32 (decode: T=1)
            positions: jax.Array,        # [B, T] int32 position of each token
            k_pool: jax.Array,           # [L, Hkv, n_pages, page, Dh] KV pool
            v_pool: jax.Array,
            write_idx: jax.Array,        # [B, T] int32 pool token-slot per new token
            read_idx: Optional[jax.Array],  # [B, S] int32 pool token-slots to attend over
            read_pos: jax.Array,         # [B, S] int32 position of each read slot
            read_valid: jax.Array,       # [B, S] bool slot holds a real token
            attn_impl: str = "xla",      # "xla" | "flash" Pallas | "ring" sp
            mesh=None,                   # required for attn_impl="ring"
            logits_idx: Optional[jax.Array] = None,  # [B] per-lane position
            embed_override: Optional[Tuple[jax.Array, jax.Array]] = None,
            attn_spans: Optional[Tuple[jax.Array, jax.Array]] = None,
            read_pages: Optional[jax.Array] = None,  # [B, S // page] int32
            i_pool: Optional[jax.Array] = None,  # index keys (has_indexer)
            stats: Optional[Dict[str, Any]] = None,
            win: Optional[Tuple[jax.Array, ...]] = None,  # window cache
            ssm: Optional[Tuple[jax.Array, ...]] = None,  # state pools
            write_pages: Optional[jax.Array] = None,  # [B, ceil(T / page)]
            ) -> Tuple[jax.Array, ...]:
    """One forward pass over a token chunk against the paged KV pool.

    The pool is head-major ([L, Hkv, n_pages, page, Dh], read and written
    as stored: see "KV pool access" above); token-slot indices (page_id *
    page_size + offset) address it. The new chunk's K/V are scattered into
    the pool at ``write_idx`` first; attention then gathers ``read_idx``
    (which must cover the chunk itself) and masks causally by position:
    token at position p attends to slots with ``read_pos <= p``. Works for
    prefill chunks and single-token decode alike.

    ``read_pages``: a caller whose read slots are whole pages in order —
    ``read_idx[b, t] == read_pages[b, t // page] * page + t % page``
    wherever ``read_valid`` — passes the page ids, and the context is
    gathered a page at a time instead of a row at a time (``read_idx`` is
    then not read and may be None; slots that are not valid may hold
    anything finite).

    ``write_pages``: a caller whose every lane's chunk starts on a page's
    first slot — ``write_idx[b, t] == write_pages[b, t // page] * page + t %
    page`` for the lane's real tokens, 0 (scratch page 0) past them — passes
    the page ids, and the new rows are written a page run at a time instead
    of a row at a time (:func:`kv_write_pages`; every pool of the model, a
    window cache's from its ``w_write_idx`` likewise). A run holds padded
    tokens' rows too: they land in scratch page 0 or past the lane's last
    token in its own unsealed page, slots that are not valid to any read.

    Returns (logits [B, T, vocab] fp32, k_pool, v_pool). With ``logits_idx``
    ([B] int32), the LM head runs only on each lane's hidden state at that
    chunk position and logits are [B, 1, vocab] — the prefill fast path,
    which never materializes the [B, T, vocab] tensor.

    A model with an indexer (``cfg.has_indexer``) also takes and returns
    ``i_pool``, the index keys on the same pages (:func:`index_pool_shape`),
    as a fourth result; it reads its context by page (``read_pages``). Each
    query attends to its ``index_topk`` best visible keys; where the context
    bucket S is no longer than ``index_topk`` that is every visible key by
    construction, so nothing is scored there (the index keys are written all
    the same). ``stats``, a dict, receives ``experts_hit`` (int32 scalar:
    experts with at least one row, summed over layers) for a routed model;
    a caller that put empty lists under ``"keep"`` / ``"chosen"`` gets each
    layer's keep mask ([B,T,S] bool, None where nothing was scored) and
    chosen expert ids ([B,T,K]) appended: what the tests compare with the
    reference's own.

    A per-kind model (``cfg.per_kind``) keeps its FULL layers' K/V in
    ``k_pool`` / ``v_pool`` ([full layers, Hkv, ...]), addressed as above,
    and its WINDOW layers' in a second pair of pools of their own pages:
    ``win`` = (wk_pool, wv_pool [window layers, window Hkv, pages, page, ·],
    w_write_idx [B, T] token slots of the new tokens there, w_read_pages
    [B, Pw] the pages that hold each lane's last ``sliding_window - 1``
    tokens and the chunk, in order, w_read_pos [B, Pw * page] the position
    of each of their slots, w_read_valid likewise). The two pools come back
    behind the others. Context is read by page for both kinds.

    A model with state-space layers (``cfg.has_state``) keeps its ATTENTION
    layers' K/V in ``k_pool`` / ``v_pool`` ([attention layers, ...], folded
    by ``cfg.kv_fold``) and takes ``ssm`` = (state_pool [state layers,
    lanes, H, P, N] float32, conv_pool [state layers, lanes, K - 1, Cd],
    lanes [B] the pool lane of each row (one past the pool: a padded row,
    which writes nothing), reset [B] bool the row's sequence starts with
    this chunk, n_valid [B] the row's real tokens): a row's chunk starts
    from the state its lane holds (zeros under ``reset``) and leaves the
    state after its last REAL token there. Both pools come back last.

    A model with latent attention (``cfg.has_latent``) keeps the rotated
    shared key in ``k_pool`` ([L, 1, pages, page, a lane tile]) and the
    compressed vector in ``v_pool`` ([L, 1, pages, page, kv_lora_rank]),
    addressed as above.

    Multimodal (Gemma3 VLM, xla attention only):

    - ``embed_override`` = (vals [B,T,D], mask [B,T] bool) replaces the
      masked positions' embeddings AFTER the embed scale — projected image
      soft tokens are injected raw, exactly HF's masked_scatter
      (modeling_gemma3.py:908-914).
    - ``attn_spans`` = (q_span [B,T], read_span [B,S]) int32 image-group
      ids (0 = text): tokens of the SAME image attend bidirectionally —
      the or-mask applies to full and sliding layers alike
      (modeling_gemma3.py:936-953).
    """
    fold = cfg.kv_fold
    page = k_pool.shape[3] * fold
    with scope("embed"):
        x = _embed(params, cfg, tokens)  # [B,T,D] bf16
        if embed_override is not None:
            ov_vals, ov_mask = embed_override
            x = jnp.where(ov_mask[..., None], ov_vals.astype(x.dtype), x)
    with scope("attn_in"):
        rope, rope_sl = rope_pair(cfg, positions)
    wp = wo = wwp = wwo = ww_pages = None
    if write_pages is None:
        with scope("kv_write"):
            flat_w = write_idx.reshape(-1)
            wp, wo = flat_w // page, flat_w % page
    if read_pages is None:
        with scope("attn"):
            rp, ro = read_idx // page, read_idx % page
    sliding_mask = None
    if attn_impl == "ring":
        from ..parallel.mesh import AXIS_TP as _TP
        from ..parallel.ring_attention import ring_attention
        head_axis = _TP if (
            mesh is not None and _TP in mesh.axis_names
            and mesh.shape[_TP] > 1
            and cfg.num_heads % mesh.shape[_TP] == 0
            and cfg.num_kv_heads % mesh.shape[_TP] == 0) else None
    elif attn_impl == "flash":
        tp_sz = _tp_size(mesh)
        from ..ops.attention import flash_attention as _flash
        _flash_cache: Dict[Optional[int], Any] = {}

        def flash_for(layer: int):
            """Kernel variant for this layer (softcap/scale always, window
            on sliding layers) — window is a static kernel param, so the
            two layer classes get two compiled variants, built once."""
            w = cfg.sliding_window if cfg.layer_sliding(layer) else None
            if w not in _flash_cache:
                fn = partial(
                    _flash, scale=cfg.attn_scale,
                    softcap=cfg.attn_logit_softcap, window=w,
                    interpret=_kernel_interpret(mesh))
                if tp_sz > 1:
                    # per-shard flash kernel: heads sharded over tp, kv
                    # heads when divisible (replicated otherwise);
                    # sequence dims replicated
                    kv_spec = (P(None, None, AXIS_TP, None)
                               if cfg.num_kv_heads % tp_sz == 0
                               else P(None, None, None, None))
                    fn = jax.shard_map(
                        fn, mesh=mesh,
                        in_specs=(P(None, None, AXIS_TP, None), kv_spec,
                                  kv_spec, P(None, None), P(None, None),
                                  P(None, None)),
                        out_specs=P(None, None, AXIS_TP, None),
                        check_vma=False)   # pallas_call can't declare vma
                # traced once a variant and program, inlined at every layer
                # (forward_decode's paged_for says why)
                _flash_cache[w] = jax.jit(fn, inline=True)
            return _flash_cache[w]
    else:
        with scope("attn"):
            # causal/validity mask [B,T,S]
            mask = (read_valid[:, None, :]
                    & (read_pos[:, None, :] <= positions[:, :, None]))
            if cfg.sliding_window is not None:
                # Gemma2 even layers: keys within the last `window` positions
                sliding_mask = mask & (
                    read_pos[:, None, :]
                    > positions[:, :, None] - cfg.sliding_window)
            if attn_spans is not None:
                # same-image bidirectional attention ORs into BOTH masks
                q_span, read_span = attn_spans
                bidir = ((q_span[:, :, None] > 0)
                         & (q_span[:, :, None] == read_span[:, None, :])
                         & read_valid[:, None, :])
                mask = mask | bidir
                if cfg.sliding_window is not None:
                    sliding_mask = sliding_mask | bidir
    if attn_spans is not None and attn_impl != "xla":
        raise ValueError(
            "image-span bidirectional attention (Gemma3 VLM) runs on "
            "attn_impl='xla' only; flash/ring kernels take no span inputs")
    _require_xla_attn(cfg, attn_impl)
    pools, index = (k_pool, v_pool), None
    w_pools = s_pools = ()
    if cfg.has_state:
        if ssm is None or read_pages is None or attn_impl == "ring":
            raise ValueError(f"forward: {NO_STATE}")
        *s_pools, s_lanes, s_reset, s_valid = ssm
    if cfg.has_window:
        if win is None or read_pages is None or attn_impl == "ring":
            raise ValueError(f"forward: {NO_SECOND_CACHE}")
        wk_pool, wv_pool, w_write, w_pages, w_pos, w_valid = win
        w_pools = (wk_pool, wv_pool)
        with scope("kv_write"):
            if write_pages is None:
                flat_ww = w_write.reshape(-1)
                wwp, wwo = flat_ww // page, flat_ww % page
            else:
                ww_pages = w_write[:, ::page] // page
        if attn_impl == "xla":
            with scope("attn"):
                w_mask = (w_valid[:, None, :]
                          & (w_pos[:, None, :] <= positions[:, :, None])
                          & (w_pos[:, None, :]
                             > positions[:, :, None] - cfg.sliding_window))
    if cfg.has_indexer and i_pool is not None:
        if read_pages is None:
            raise ValueError("a model with an indexer reads its context by "
                             "page (read_pages)")
        if attn_impl == "ring" or attn_spans is not None:
            raise ValueError("ring attention / image spans take no "
                             "selection (model with an indexer)")
        with scope("attn_in"):
            rope_i = _index_rope(cfg, positions)
            # a context bucket no longer than topk selects every visible key
            # by construction: nothing is scored there (exact, not a shortcut)
            visible = (read_valid[:, None, :]
                       & (read_pos[:, None, :] <= positions[:, :, None])
                       ) if read_pos.shape[1] > cfg.index_topk else None
        pools, index = (k_pool, v_pool, i_pool), (rope_i, read_pages, visible)

    carried: List[jax.Array] = []       # a routed branch between sublayers
    for l, n in _segments(cfg):
        if cfg.layer_state(l):
            x, s_pools = _state_run(x, params, cfg, l, n, s_pools, s_lanes,
                                    s_reset, s_valid, mesh, stats)
            continue
        lp, la, *ffn = layer_stacks(params, cfg, l)
        sl = cfg.layer_sliding(l)
        if cfg.layer_window(l):
            # a window layer of a per-kind model: its own pools, slots and
            # the short context that holds the window
            x, w_pools = _window_layer(
                x, lp, la, ffn, cfg, _rope_of(cfg, l, rope_sl, rope), w_pools,
                wwp, wwo, w_pages, positions, w_pos, w_valid,
                flash_for(l) if attn_impl == "flash" else w_mask,
                mesh, stats, ww_pages)
            continue
        h = [] if cfg.parallel_block else None
        q, pools, keep = layer_in(x, lp, la, cfg,
                                  _rope_of(cfg, l, rope_sl, rope),
                                  pools, wp, wo, index=index, stats=stats,
                                  w_pages=write_pages, normed=h)
        # gather this sequence's context: [B, S, Hkv, Dh]
        with scope("attn"):
            if read_pages is not None:
                k_ctx = kv_pages(pools[0], la, read_pages, fold)
                v_ctx = kv_pages(pools[1], la, read_pages, fold)
            else:
                k_ctx = kv_rows(pools[0], la, rp, ro, fold)
                v_ctx = kv_rows(pools[1], la, rp, ro, fold)
        extra = {} if keep is None else {"keep": keep}
        with _attn_scope(cfg, False):
            if "sink" in lp:
                extra["sink"] = lp["sink"][la]
            if cfg.has_latent:
                # all heads against the lane's cached rows as they lie
                if attn_impl == "ring":
                    raise ValueError("ring attention takes no latent cache")
                attn = (flash_for(l)(q[0], k_ctx, v_ctx, positions, read_pos,
                                     read_valid, latent=q[1])
                        if attn_impl == "flash"
                        else latent_attend(cfg, q, k_ctx, v_ctx, mask))
            elif attn_impl == "flash":
                attn = flash_for(l)(q, k_ctx, v_ctx, positions, read_pos,
                                    read_valid, **extra)
            elif attn_impl == "ring":
                attn = ring_attention(q, k_ctx, v_ctx, positions, read_pos,
                                      read_valid, mesh=mesh,
                                      head_axis=head_axis,
                                      scale=cfg.attn_scale)
            else:
                attn = attend_ctx(cfg, q, k_ctx, v_ctx,
                                  pick(sl, sliding_mask, mask), **extra)
        x = layer_out(x, attn, lp, la, cfg, mesh=mesh, stats=stats,
                      ffn=ffn if cfg.per_kind else None,
                      branch=_branch_of(params, cfg, l, carried), normed=h)

    if logits_idx is not None:
        with scope("head"):
            x = jnp.take_along_axis(
                x, logits_idx[:, None, None].astype(jnp.int32),
                axis=1)                                          # [B,1,D]
    return (_lm_head(x, params, cfg), *pools, *w_pools, *s_pools)


def _window_layer(x, lp, la, ffn, cfg: LlamaConfig, rope, w_pools, wwp, wwo,
                  w_pages, q_pos, w_pos, w_valid, attn, mesh, stats,
                  ww_pages=None):
    """A window layer of a per-kind model over a prefill chunk: the same
    layer body around attention over the window cache's short context.
    ``attn``: the flash kernel of this layer, or the xla path's mask."""
    h = [] if cfg.parallel_block else None
    q, w_pools, _ = layer_in(x, lp, la, cfg, rope, w_pools, wwp, wwo,
                             w_pages=ww_pages, normed=h)
    with scope("attn"):
        k_ctx = kv_pages(w_pools[0], la, w_pages)
        v_ctx = kv_pages(w_pools[1], la, w_pages)
    with _attn_scope(cfg, True):
        sink = {"sink": lp["sink"][la]} if "sink" in lp else {}
        if callable(attn):
            a = attn(q, k_ctx, v_ctx, q_pos, w_pos, w_valid, **sink)
        else:
            a = attend_ctx(cfg, q, k_ctx, v_ctx, attn, **sink)
    return (layer_out(x, a, lp, la, cfg, mesh=mesh, stats=stats, ffn=ffn,
                      normed=h), w_pools)


def forward_pp(params: Dict[str, Any], cfg: LlamaConfig,
               tokens: jax.Array,        # [M, Bm, T] microbatched token ids
               positions: jax.Array,     # [M, Bm, T]
               k_pool: jax.Array,        # [L, Hkv, n_pages, page, Dh]
               v_pool: jax.Array,
               write_idx: jax.Array,     # [M, Bm, T]
               read_idx: jax.Array,      # [M, Bm, S]
               read_pos: jax.Array,      # [M, Bm, S]
               read_valid: jax.Array,    # [M, Bm, S]
               mesh,                     # must carry a pp axis > 1 (or == 1)
               logits_idx: Optional[jax.Array] = None,  # [M, Bm] positions
               attn_impl: str = "xla",   # "xla" gather | "flash" in-stage
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pipeline-parallel forward: the layer stack is split into ``pp``
    contiguous stages (params AND the KV pools sharded on the layer dim —
    each device materializes only its stage's weights and pages, the memory
    win that fits 70B-class models on small slices). Microbatches enter
    stage 0 one per step; activations hop stages with ``ppermute``; KV
    writes land in each stage's local pool shard. Exact vs. the sequential
    :func:`forward` per microbatch.

    Composes with tensor parallelism: when the mesh carries a tp axis > 1,
    heads/ffn shard over tp WITHIN each stage (manual-SPMD psum after the
    wo/wd contractions — the scaling-book megatron recipe), and the KV pool
    shards over (pp: layers, tp: kv heads).

    Returns (logits [M, Bm, T, V] fp32, k_pool, v_pool); with ``logits_idx``
    ([M, Bm] int32), the LM head runs only at each lane's given chunk
    position and logits are [M, Bm, 1, V] (the prefill fast path). Embedding
    and head run outside the stage loop under GSPMD (they are not
    layer-stacked).

    Reference capability: SURVEY §2.5 pipeline parallelism (the reference
    delegates to vLLM `pipeline_parallel_size`); here the model compute
    path itself is pp-partitioned and engine-served (JaxEngineConfig.pp).
    """
    from ..parallel.mesh import AXIS_PP

    M = tokens.shape[0]
    L = cfg.num_layers
    pp = _pp_size(mesh)
    _require_xla_attn(cfg, attn_impl)
    if cfg.has_indexer:
        raise ValueError(f"forward_pp: {NO_INDEX_KEYS}")
    if cfg.has_state:
        raise ValueError(f"forward_pp: {NO_STATE}")
    if cfg.has_latent:
        raise ValueError(f"forward_pp: {NO_LATENT}")
    if cfg.per_kind:
        raise ValueError(f"forward_pp: {NO_SECOND_CACHE}")
    if pp == 1:
        outs = []
        li = None
        for m in range(M):
            if logits_idx is not None:
                li = logits_idx[m]
            lg, k_pool, v_pool = forward(
                params, cfg, tokens[m], positions[m], k_pool, v_pool,
                write_idx[m], read_idx[m], read_pos[m], read_valid[m],
                logits_idx=li)
            outs.append(lg)
        return jnp.stack(outs), k_pool, v_pool
    assert L % pp == 0, f"layers {L} must divide pp {pp}"
    tp_sz = _tp_size(mesh)
    # per-shard GQA grouping must stay integral: with kv heads replicated a
    # shard would silently pair its local q heads with the wrong kv heads
    assert cfg.num_kv_heads % tp_sz == 0, \
        f"pp with tp={tp_sz} needs kv heads divisible (got {cfg.num_kv_heads})"
    # the stage body is manual SPMD over pp AND tp / ep (shard_maps do not
    # nest): each shard computes its head / ffn / expert slice and the layer
    # reduces the partial sums over the axes named here
    inside = {ax: mesh.shape[ax] for ax in (AXIS_EP, AXIS_TP)
              if ax in mesh.axis_names and mesh.shape[ax] > 1}
    page = k_pool.shape[3]
    lp = params["layers"]

    # embed + rope for every microbatch, replicated (cheap, not stacked);
    # rope_tables handles arbitrary leading dims
    x0 = _embed(params, cfg, tokens)                   # [M, Bm, T, D]
    rope, rope_sl = rope_pair(cfg, positions)      # (cos, sin) [M,Bm,T,Dh/2]

    perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
    if attn_impl == "flash":
        # in-stage Pallas flash: already inside manual SPMD, so the kernel
        # runs on this shard's q/kv head slices directly, the per-shard call
        # shape of forward()'s tp path
        from ..ops.attention import flash_attention
        fl = partial(flash_attention, scale=cfg.attn_scale,
                     softcap=cfg.attn_logit_softcap,
                     interpret=_kernel_interpret(mesh))

    def local(lp_loc, kp_loc, vp_loc, x0, rope, rope_sl, positions, widx,
              ridx, rpos, rvalid):
        idx = jax.lax.axis_index(AXIS_PP)
        Lloc = L // pp
        cur = jnp.zeros_like(x0[0])
        outs = jnp.zeros_like(x0)

        def apply_stage(carry, mb, live):
            x, *pools = carry
            (rope_m, rope_sl_m, widx_m, ridx_m, rpos_m, rval_m,
             pos_m) = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, mb, keepdims=False),
                (rope, rope_sl, widx, ridx, rpos, rvalid, positions))
            flat_w = widx_m.reshape(-1)
            # bubble steps write NOTHING: out-of-bounds page index + drop
            # mode gates the scatter itself (a whole-pool select per step
            # would copy the dominant HBM tensor twice each step)
            flat_w = jnp.where(live, flat_w, pools[0].shape[2] * page)
            wp, wo = flat_w // page, flat_w % page
            rp, ro = ridx_m // page, ridx_m % page
            mask = (rval_m[:, None, :]
                    & (rpos_m[:, None, :] <= pos_m[:, :, None]))
            sliding_mask = None
            if cfg.sliding_window is not None:
                sliding_mask = mask & (
                    rpos_m[:, None, :]
                    > pos_m[:, :, None] - cfg.sliding_window)
            for l in range(Lloc):
                # the GLOBAL layer index (traced stage offset + local index)
                # decides sliding vs full: rotary tables and masks are
                # selected, the two compiled kernel variants cond'ed
                sl = cfg.layer_sliding(idx * Lloc + l)
                q, pools, _ = layer_in(x, lp_loc, l, cfg,
                                       pick(sl, rope_sl_m, rope_m), pools,
                                       wp, wo, mode="drop")
                k_ctx = kv_rows(pools[0], l, rp, ro)
                v_ctx = kv_rows(pools[1], l, rp, ro)
                if attn_impl != "flash":
                    attn = attend_ctx(cfg, q, k_ctx, v_ctx,
                                      pick(sl, sliding_mask, mask))
                elif cfg.sliding_window is None:
                    attn = fl(q, k_ctx, v_ctx, pos_m, rpos_m, rval_m)
                else:
                    # window is a static kernel param
                    attn = jax.lax.cond(
                        sl, partial(fl, window=cfg.sliding_window), fl,
                        q, k_ctx, v_ctx, pos_m, rpos_m, rval_m)
                x = layer_out(x, attn, lp_loc, l, cfg, inside=inside)
            return (x, *pools)

        for t in range(M + pp - 1):
            if t < M:
                cur = jnp.where(idx == 0, x0[t], cur)
            # the microbatch THIS stage processes at step t entered at
            # t - idx; clamp keeps the index legal during bubble steps
            # (their results are masked out)
            mb = jnp.clip(t - idx, 0, M - 1)
            live = (t - idx >= 0) & (t - idx < M)
            y, kp_loc, vp_loc = apply_stage((cur, kp_loc, vp_loc), mb, live)
            if t >= pp - 1:
                m_out = t - (pp - 1)
                outs = outs.at[m_out].set(
                    jnp.where(idx == pp - 1, y, outs[m_out]))
            cur = jax.lax.ppermute(y, AXIS_PP, perm_fwd)
        outs = jax.lax.psum(
            jnp.where(jax.lax.axis_index(AXIS_PP) == pp - 1, outs, 0.0),
            AXIS_PP)
        return outs, kp_loc, vp_loc

    # per-layer params carry their tp sharding INTO the stage (manual SPMD
    # over both axes); pools shard (pp: layer dim, tp: kv heads). Axis
    # names the mesh doesn't carry (pp-only meshes) are dropped.
    from ..parallel.mesh import filter_spec
    pspec = param_specs(cfg, tp_sz, pp=pp)["layers"]
    pspec = {k: filter_spec(mesh, pspec[k]) for k in lp}
    pool_spec = filter_spec(mesh, kv_cache_spec(cfg, tp_sz, pp=pp))
    rep = P()
    xs, k_pool, v_pool = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec, pool_spec, pool_spec) + (rep,) * 8,
        out_specs=(rep, pool_spec, pool_spec),
        check_vma=False,
    )(lp, k_pool, v_pool, x0, rope, rope_sl, positions, write_idx, read_idx,
      read_pos, read_valid)

    if logits_idx is not None:
        xs = jnp.take_along_axis(
            xs, logits_idx[:, :, None, None].astype(jnp.int32), axis=2)
    return _lm_head(xs, params, cfg), k_pool, v_pool


def forward_decode_pp(params: Dict[str, Any], cfg: LlamaConfig,
                      tokens: jax.Array,        # [B] int32 last sampled
                      k_pool: jax.Array,        # [L, Hkv, n_pages, page, Dh]
                      v_pool: jax.Array,
                      page_tables: jax.Array,   # [B, P] int32
                      lengths: jax.Array,       # [B] tokens incl. current
                      mesh,
                      microbatches: int = 0,    # 0 => pp stages
                      attn_impl: str = "xla",
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode through the pipeline-parallel stage loop.

    Builds the (write, read) pool addressing on device from the page tables
    — exactly :func:`forward_decode`'s XLA path — then microbatches the B
    lanes through :func:`forward_pp` to keep every stage busy. Returns
    (logits [B, 1, vocab] fp32, k_pool, v_pool).
    """
    B = tokens.shape[0]
    page = k_pool.shape[3]
    M = pp_microbatches(B, microbatches or _pp_size(mesh))
    Bm = B // M

    pos = lengths - 1                                       # [B]
    w_page = jnp.take_along_axis(page_tables, (pos // page)[:, None],
                                 axis=1)[:, 0]
    write_idx = w_page * page + pos % page                  # [B]
    S = page_tables.shape[1] * page
    t = jnp.arange(S, dtype=jnp.int32)
    rp = jnp.take_along_axis(
        page_tables, jnp.broadcast_to((t // page)[None], (B, S)), axis=1)
    read_idx = rp * page + (t % page)[None]                 # [B, S]
    read_pos = jnp.broadcast_to(t[None], (B, S))
    read_valid = t[None] < lengths[:, None]                 # [B, S]

    logits, k_pool, v_pool = forward_pp(
        params, cfg,
        tokens.reshape(M, Bm, 1),
        pos.reshape(M, Bm, 1),
        k_pool, v_pool,
        write_idx.reshape(M, Bm, 1),
        read_idx.reshape(M, Bm, S),
        read_pos.reshape(M, Bm, S),
        read_valid.reshape(M, Bm, S),
        mesh,
        logits_idx=jnp.zeros((M, Bm), jnp.int32),
        attn_impl=attn_impl,
    )
    return logits.reshape(B, 1, -1), k_pool, v_pool


def pallas_tp_ok(cfg: LlamaConfig, tp: int) -> bool:
    """Can the Pallas kernels run per-shard at this tp? Each shard needs an
    integral GQA group: Hq/tp divisible by the per-shard kv head count."""
    if tp <= 1:
        return True
    if cfg.num_heads % tp:
        return False
    hq_shard = cfg.num_heads // tp
    hkv_shard = (cfg.num_kv_heads // tp if cfg.num_kv_heads % tp == 0
                 else cfg.num_kv_heads)     # kv replicated when not divisible
    return hq_shard % hkv_shard == 0


def _kernel_interpret(mesh) -> bool:
    """Pallas kernels compile when the mesh's devices are TPUs and run in
    the interpreter elsewhere (the CPU test rig); without a mesh the
    process's first device decides."""
    from ..utils.jaxenv import on_tpu
    return not on_tpu(None if mesh is None else mesh.devices.flat[0])


def kernel_writes(mesh, attn_impl: str, row: int, fold: int) -> bool:
    """Whether :func:`forward_decode`'s attention kernel writes the step's
    new K/V rows itself, into pools whose K rows are stored ``row`` wide,
    ``fold`` tokens to a pool row: the paged kernel on one shard, over a pool
    stored as it reads it (``ops.attention.paged_kernel_writes``). Every
    other decode step scatters them first (:func:`kv_write`): the dense
    path, a tensor-parallel mesh (the kernel runs inside a ``shard_map``
    whose results are the attention output alone), rows narrower than a lane
    tile stored unfolded. The engine reports the answer for each of its
    cache kinds (``dyn_engine_info{decode_kv_write}``)."""
    from ..ops.attention import paged_kernel_writes
    return (attn_impl == "pallas" and _tp_size(mesh) == 1
            and paged_kernel_writes(row, fold))


def state_kernel_taken(mesh, attn_impl: str) -> bool:
    """Whether :func:`forward_decode` runs a state-space layer's one-token
    recurrence as the state kernel (``ops.state.state_step``: the served
    lanes' state once in and once out, in place): wherever the paged kernel
    is taken on one shard. The dense path and a tensor-parallel mesh (a
    sharded state) keep :func:`ssm_step`'s ``jax.numpy`` form."""
    return attn_impl == "pallas" and _tp_size(mesh) == 1


def _tp_size(mesh) -> int:
    from ..parallel.mesh import AXIS_TP as _TP
    if mesh is None or _TP not in mesh.axis_names:
        return 1
    return mesh.shape[_TP]


def _pp_size(mesh) -> int:
    from ..parallel.mesh import AXIS_PP as _PP
    if mesh is None or _PP not in mesh.axis_names:
        return 1
    return mesh.shape[_PP]


def pp_microbatches(B: int, pp: int) -> int:
    """Largest microbatch count <= pp that divides B (keeps every pipeline
    stage busy without padding lanes). Shared by the engine's prefill
    program and :func:`forward_decode_pp` so both pipeline identically."""
    M = max(1, min(B, pp))
    while B % M:
        M -= 1
    return M


def forward_decode(params: Dict[str, Any], cfg: LlamaConfig,
                   tokens: jax.Array,        # [B] int32 — last sampled token
                   k_pool: jax.Array,        # [L, Hkv, n_pages, page, Dh]
                   v_pool: jax.Array,
                   page_tables: jax.Array,   # [B, P] int32 (pad rows: page 0)
                   lengths: jax.Array,       # [B] tokens incl. current one
                   attn_impl: str = "xla",   # "xla" gather | "pallas" paged
                   mesh=None,                # for pallas at tp>1 (shard_map)
                   i_pool: Optional[jax.Array] = None,
                   stats: Optional[Dict[str, Any]] = None,
                   win: Optional[Tuple[jax.Array, ...]] = None,
                   ssm: Optional[Tuple[jax.Array, ...]] = None,
                   active: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, ...]:
    """Single-token decode step addressed purely by page tables.

    The current token sits at position ``lengths - 1``; its KV is written
    through the page table, then attention covers tokens [0, length). With
    ``attn_impl="pallas"`` the paged-attention kernel reads pages straight
    from the HBM pool (no contiguous-context gather at all).

    Returns (logits [B, 1, vocab] fp32, k_pool, v_pool); ``i_pool`` and
    ``stats`` as in :func:`forward` (the index keys come back as a fourth
    result). The selection goes INTO the paged kernel as a keep mask over
    the lane's logical positions.

    A per-kind model's window layers write and read ``win`` = (wk_pool,
    wv_pool, w_page_tables [B, P]): the window cache's own pools and each
    lane's page table INTO THEM, as wide as ``page_tables`` and by the same
    logical pages, of which only those that still hold a key some query can
    see name a page of the lane's (the rest: scratch page 0, masked). The
    two pools come back behind the others.

    A model with state-space layers takes ``ssm`` = (state_pool, conv_pool,
    active [B] bool): row b IS lane b of both pools, and a lane that is not
    ``active`` (an empty slot, a lane deferred under pool pressure) keeps
    its state and convolution tail bit for bit: unlike K/V written past a
    sequence's end, an advanced state cannot be trimmed afterwards. Both
    pools come back last. Where the paged kernel is taken on one shard
    (:func:`state_kernel_taken`) the one-token recurrence is the state
    kernel's: one call a layer moves the served lanes' state once in and
    once out of the pool, in place, and touches no other lane.

    ``active`` [B] bool, the same mask for any model (a model with state
    layers may leave it to ``ssm``'s): a routed feed-forward dispatches the
    active rows' assignments alone, counts theirs alone in ``stats``
    (``experts_hit``, ``held``) and adds the routed layers it dispatched
    sorted to ``stats["sorted"]`` (:func:`_ffn_block`). An idle row's
    logits are never read. The paged kernel is handed an idle lane as length
    0, which it skips (no page copied, no row written, zeros out:
    ``ops.attention.paged_attention``); ``lengths`` itself stays as given
    for everything else (positions, rotary tables, ``kv_write``, the state
    layers).
    """
    fold = cfg.kv_fold
    page = k_pool.shape[3] * fold
    with scope("attn_in"):
        pos = lengths - 1                              # [B]
    pools, index = (k_pool, v_pool), None
    if cfg.has_indexer and i_pool is not None:
        with scope("attn_in"):
            rope_i = _index_rope(cfg, pos[:, None])
            S_ctx = page_tables.shape[1] * page
            visible = (jnp.arange(S_ctx, dtype=jnp.int32)[None]
                       < lengths[:, None])[:, None, :] if (
                S_ctx > cfg.index_topk) else None       # [B,1,S]
        pools, index = (k_pool, v_pool, i_pool), (rope_i, page_tables, visible)
    with scope("embed"):
        x = _embed(params, cfg, tokens)[:, None]       # [B,1,D]
    with scope("attn_in"):
        rope, rope_sl = rope_pair(cfg, pos[:, None])
    with scope("kv_write"):
        w_page = jnp.take_along_axis(page_tables, (pos // page)[:, None],
                                     axis=1)[:, 0]
        w_off = pos % page
    w_pools = s_pools = ()
    if cfg.has_state:
        if ssm is None:
            raise ValueError(f"forward_decode: {NO_STATE}")
        *s_pools, s_active = ssm
        if active is None:
            active = s_active
    if cfg.has_window:
        if win is None:
            raise ValueError(f"forward_decode: {NO_SECOND_CACHE}")
        *w_pools, w_tables = win
        w_pools = tuple(w_pools)
        with scope("kv_write"):
            ww_page = jnp.take_along_axis(w_tables, (pos // page)[:, None],
                                          axis=1)[:, 0]
    state_kernel = None
    if cfg.ssm_heads and state_kernel_taken(mesh, attn_impl):
        from ..ops.state import served_lanes, state_step
        # which lanes the kernel visits, once a step for every layer (the
        # mask is the dispatch's: the same list in each of its steps)
        with scope("ssm_step"):
            served = served_lanes(s_active)
        # traced once a program, as the paged kernel is (``paged_for``)
        _state = jax.jit(partial(state_step,
                                 interpret=_kernel_interpret(mesh)),
                         inline=True)

        def state_kernel(pool, layer, *operands):
            return _state(pool, layer, *served, *operands)
    tp_sz = _tp_size(mesh) if attn_impl == "pallas" else 1
    if attn_impl == "pallas":
        from ..ops.attention import paged_attention as _paged
        _paged_cache: Dict[Optional[int], Any] = {}
        # what the kernel attends over, once a step for every layer: a lane
        # that is not served has nothing (the kernel skips a length of 0)
        with scope("attn_in"):
            attended = (lengths if active is None
                        else jnp.where(active, lengths, 0))

        def paged_for(layer: int):
            """Per-layer kernel variant (window on sliding layers; softcap/
            scale always) — static kernel params, so the two layer classes
            compile two variants, built once. At tp>1 the kernel runs per
            tp shard: q sharded over heads, pools over kv heads when
            divisible (replicated otherwise); axes the specs don't mention
            (sp/dp/...) stay replicated."""
            w = cfg.sliding_window if cfg.layer_sliding(layer) else None
            if w not in _paged_cache:
                fn = partial(_paged, scale=cfg.attn_scale,
                             softcap=cfg.attn_logit_softcap, window=w,
                             interpret=_kernel_interpret(mesh),
                             **({"fold": fold} if fold > 1 else {}))
                if tp_sz > 1:
                    kv_spec = (P(None, AXIS_TP, None, None, None)
                               if cfg.num_kv_heads % tp_sz == 0
                               else P(None, None, None, None, None))
                    fn = jax.shard_map(
                        fn, mesh=mesh,
                        in_specs=(P(None, AXIS_TP, None), kv_spec, kv_spec,
                                  P(None, None), P(None), P()),
                        out_specs=P(None, AXIS_TP, None),
                        check_vma=False)   # pallas_call can't declare vma
                # traced ONCE a variant and program, inlined at every layer:
                # the layers' equations are what they were, and the kernel's
                # body (most of a decode program's tracing time) is not
                # traced again for each of them
                _paged_cache[w] = jax.jit(fn, inline=True)
            return _paged_cache[w]
    _require_xla_attn(cfg, attn_impl)
    if attn_impl != "pallas":
        with scope("attn"):
            S = page_tables.shape[1] * page
            t = jnp.arange(S, dtype=jnp.int32)
            # causal == validity here: the query is the last token
            mask = (t[None] < lengths[:, None])[:, None, :]  # [B,1,S]
            sliding_mask = None
            if cfg.sliding_window is not None:
                # single-query: the window collapses to a per-lane slot range
                sliding_mask = mask & (
                    t[None] > pos[:, None] - cfg.sliding_window)[:, None, :]

    carried: List[jax.Array] = []       # a routed branch between sublayers
    for l, n in _segments(cfg):
        if cfg.layer_state(l):
            x, s_pools = _state_run(x, params, cfg, l, n, s_pools, None,
                                    None, s_active, mesh, stats,
                                    state_kernel)
            continue
        lp, la, *ffn = layer_stacks(params, cfg, l)
        sl = cfg.layer_sliding(l)
        in_win = cfg.layer_window(l)
        # a per-kind model's window layers: their own pools and page tables
        kv, tables, wpg = ((w_pools, w_tables, ww_page) if in_win
                           else (pools, page_tables, w_page))
        # the paged kernel holds the page of each lane's new token and
        # writes the rows itself where it can; elsewhere they are scattered
        new = [] if kernel_writes(mesh, attn_impl, kv[0].shape[-1] // fold,
                                  fold) else None
        h = [] if cfg.parallel_block else None
        q, kv, keep = layer_in(x, lp, la, cfg, _rope_of(cfg, l, rope_sl, rope),
                               kv, wpg, w_off, index=None if in_win else index,
                               stats=None if in_win else stats, hold=new,
                               normed=h)
        with _attn_scope(cfg, in_win):
            extra = {"sink": lp["sink"][la]} if "sink" in lp else {}
            if attn_impl == "pallas":
                # the kernel reads the whole pool in place, by layer index
                q0 = None if cfg.has_latent else q[:, 0]
                if keep is not None:
                    extra["keep"] = keep[:, 0]
                if cfg.has_latent:
                    q0, extra["latent"] = q[0][:, 0], q[1][:, 0]
                attn = paged_for(l)(
                    q0, kv[0], kv[1], tables, attended,
                    jnp.int32(la), **extra,
                    **({"new": tuple(new)} if new else {}))
                if new:
                    attn, *written = attn
                    kv = (*written, *kv[2:])
                attn = attn[:, None]
            else:
                k_ctx = kv_pages(kv[0], la, tables, fold)   # [B,S,Hkv,Dh]
                v_ctx = kv_pages(kv[1], la, tables, fold)
                if keep is not None:
                    extra["keep"] = keep
                attn = (latent_attend(cfg, q, k_ctx, v_ctx, mask)
                        if cfg.has_latent else attend_ctx(
                            cfg, q, k_ctx, v_ctx,
                            pick(sl, sliding_mask, mask), **extra))
        if in_win:
            w_pools = kv
        else:
            pools = kv
        x = layer_out(x, attn, lp, la, cfg, mesh=mesh, stats=stats,
                      ffn=ffn if cfg.per_kind else None, active=active,
                      branch=_branch_of(params, cfg, l, carried), normed=h)

    return (_lm_head(x, params, cfg), *pools, *w_pools, *s_pools)
