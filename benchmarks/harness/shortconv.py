"""What the per-layer metrics of a model with GATED SHORT-CONVOLUTION layers
share (LFM2: a two-row tail a lane beside the K/V cache of a few GQA layers,
under routed experts behind leading dense layers): the program's counters,
and the functions that count the LEAST bytes and operations any
implementation must move. A program without the counters (a parent commit
from before they existed, another model) reads as no value, never as an
error. Beside ``harness/routed.py``, ``harness/state.py`` and
``harness/step.py``, whose readers it uses unedited; ``step.py`` calls this
configuration ``unknown`` (layer types ``conv``, ``full_attention``), hence
the whole step's count of its own here.

The counters (``docs/observability.md``), by ``kind`` (prefill / decode),
mirrored into ``dyn_profile_captured_work_total{counter, kind}`` under their
own names while a capture runs:

    dyn_ssm_active_lane_steps_total   lane-steps of lanes a dispatch served
        (one layer's worth; prefill: each served row of the chunk program)
    dyn_ssm_tokens_total              real tokens through the conv operators
    dyn_moe_experts_hit_total         experts with a row, per layer and step
    dyn_moe_assignments_total         token x expert pairs, all layers
    dyn_moe_layer_calls_total         routed layers x steps of a decode
        dispatch, x 1 of a chunk: the calls those experts were hit in

Least work, derived:

- the conv recurrence (the scopes ``dynamo.ssm_step`` / ``dynamo.ssm_scan``:
  tail in, both gates, the taps, tail out; the two projections are outside),
  per conv layer: a SERVED lane-step's tail read once and written once, ``2
  x (K - 1) x D x 2`` bytes (16,384 at the published sizes); a real token's
  B, C, z in and y out, ``4 x D x 2`` bytes (16,384); 2 gates and K
  multiply-adds a channel a token, ``(2 + 2 K) x D`` operations (8 a channel
  at 3 taps). The taps' weights are left out: a lower bound.
- the whole decode step: every matrix the step multiplies by whatever it
  routes read once a step (the conv layers' W_in and W_out, the attention
  layers' four projections, the leading dense feed-forwards, the routers,
  the tied head once; the taps, the norms' weights and ``expert_bias`` left
  out) and ONE expert's three matrices per expert hit; 2 operations a weight
  a real token and ``2 x 3 x D x Fe`` an assignment; plus the recurrence's
  least above. The K/V rows the attention layers read are left out: a lower
  bound. A dense dispatch reads every expert, hit or not, and so reads
  further under 100 than a sorted one: ``dyn_engine_info{moe_dispatch}`` says
  which the program took.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from .launch import delta
from .routed import ASSIGNMENTS, EXPERTS_HIT, ITEMSIZE, traced
from .state import ACTIVE, TOKENS

LAYER_CALLS = "dyn_moe_layer_calls_total"
MODULE = "jit_step"


def dims(config: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The sizes the least-work functions need, from the published keys;
    None for a configuration without gated short-convolution layers."""
    L = config.get("num_hidden_layers", 0)
    kinds = (config.get("layer_types") or ())[:L]
    if "conv" not in kinds:
        return None
    Hq = config["num_attention_heads"]
    dense = int(config.get("num_dense_layers", 0))
    return {"conv": sum(k == "conv" for k in kinds),
            "attn": sum(k != "conv" for k in kinds),
            "dense": dense, "routed": L - dense,
            "D": config["hidden_size"], "K": int(config["conv_L_cache"]),
            "Hq": Hq, "Hkv": config["num_key_value_heads"],
            "Dh": config["hidden_size"] // Hq,
            "F": config["intermediate_size"],
            "Fe": config["moe_intermediate_size"],
            "E": config["num_experts"], "V": config["vocab_size"]}


def conv_least(scrapes, trace, run, kind: str) -> Optional[tuple]:
    """-> (bytes, operations, {kind: real tokens}) the traced dispatches of
    ``kind`` need under the recurrence's scope."""
    d = dims(run["config"])
    if d is None:
        return None
    tokens = traced(scrapes, trace, TOKENS, kind)
    served = traced(scrapes, trace, ACTIVE, kind)
    D, K = d["D"], d["K"]
    bytes_ = d["conv"] * ITEMSIZE * (served * 2 * (K - 1) * D
                                     + tokens * 4 * D)
    return bytes_, float(d["conv"] * tokens * (2 + 2 * K) * D), {kind: tokens}


def weights(config: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """-> (weights every decode step reads whatever it routes, weights of
    ONE routed expert); None for another configuration."""
    d = dims(config)
    if d is None:
        return None
    D = d["D"]
    conv = 3 * D * D + D * D
    attn = D * d["Hq"] * d["Dh"] + 2 * D * d["Hkv"] * d["Dh"] + (
        d["Hq"] * d["Dh"] * D)
    fixed = (d["conv"] * conv + d["attn"] * attn + d["dense"] * 3 * D * d["F"]
             + d["routed"] * D * d["E"] + d["V"] * D)
    return fixed, 3 * D * d["Fe"]


def decode_step_least(scrapes, trace, run) -> Optional[Tuple[float, float,
                                                             float]]:
    """-> (bytes, operations, device seconds) of the traced runs of the
    decode program; None where the trace holds none, the configuration is
    another, or the program lacks the counters."""
    m = (trace or {}).get("modules", {}).get(MODULE)
    counted = weights(run["config"])
    if not m or not m["runs"] or m["total_s"] <= 0 or counted is None:
        return None
    tokens = traced(scrapes, trace, TOKENS, "decode")
    if tokens <= 0:
        return None                 # a program without the counters
    fixed, expert = counted
    steps = m["runs"] * int(run["engine"]["decode_steps"])
    bytes_ = float(steps * fixed * ITEMSIZE) + traced(
        scrapes, trace, EXPERTS_HIT, "decode") * expert * ITEMSIZE
    flops = 2.0 * fixed * tokens + 2.0 * expert * traced(
        scrapes, trace, ASSIGNMENTS, "decode")
    conv = conv_least(scrapes, trace, run, "decode")
    return bytes_ + conv[0], flops + conv[1], m["total_s"]


def expert_read_share(scrapes, run) -> Optional[float]:
    """Percent of a routed layer's experts that a decode step's rows were
    routed to, over the window: delta experts hit{decode} / (delta layer
    calls{decode} x experts)."""
    d = dims(run["config"])
    if d is None:
        return None
    b, a = scrapes["before"], scrapes["after"]
    calls = delta(b, a, LAYER_CALLS, kind="decode")
    hit = delta(b, a, EXPERTS_HIT, kind="decode")
    return 100.0 * hit / (calls * d["E"]) if calls > 0 and hit > 0 else None
