"""What the per-layer metrics of a model whose layer is TWO latent-attention
sublayers with a routed branch across them share (LongCat-Flash: two rows a
token a published layer in the latent pool, two dense feed-forwards, a
router over routed AND identity experts, this chip's share of the routed
ones): the program's counters, and the functions that count the LEAST bytes
and operations any implementation must move. A program without the counters
(a parent commit from before they existed, another model) reads as no value,
never as an error. Beside ``harness/routed.py``, whose readers of a trace it
uses unedited; ``harness/latent.py`` reads another family's key names
(``num_hidden_layers``, ``intermediate_size``) and ``harness/step.py`` calls
this configuration ``unknown``, hence the counts of its own here.

The counters (``docs/observability.md``), by ``kind`` (prefill / decode),
mirrored into ``dyn_profile_captured_work_total{counter, kind}`` under their
own names while a capture runs:

    dyn_attn_latent_keys_total    latent rows a dispatch's attention had to
        read, ONE SUBLAYER's worth (a decode query its lane's visible rows;
        a chunk's queries share their lane's rows, read once)
    dyn_attn_latent_pairs_total   (query, visible key) pairs, likewise
    dyn_moe_experts_hit_total     held experts with a row, per layer and step
    dyn_moe_assignments_total     token x HELD expert pairs, all layers
    dyn_moe_routed_assignments_total   all the router chose, 12 a token
    dyn_moe_zero_assignments_total     those that went to identity experts

Least work, derived:

- latent attention (the scope ``dynamo.attn``), per SUBLAYER, of which a
  published layer has two: a row read is ``(kv_lora_rank + rope) x 2``
  bytes (1,152); a (query, key) pair costs the absorbed form's ``Hq x (2 x
  kv_lora_rank + rope)`` multiply-adds in a decode step (the only form that
  reads one row a key for all heads) and the published per-head form's ``Hq
  x (nope + rope + v)`` in a chunk (the least any form needs there).
- the routed branch (the scope ``dynamo.moe_ffn``), per published layer and
  pass (a decode step, a chunk): the router's ``D x (R + Z)`` matrix read
  once; the three matrices of every HELD expert that a row was routed to
  read once; 2 operations a router weight a real token, ``2 x 3 x D x Fe``
  an assignment to a held expert, ``2 x D`` an assignment to an identity
  expert (gate x input) and no bytes.
- the whole decode step: every matrix the step multiplies by whatever it
  routes read once a step (two sublayers' latent projections and two dense
  feed-forwards a published layer, the router, the head once; norms and the
  selection bias left out) and ONE expert's three matrices per held expert
  hit; 2 operations a weight a real token, the assignments as above; plus
  the decode part of the attention's least.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from .launch import delta
from .routed import ASSIGNMENTS, EXPERTS_HIT, ITEMSIZE, KINDS, traced

KEYS = "dyn_attn_latent_keys_total"
PAIRS = "dyn_attn_latent_pairs_total"
ROUTED = "dyn_moe_routed_assignments_total"
ZERO = "dyn_moe_zero_assignments_total"
MODULE = "jit_step"


def dims(config: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The sizes the least-work functions need, from the published keys;
    None for a configuration of another family."""
    if not (config.get("kv_lora_rank") and config.get("num_layers")
            and config.get("expert_ffn_hidden_size")):
        return None
    E = config["n_routed_experts"]
    return {"L": config["num_layers"], "D": config["hidden_size"],
            "Hq": config["num_attention_heads"],
            "Rq": config["q_lora_rank"], "Rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "Dv": config["v_head_dim"],
            "F": config["ffn_hidden_size"],
            "Fe": config["expert_ffn_hidden_size"], "E": E,
            "R": int((config.get("expert_shard") or {}).get(
                "router_experts", E)),
            "Z": int(config.get("zero_expert_num") or 0),
            "V": config["vocab_size"]}


def attn_least(scrapes, trace, config, kind: str) -> Optional[tuple]:
    """-> (bytes, operations, {kind: rows read}) the traced dispatches'
    latent attention needs, in the programs of ``kind``: the counters are
    one sublayer's worth, a published layer has two."""
    d = dims(config)
    if d is None:
        return None
    rows = traced(scrapes, trace, KEYS, kind)
    pairs = traced(scrapes, trace, PAIRS, kind)
    row = d["Rkv"] + d["rope"]
    a_pair = (row + d["Rkv"] if kind == "decode"
              else d["nope"] + d["rope"] + d["Dv"])
    sublayers = 2 * d["L"]
    return (rows * row * ITEMSIZE * sublayers,
            2.0 * pairs * d["Hq"] * a_pair * sublayers, {kind: rows})


def moe_least(scrapes, trace, config, decode_steps: int,
              kinds=tuple(KINDS)) -> Optional[tuple]:
    """-> (bytes, operations, {kind: tokens}) the traced dispatches' routed
    branches need: the router, the held experts hit, the identity part."""
    d = dims(config)
    if d is None:
        return None
    tokens = {k: traced(scrapes, trace, "tokens", k) for k in kinds}
    passes = sum(traced(scrapes, trace, "dispatches", k)
                 * (decode_steps if k == "decode" else 1) for k in kinds)
    hit = sum(traced(scrapes, trace, EXPERTS_HIT, k) for k in kinds)
    held = sum(traced(scrapes, trace, ASSIGNMENTS, k) for k in kinds)
    zero = sum(traced(scrapes, trace, ZERO, k) for k in kinds)
    router = d["D"] * (d["R"] + d["Z"])
    expert = 3 * d["D"] * d["Fe"]
    return ((passes * d["L"] * router + hit * expert) * ITEMSIZE,
            2.0 * (router * d["L"] * sum(tokens.values()) + expert * held
                   + d["D"] * zero), tokens)


def weights(config: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """-> (weights every decode step reads whatever it routes, weights of
    ONE routed expert); None for another configuration."""
    d = dims(config)
    if d is None:
        return None
    attn = (d["D"] * d["Rq"] + d["Rq"] * d["Hq"] * (d["nope"] + d["rope"])
            + d["D"] * (d["Rkv"] + d["rope"])
            + d["Rkv"] * d["Hq"] * (d["nope"] + d["Dv"])
            + d["Hq"] * d["Dv"] * d["D"])
    layer = (2 * (attn + 3 * d["D"] * d["F"]) + d["D"] * (d["R"] + d["Z"]))
    return d["L"] * layer + d["V"] * d["D"], 3 * d["D"] * d["Fe"]


def decode_step_least(scrapes, trace, run) -> Optional[Tuple[float, float,
                                                             float]]:
    """-> (bytes, operations, device seconds) of the traced runs of the
    decode program; None where the trace holds none, the configuration is
    another, or the program lacks the counters."""
    m = (trace or {}).get("modules", {}).get(MODULE)
    counted = weights(run["config"])
    if not m or not m["runs"] or m["total_s"] <= 0 or counted is None:
        return None
    if traced(scrapes, trace, KEYS, "decode") <= 0:
        return None                 # a program without the counters
    fixed, expert = counted
    D = run["config"]["hidden_size"]
    steps = m["runs"] * int(run["engine"]["decode_steps"])
    tokens = traced(scrapes, trace, "tokens", "decode")
    bytes_ = float(steps * fixed * ITEMSIZE) + traced(
        scrapes, trace, EXPERTS_HIT, "decode") * expert * ITEMSIZE
    flops = (2.0 * fixed * tokens
             + 2.0 * expert * traced(scrapes, trace, ASSIGNMENTS, "decode")
             + 2.0 * D * traced(scrapes, trace, ZERO, "decode"))
    attn = attn_least(scrapes, trace, run["config"], "decode")
    return bytes_ + attn[0], flops + attn[1], m["total_s"]


def zero_assignment_share(scrapes, run) -> Optional[float]:
    """Percent of the router's choices that went to identity experts over
    the window: delta zero assignments / delta routed assignments."""
    if dims(run["config"]) is None:
        return None
    b, a = scrapes["before"], scrapes["after"]
    routed, zero = delta(b, a, ROUTED), delta(b, a, ZERO)
    return 100.0 * zero / routed if routed > 0 and zero > 0 else None
