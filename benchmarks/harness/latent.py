"""What the per-layer metrics of a LATENT-ATTENTION model share (one
compressed row a token for all heads; low-rank projections; a shared expert
beside group-limited routed ones, of which the chip holds a share): the
program's two counters, and the functions that count the LEAST bytes and
operations any implementation must move. A program without the counters (a
parent commit from before they existed, another model) reads as no value,
never as an error. Beside ``harness/routed.py``, ``harness/kinds.py`` and
``harness/step.py``, whose readers it uses unedited; ``step.py`` calls this
configuration ``unknown`` (low-rank projections), hence the whole step's
count of its own here.

The counters (``docs/observability.md``), by ``kind`` (prefill / decode),
one layer's worth, mirrored into ``dyn_profile_captured_work_total{counter,
kind}`` under their own names while a capture runs:

    dyn_attn_latent_keys_total    latent rows a dispatch's attention had to
        read: a decode query its lane's visible rows, each step; a chunk's
        queries share their lane's rows, read once
    dyn_attn_latent_pairs_total   (query, visible key) pairs

Least work, derived:

- attention, per layer: every row read costs ``(kv_lora_rank + rope) x
  itemsize`` bytes AS THE MODEL DEFINES IT (what the pool pads the rotary
  key by is the implementation's, so it counts against the share). A pair
  costs, in decode, the absorbed form's ``Hq x ((Rkv + rope) + Rkv)``
  multiply-adds (a decode step that expanded K and V a head would read
  ``Hq x (nope + v)`` a key more than it saves); in a chunk the PUBLISHED
  per-head form's ``Hq x ((nope + rope) + v)`` a pair, its expansion left
  out: the least any form needs (the absorbed form the program runs does
  3.4 times that a pair). The projections, the absorption of q, ``W_uv``
  and the cache writes are outside the scope.
- the routed layers' feed-forward, per routed layer and step or chunk: the
  router and the shared expert are read once, every held expert that at
  least one row was routed to once; a token costs the shared expert's ``3 x
  D x Fs`` multiply-adds and a held assignment ``3 x D x Fe``. The router's
  operations are left out (a lower bound).
- the whole decode step: every matrix the step multiplies by read once (the
  low-rank projections with their two inner norms' weights, ``wo``, the
  dense layer, router and shared expert of each routed layer, the head) and
  only the experts hit; 2 operations a weight a real token; plus the decode
  part of the attention's least.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from .launch import delta
from .routed import ASSIGNMENTS, EXPERTS_HIT, ITEMSIZE, KINDS, traced

KEYS = "dyn_attn_latent_keys_total"
PAIRS = "dyn_attn_latent_pairs_total"
DISPATCHES = "dyn_engine_dispatches_total"
MODULE = "jit_step"


def dims(config: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The sizes the least-work functions need, from the published keys;
    None for a configuration without latent attention."""
    if not config.get("kv_lora_rank"):
        return None
    L = config["num_hidden_layers"]
    first, freq = (config.get("first_k_dense_replace", 0),
                   config.get("moe_layer_freq", 1))
    routed = sum(l >= first and l % freq == 0 for l in range(L))
    E = config.get("n_routed_experts") or 0
    return {"L": L, "routed": routed if E else 0, "D": config["hidden_size"],
            "Hq": config["num_attention_heads"],
            "Rq": config["q_lora_rank"], "Rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "Dv": config["v_head_dim"],
            "F": config["intermediate_size"],
            "Fe": config.get("moe_intermediate_size") or 0,
            "Fs": (config.get("n_shared_experts") or 0)
            * (config.get("moe_intermediate_size") or 0),
            "R": int((config.get("expert_shard") or {}).get(
                "router_experts", E)),
            "V": config["vocab_size"]}


def attn_least(scrapes, trace, config, kind: str) -> Optional[tuple]:
    """-> (bytes, operations, {kind: rows read}) the traced dispatches'
    latent attention needs, in the programs of ``kind``."""
    d = dims(config)
    if d is None:
        return None
    rows = traced(scrapes, trace, KEYS, kind)
    pairs = traced(scrapes, trace, PAIRS, kind)
    row = d["Rkv"] + d["rope"]
    a_pair = (row + d["Rkv"] if kind == "decode"
              else d["nope"] + d["rope"] + d["Dv"])
    return (rows * row * ITEMSIZE * d["L"],
            2.0 * pairs * d["Hq"] * a_pair * d["L"], {kind: rows})


def moe_shared_least(scrapes, trace, config, decode_steps: int
                     ) -> Optional[tuple]:
    """-> (bytes, operations, {kind: tokens}) the traced dispatches' routed
    layers' feed-forward needs: router, shared expert, held experts hit."""
    d = dims(config)
    if d is None or not d["routed"]:
        return None
    tokens = {k: traced(scrapes, trace, "tokens", k) for k in KINDS}
    passes = (traced(scrapes, trace, "dispatches", "decode") * decode_steps
              + traced(scrapes, trace, "dispatches", "prefill"))
    hit = sum(traced(scrapes, trace, EXPERTS_HIT, k) for k in KINDS)
    held = sum(traced(scrapes, trace, ASSIGNMENTS, k) for k in KINDS)
    every = d["D"] * d["R"] + 3 * d["D"] * d["Fs"]
    expert = 3 * d["D"] * d["Fe"]
    return ((passes * d["routed"] * every + hit * expert) * ITEMSIZE,
            2.0 * 3 * d["D"] * (d["Fs"] * sum(tokens.values()) * d["routed"]
                                + d["Fe"] * held), tokens)


def weights(config: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """-> (weights every decode step reads whatever it routes, weights of
    ONE routed expert); None for a configuration without latent attention."""
    d = dims(config)
    if d is None:
        return None
    attn = (d["D"] * d["Rq"] + d["Rq"]
            + d["Rq"] * d["Hq"] * (d["nope"] + d["rope"])
            + d["D"] * (d["Rkv"] + d["rope"]) + d["Rkv"]
            + d["Rkv"] * d["Hq"] * (d["nope"] + d["Dv"])
            + d["Hq"] * d["Dv"] * d["D"])
    fixed = (d["L"] * attn + (d["L"] - d["routed"]) * 3 * d["D"] * d["F"]
             + d["routed"] * (d["D"] * d["R"] + 3 * d["D"] * d["Fs"])
             + d["V"] * d["D"])
    return fixed, 3 * d["D"] * d["Fe"]


def decode_step_least(scrapes, trace, run) -> Optional[Tuple[float, float,
                                                             float]]:
    """-> (bytes, operations, device seconds) of the traced runs of the
    decode program; None where the trace holds none or the configuration
    has no latent attention."""
    m = (trace or {}).get("modules", {}).get(MODULE)
    counted = weights(run["config"])
    if not m or not m["runs"] or m["total_s"] <= 0 or counted is None:
        return None
    if traced(scrapes, trace, KEYS, "decode") <= 0:
        return None                 # a program without the counters
    fixed, expert = counted
    steps = m["runs"] * int(run["engine"]["decode_steps"])
    tokens = traced(scrapes, trace, "tokens", "decode")
    bytes_ = float(steps * fixed * ITEMSIZE) + traced(
        scrapes, trace, EXPERTS_HIT, "decode") * expert * ITEMSIZE
    flops = 2.0 * fixed * tokens + 2.0 * expert * traced(
        scrapes, trace, ASSIGNMENTS, "decode")
    attn = attn_least(scrapes, trace, run["config"], "decode")
    return bytes_ + attn[0], flops + attn[1], m["total_s"]


def keys_per_step(scrapes, run) -> Optional[float]:
    """Latent rows a decode STEP read over the window (one layer's worth,
    all its lanes): delta keys{decode} / (decode dispatches x
    ``decode_steps``)."""
    b, a = scrapes["before"], scrapes["after"]
    n = delta(b, a, DISPATCHES, kind="decode") * int(
        run["engine"]["decode_steps"])
    rows = delta(b, a, KEYS, kind="decode")
    return rows / n if n > 0 and rows > 0 else None
