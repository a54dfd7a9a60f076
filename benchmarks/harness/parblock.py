"""What the per-layer metrics of a PARALLEL-BLOCK model share (the Cohere2-MoE
family: one norm a layer that attention, router, routed and shared experts
all read; window and full layers named by ``layer_types``, with caches of
their own; a chip's share of the routed experts beside shared experts that
are whole on every chip): its sizes from the published keys, and the
functions that count the LEAST bytes and operations any implementation must
move for the traced dispatches. A program without the counters (a parent
commit from before they existed, another model) reads as no value, never as
an error. Beside ``harness/routed.py`` and ``harness/scopes.py``, whose
readers of a trace it uses unedited; ``harness/kinds.py`` knows a per-kind
model by ``hybrid_layer_pattern`` and reads this family's file as ``None``,
hence the counts of its own here.

The counters (``docs/observability.md``), by ``kind`` (prefill / decode),
mirrored into ``dyn_profile_captured_work_total{counter, kind}`` while a
capture runs:

    attn_full_keys / attn_window_keys     keys the traced dispatches'
        attention of that kind of layer had to read, ONE layer's worth (a
        decode query reads its lane's visible keys, all of them or the
        window's own ``sliding_window``; a chunk's queries share their
        lane's keys, read once)
    attn_full_pairs / attn_window_pairs   (query, visible key) pairs, likewise
    dyn_moe_experts_hit_total             held experts with a row, per layer
                                          and step, summed on the device
    dyn_moe_assignments_total             token x HELD expert pairs
    dyn_moe_shared_rows_total             rows through the shared experts,
                                          summed over the layers

Least work, derived:

- attention of one kind (the scopes ``dynamo.attn_window`` /
  ``dynamo.attn_full``), per layer of the kind: a key read costs its K row
  and its V row once, ``Hkv x 2 Dh x 2`` bytes; a (query, key) pair ``Hq x 2
  Dh`` multiply-adds, 2 operations each. Projections, rotary and the cache
  writes lie outside the scopes.
- the feed-forward branch (the scopes ``dynamo.ffn`` + ``dynamo.moe_ffn``:
  router and routed experts under the second, the shared experts and the
  layer's one residual add under the first), per layer and pass (a decode
  step, a chunk): the router's ``D x R`` and the shared experts' ``S x 3 x D
  x F`` read once, the three matrices of every held expert HIT read once; 2
  operations a router or shared weight a real token, ``2 x 3 x D x F`` an
  assignment to a held expert.
- the whole decode step: every matrix the step multiplies by whatever it
  routes read once (a layer's q / k / v / o projections, its shared experts
  and router, the tied head once) and one expert's three matrices per held
  expert hit; 2 operations a weight a real token; plus the decode part of
  both kinds' attention.
- the whole prefill chunk: the layers' fixed matrices read once a chunk
  (the head is left out: a chunk needs it for one row at most, and an
  embedding row is gathered, not multiplied), the held experts hit, 2
  operations a weight a token met; plus the prefill part of both kinds'
  attention. The share is of the LARGER of the least byte time and the
  least operation time (``routed.roofline_share``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from .launch import delta
from .routed import (ASSIGNMENTS, CAPTURED, EXPERTS_HIT, ITEMSIZE, KINDS,
                     device_peaks, roofline_share, traced)
from .scopes import scope_seconds

SCOPES = {True: "dynamo.attn_window", False: "dynamo.attn_full"}
FFN_SCOPES = ("dynamo.ffn", "dynamo.moe_ffn")
MODULES = {"decode": "jit_step", "prefill": "jit_fn"}


def dims(config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The sizes the least-work functions need, from the published keys;
    None for a configuration of another family."""
    if not (config.get("use_parallel_block") and config.get("layer_types")
            and config.get("num_shared_experts")):
        return None
    L = config["num_hidden_layers"]
    window = sum(t == "sliding_attention" for t in config["layer_types"][:L])
    E = config["num_experts"]
    return {"L": L, "layers": {True: window, False: L - window},
            "D": config["hidden_size"], "Hq": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "Dh": config["head_dim"],
            "F": config["intermediate_size"], "E": E,
            "R": int((config.get("expert_shard") or {}).get(
                "router_experts", E)),
            "S": config["num_shared_experts"], "V": config["vocab_size"]}


def weights(config: Dict[str, Any]) -> Optional[Tuple[int, int, int]]:
    """-> (parameters every pass reads whatever it routes: the layers'
    projections, shared experts, router and one norm, the tied embedding and
    the final norm; of them the embedding's; parameters of ONE routed
    expert); None for another configuration. (fixed + L x E x expert is
    ``init_params``' own count: ``test_parblock_metrics.py``.)"""
    d = dims(config)
    if d is None:
        return None
    attn = d["D"] * d["Dh"] * 2 * (d["Hq"] + d["Hkv"])
    expert = 3 * d["D"] * d["F"]
    layer = attn + d["S"] * expert + d["D"] * d["R"] + d["D"]
    embed = d["V"] * d["D"]
    return d["L"] * layer + embed + d["D"], embed, expert


def attn_least(scrapes, trace, config, window: bool, kinds=tuple(KINDS)
               ) -> Optional[tuple]:
    """-> (bytes, operations, {kind: keys read}) the traced dispatches'
    (``kinds``: of those kinds of program) attention of one kind of layer
    needs."""
    d = dims(config)
    if d is None:
        return None
    name = "attn_window" if window else "attn_full"
    n = d["layers"][window]
    work = {k: traced(scrapes, trace, name + "_keys", k) for k in kinds}
    pairs = sum(traced(scrapes, trace, name + "_pairs", k) for k in kinds)
    return (sum(work.values()) * d["Hkv"] * 2 * d["Dh"] * ITEMSIZE * n,
            2.0 * pairs * d["Hq"] * 2 * d["Dh"] * n, work)


def attn_share(scrapes, trace, config, window: bool) -> Optional[float]:
    """Roofline share of one attention scope over the traced dispatches of
    both kinds, in percent; None where there is nothing to read."""
    least = attn_least(scrapes, trace, config, window)
    peaks = device_peaks(scrapes)
    if not least or not peaks or not any(least[2].values()):
        return None
    seconds = scope_seconds(trace, SCOPES[window], least[2])
    if seconds is None:
        return None
    return roofline_share(least[0], least[1], seconds, peaks)


def ffn_least(scrapes, trace, config, decode_steps: int,
              kinds=("decode",)) -> Optional[tuple]:
    """-> (bytes, operations, {kind: tokens}) the traced dispatches'
    feed-forward branches need: router, held experts hit, shared experts."""
    d = dims(config)
    if d is None:
        return None
    tokens = {k: traced(scrapes, trace, "tokens", k) for k in kinds}
    passes = sum(traced(scrapes, trace, "dispatches", k)
                 * (decode_steps if k == "decode" else 1) for k in kinds)
    hit = sum(traced(scrapes, trace, EXPERTS_HIT, k) for k in kinds)
    held = sum(traced(scrapes, trace, ASSIGNMENTS, k) for k in kinds)
    expert = 3 * d["D"] * d["F"]
    every = d["D"] * d["R"] + d["S"] * expert       # a layer, a pass
    return ((passes * d["L"] * every + hit * expert) * ITEMSIZE,
            2.0 * (every * d["L"] * sum(tokens.values()) + expert * held),
            tokens)


def ffn_share(scrapes, trace, run) -> Optional[float]:
    """Roofline share of the feed-forward branch in the traced DECODE
    dispatches: its least over the device seconds under ``dynamo.ffn`` and
    ``dynamo.moe_ffn`` together."""
    least = ffn_least(scrapes, trace, run["config"],
                      int(run["engine"]["decode_steps"]))
    peaks = device_peaks(scrapes)
    if not least or not peaks or not any(least[2].values()):
        return None
    seconds = [scope_seconds(trace, s, least[2]) for s in FFN_SCOPES]
    if None in seconds:
        return None
    return roofline_share(least[0], least[1], sum(seconds), peaks)


def program_least(scrapes, trace, run, kind: str
                  ) -> Optional[Tuple[float, float, float]]:
    """-> (bytes, operations, device seconds) of the traced runs of the
    ``kind`` program (a decode dispatch of ``decode_steps`` steps, a prefill
    chunk); None where the trace holds none, the configuration is another,
    or the program lacks the counters."""
    m = (trace or {}).get("modules", {}).get(MODULES[kind])
    counted = weights(run["config"])
    if not m or not m["runs"] or m["total_s"] <= 0 or counted is None:
        return None
    tokens = traced(scrapes, trace, "tokens", kind)
    if tokens <= 0 or traced(scrapes, trace, "attn_full_keys", kind) <= 0:
        return None                 # a program without the counters
    fixed, embed, expert = counted
    if kind == "decode":
        passes = m["runs"] * int(run["engine"]["decode_steps"])
    else:
        passes, fixed = m["runs"], fixed - embed
    bytes_ = float(passes * fixed * ITEMSIZE) + traced(
        scrapes, trace, EXPERTS_HIT, kind) * expert * ITEMSIZE
    flops = 2.0 * fixed * tokens + 2.0 * expert * traced(
        scrapes, trace, ASSIGNMENTS, kind)
    for window in (True, False):
        a = attn_least(scrapes, trace, run["config"], window, kinds=(kind,))
        bytes_, flops = bytes_ + a[0], flops + a[1]
    return bytes_, flops, m["total_s"]


def program_share(scrapes, trace, run, kind: str) -> Optional[float]:
    least = program_least(scrapes, trace, run, kind)
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    return roofline_share(*least, peaks)


def window_key_share(scrapes, trace, run) -> Optional[float]:
    """Percent of the attention keys the traced dispatches had to read that
    window layers read: window keys x window layers over that + full keys x
    full layers. With every layer reading the whole context it is the
    window layers' share of the layers (75 % at three to one)."""
    d = dims(run["config"])
    if d is None:
        return None
    # (the counters as they are: a share of counts needs no trace to scale by)
    count = lambda name: delta(scrapes["before"], scrapes["after"], CAPTURED,
                               counter=name)
    w, f = count("attn_window_keys"), count("attn_full_keys")
    w, f = w * d["layers"][True], f * d["layers"][False]
    return 100.0 * w / (w + f) if w > 0 and f > 0 else None
