"""What the per-layer metrics of a PER-KIND model share (window and full
layers with head counts and caches of their own, a chip's share of the
experts): the program's counters of its two page pools and of the share, the
work of the traced dispatches by kind of layer, and the functions that count
the LEAST bytes and operations any implementation must move for it. A
program without the counters (a parent commit from before they existed, a
model of one law) reads as no value, never as an error. Beside
``harness/routed.py``, whose readers of a trace it uses unedited.

The counters (``docs/observability.md``):

    dyn_kv_resident_token_steps_total{pool}   tokens of a decode dispatch's
        lanes that a page of the pool (global / window) held when the
        dispatch was fetched, summed over dispatches
    dyn_kv_window_pages_released_total        window pages given back while
        their sequence lived on
    dyn_moe_assignments_total{kind}           token x expert pairs COMPUTED
        here (under a share: those to experts held here)
    dyn_moe_routed_assignments_total{kind}    ... the router chose, all
    dyn_profile_captured_work_total{counter, kind}, ``counter`` =
        ``attn_full_keys`` / ``attn_window_keys``: keys the traced
        dispatches' attention of that kind had to read, one layer's worth (a
        decode query reads its lane's visible keys, full or the window's
        128; a chunk's queries share their lane's keys, read once);
        ``attn_full_pairs`` / ``attn_window_pairs``: (query, visible key)
        pairs, one layer's worth

Least work, derived:

- attention of one kind, per layer of the kind: every key a dispatch must
  read costs its K row and its V row once, ``Hkv x (Dh + Dv) x itemsize``
  bytes AS THE MODEL DEFINES THEM (what the pool pads a K row by is the
  implementation's, so it counts against the share); every (query, key)
  pair costs ``Hq x (Dh + Dv)`` multiply-adds, 2 operations each. The
  projections, rotary and the cache writes are outside the scope.
- the held experts, per layer and step: the three matrices of every held
  expert that at least one row was routed to are read once, and every
  (token, held expert) pair costs ``3 x D x F`` multiply-adds.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .routed import ASSIGNMENTS, EXPERTS_HIT, ITEMSIZE, KINDS, traced

ROUTED = "dyn_moe_routed_assignments_total"
RESIDENT = "dyn_kv_resident_token_steps_total"


def dims(config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The sizes the least-work functions need, from the published keys;
    None for a configuration that is not per-kind."""
    if "hybrid_layer_pattern" not in config:
        return None
    L = config["num_hidden_layers"]
    pattern = config["hybrid_layer_pattern"][:L]
    Dh = config["head_dim"]
    return {
        "layers": {True: sum(pattern), False: L - sum(pattern)},
        "Hkv": {True: config["swa_num_key_value_heads"],
                False: config["num_key_value_heads"]},
        "Hq": config["num_attention_heads"], "Dh": Dh,
        "Dv": config.get("v_head_dim", Dh), "D": config["hidden_size"],
        "F": config["moe_intermediate_size"],
    }


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
    row = d["Dh"] + d["Dv"]
    return (sum(work.values()) * d["Hkv"][window] * row * ITEMSIZE * n,
            2.0 * pairs * d["Hq"] * row * n, work)


def moe_share_least(scrapes, trace, config) -> Optional[tuple]:
    """-> (bytes, operations, {kind: assignments to held experts}) the
    traced dispatches' held experts need."""
    d = dims(config)
    if d is None or not config.get("n_routed_experts"):
        return None
    hit = sum(traced(scrapes, trace, EXPERTS_HIT, k) for k in KINDS)
    work = {k: traced(scrapes, trace, ASSIGNMENTS, k) for k in KINDS}
    one = 3.0 * d["D"] * d["F"]
    return one * ITEMSIZE * hit, 2.0 * one * sum(work.values()), work
