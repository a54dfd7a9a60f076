"""The LEAST a whole decode step costs the chip, from the configuration's
published keys: the lower bound under ``program.decode_step_mfu_share``, the
one share that still bounds a claim when a kernel is taken off the path and
its own roofline falls silent. Beside ``harness/routed.py``,
``harness/kinds.py`` and ``harness/state.py``, whose scopes' least work it
adds in, each unedited.

What any implementation of one decode step must do, whatever it fuses:

- read every matrix the step multiplies by ONCE (bfloat16, 2 bytes a
  weight; a step's token does not exist before the step before it ends, so
  no weight is kept on the chip from step to step: the chip's fast memory is
  a hundredth of the smallest cell's weights): per layer the attention
  projections (q, k, v, out; a window layer of a per-kind model by its own
  ``swa_*`` head counts and widths) or the state-space layer's two
  projections (in: ``D x (2 I + 2 G N + H)``, out: ``I x D``), the
  feed-forward's three matrices, or for a routed layer the router, the
  shared experts and ONLY THE EXPERTS HIT (the program's own counter,
  ``dyn_moe_experts_hit_total`` of the traced decode dispatches); the LM
  head (``V x D``; where the embedding table is tied it IS the head and is
  counted here, once; an untied table is only looked up: left out);
- 2 operations a weight a real token (the traced dispatches' ``tokens``; a
  cell whose program counts none while a capture runs, a dense model's,
  leaves the operations out: bytes bind every decode step under 240 lanes);
- what the cell's own scopes need at least, decode dispatches only: the
  state a step (``state.ssm_least``), a per-kind model's keys
  (``kinds.attn_least``), the index scores (``routed.index_select_least``).

Left out, so the bound stays a LOWER one (it may leave work out, it may not
invent any): K/V bytes where no counter gives them (every cell but mimo),
biases, norms' weights, the convolution's, the indexer's projections (their
shapes are the repo's, not published keys), activations, the sampler.

The number of steps is read from the trace: runs of ``jit_step`` x the
engine's ``decode_steps``; their device time is the runs' total.

Which cells: those the metric's ``workloads`` in ``BENCHMARK.json`` lists,
the cells of the five configurations whose count
``benchmarks/tests/test_step_mfu.py`` pins by hand. ``weights`` knows the
weight structures of those five and no other, and a later PR may not edit
this file. So a later configuration joins in one of two ways, both by
adding only: a test of its own pins ``weights(config)`` to its hand count
and its cell is appended to the list; or, where its matrices are of a form
not counted here (a low-rank projection, a second shared width, another kind
of layer), it brings a metric file of its own with ``mfu`` in its name and
its own count. A configuration that ``weights`` can tell it does not know
(``unknown``) reads as NO value: it never raises and never guesses.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from . import kinds, routed, state
from .routed import ASSIGNMENTS, EXPERTS_HIT, ITEMSIZE, traced

MODULE = "jit_step"


def attention_weights(D: int, Hq: int, Hkv: int, Dh: int, Dv: int) -> int:
    return D * Hq * Dh + D * Hkv * (Dh + Dv) + Hq * Dv * D


NEEDS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "intermediate_size", "vocab_size")
LAYER_TYPES = ("mamba", "attention")


def unknown(config: Dict[str, Any]) -> Optional[str]:
    """Why ``weights`` cannot count this configuration, or None: a key it
    needs is not there, or the configuration names a weight structure that
    nothing here counts."""
    missing = [k for k in NEEDS if not config.get(k)]
    if missing:
        return f"no {missing}"
    low_rank = [k for k, v in config.items() if k.endswith("_rank") and v]
    if low_rank:
        return f"low-rank projections {low_rank}"
    other = set(config.get("layer_types") or ()) - set(LAYER_TYPES)
    if other:
        return f"layer types {sorted(other)}"
    if config.get("shared_intermediate_size") not in (
            None, config["intermediate_size"]):
        return "a shared width of its own"
    return None


def weights(config: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """-> (weights every decode step reads whatever it routes, weights of
    ONE routed expert; 0 for a model without routed layers); None for a
    configuration it does not know (``unknown``)."""
    if unknown(config):
        return None
    D, L = config["hidden_size"], config["num_hidden_layers"]
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config.get("head_dim") or D // Hq
    full = attention_weights(D, Hq, Hkv, Dh, config.get("v_head_dim", Dh))
    window = full
    if "swa_num_key_value_heads" in config:
        sDh = config.get("swa_head_dim", Dh)
        window = attention_weights(
            D, config.get("swa_num_attention_heads", Hq),
            config["swa_num_key_value_heads"], sDh,
            config.get("swa_v_head_dim", config.get("v_head_dim", sDh)))
    ssm = state.dims(config)
    if ssm:
        groups = config.get("mamba_n_groups", 1)
        mixer = (D * (2 * ssm["I"] + 2 * groups * ssm["N"] + ssm["H"])
                 + ssm["I"] * D)
    experts = config.get("num_experts") or config.get("n_routed_experts") or 0
    Fm = config.get("moe_intermediate_size") or 0
    # the router scores every PUBLISHED expert, also where a chip holds a share
    router = D * int((config.get("expert_shard") or {}).get(
        "router_experts", experts))
    shared = 3 * D * Fm * int(config.get("n_shared_experts") or 0)
    dense = 3 * D * config["intermediate_size"]
    layer_types = config.get("layer_types") or ()
    pattern = config.get("hybrid_layer_pattern") or ()
    moe_freq = config.get("moe_layer_freq")
    mlp_only = set(config.get("mlp_only_layers") or ())
    fixed = config["vocab_size"] * D                     # the LM head
    for l in range(L):
        if l < len(layer_types) and layer_types[l] == "mamba":
            fixed += mixer
        else:
            fixed += window if l < len(pattern) and pattern[l] else full
        routed_layer = bool(experts) and l not in mlp_only and (
            moe_freq is None or bool(moe_freq[l]))
        fixed += router + shared if routed_layer else dense
    return fixed, (3 * D * Fm if experts else 0)


def decode_step_least(scrapes, trace, run) -> Optional[Tuple[float, float,
                                                             float]]:
    """-> (bytes, operations, device seconds) of the traced runs of the
    decode program; None where the trace holds none or the configuration is
    one ``weights`` does not know."""
    m = (trace or {}).get("modules", {}).get(MODULE)
    config = run["config"]
    counted = weights(config)
    if not m or not m["runs"] or m["total_s"] <= 0 or counted is None:
        return None
    steps = m["runs"] * int(run["engine"]["decode_steps"])
    fixed, expert = counted
    tokens = traced(scrapes, trace, "tokens", "decode")
    bytes_ = float(steps * fixed * ITEMSIZE)
    flops = 2.0 * fixed * tokens
    if expert:
        bytes_ += traced(scrapes, trace, EXPERTS_HIT, "decode") * expert \
            * ITEMSIZE
        flops += 2.0 * expert * traced(scrapes, trace, ASSIGNMENTS, "decode")
    for least in (
            state.ssm_least(scrapes, trace, run, "decode"),
            kinds.attn_least(scrapes, trace, config, True, ("decode",)),
            kinds.attn_least(scrapes, trace, config, False, ("decode",)),
            routed.index_select_least(scrapes, trace, config, ("decode",))):
        if least:
            bytes_, flops = bytes_ + least[0], flops + least[1]
    return bytes_, flops, m["total_s"]
