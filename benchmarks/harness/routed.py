"""What the per-layer metrics of routed experts and of learned top-k
attention share: the program's four counters between the two scrapes, the
part of them that the traced iterations did, and the functions that count
the LEAST bytes and operations any implementation must move for that work.
A program without the counters (a parent commit from before they existed, a
dense model) reads as no value, never as an error.

The counters (``docs/observability.md``), all by ``kind`` (prefill / decode):

    dyn_moe_assignments_total             token x expert pairs, all layers
    dyn_moe_experts_hit_total             experts with a row, per layer and
                                          step, summed on the device
    dyn_sparse_attn_context_tokens_total  keys visible to each query, summed
                                          over queries (one layer's worth)
    dyn_sparse_attn_selected_tokens_total min(visible, topk), summed

They cover the whole window; the trace covers its first ``trace_steps``
engine iterations (the ramp from an empty batch: short contexts, few
lanes). The work of the TRACED dispatches themselves is what the program
counts a second time while a capture runs (``docs/observability.md``):

    dyn_profile_captured_work_total{counter, kind}
        ``counter`` = one of the four names above, ``dispatches`` or
        ``tokens``: the same amounts, of the dispatches enqueued while the
        ``DYN_PROFILE_DIR`` capture ran; ``scored_keys`` /
        ``scoring_dispatches`` / ``scoring_tokens``: visible keys,
        dispatches and queries of those whose program scores at all

so a roofline share divides the least time for exactly the dispatches whose
device time the trace holds. A capture is stopped by a thread of its own, so
its last dispatch may be cut: where the trace holds fewer runs of a kind's
program (``modules["jit_step"]`` decode, ``modules["jit_fn"]`` prefill) than
dispatches were counted, the work is scaled down by runs / dispatches, never
up. A program without that counter (a parent commit from before it existed)
reads as no value.

The device time is that of the operations under the program's
``jax.named_scope``, read by INSTANCE (``harness/scopes.py``
``twin_share``): every operation of a bucket program carries its scope in
the capture (``tf_op``), so no list of operation names stands between a
metric and the trace, and a change of fusion or of kernel under a scope is
read as it runs.

Least work, derived:

- index select, per layer: every visible key's index key has to be read at
  least once per query ROW GROUP that scores it. A decode query reads its
  own lane's keys: ``visible x Di x itemsize`` bytes a query. A prefill
  chunk's queries share one lane's keys, so the chunk reads them once:
  bytes = (sum of visible over the chunk's queries / queries in the chunk) x
  Di x itemsize, taken here as the mean visible count of the kind's queries
  per dispatch. Multiply-adds: ``visible x Hi x Di`` a query, 2 operations
  each. The top-k itself is counted as free (a lower bound).
- routed experts, per layer and step: the three matrices of every expert
  that at least one row was routed to are read once (``3 x D x F x
  itemsize`` an expert hit), and every (token, expert) pair costs ``3 x D x
  F`` multiply-adds. The router is left out (a lower bound).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .launch import Series, delta
from .peaks import peaks_for

ASSIGNMENTS = "dyn_moe_assignments_total"
EXPERTS_HIT = "dyn_moe_experts_hit_total"
CONTEXT = "dyn_sparse_attn_context_tokens_total"
SELECTED = "dyn_sparse_attn_selected_tokens_total"
KINDS = {"prefill": "jit_fn", "decode": "jit_step"}   # kind -> trace module
ITEMSIZE = 2                                          # bfloat16


def window(scrapes: Dict[str, Series], name: str, kind: Optional[str] = None
           ) -> float:
    match = {"kind": kind} if kind else {}
    return delta(scrapes["before"], scrapes["after"], name, **match)


CAPTURED = "dyn_profile_captured_work_total"


def traced(scrapes: Dict[str, Series], trace: Optional[Dict[str, Any]],
           name: str, kind: str) -> float:
    """What the traced dispatches of ``kind`` added to counter ``name``
    (``dispatches`` / ``tokens``: themselves); see the module's text."""
    counted = delta(scrapes["before"], scrapes["after"], CAPTURED,
                    counter="dispatches", kind=kind)
    runs = ((trace or {}).get("modules", {}).get(KINDS[kind]) or {}).get(
        "runs", 0)
    if counted <= 0 or not runs:
        return 0.0
    return (delta(scrapes["before"], scrapes["after"], CAPTURED,
                  counter=name, kind=kind) * min(1.0, runs / counted))


def device_peaks(scrapes: Dict[str, Series]) -> Optional[Dict[str, float]]:
    """The published peaks of the TPU the engine reports; None off a TPU (a
    CPU rehearsal has no roofline), an error for a TPU not in the table."""
    kinds = [l.get("device_kind") for n, l, v in scrapes["before"]
             if n == "dyn_engine_info" and v == 1
             and l.get("platform") == "tpu"]
    return peaks_for(kinds[0]) if kinds else None


def roofline_share(bytes_: float, flops: float, seconds: float,
                   peaks: Dict[str, float]) -> Optional[float]:
    """100 x the least time the chip could take / the time it took."""
    if seconds <= 0 or (bytes_ <= 0 and flops <= 0):
        return None
    least = max(bytes_ / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return 100.0 * least / seconds


def index_select_least(scrapes, trace, config, kinds=tuple(KINDS)
                       ) -> Optional[tuple]:
    """-> (bytes, operations, {kind: visible keys scored}) the traced
    dispatches' index scores need (``kinds``: of those kinds of program).
    Only dispatches whose program SCORES count (``scored_keys``: a context
    bucket no longer than ``topk`` selects every visible key by construction
    and the program skips the scoring)."""
    sa = config.get("sa_config")
    if not sa:
        return None
    L, Hi, Di = (config["num_hidden_layers"], sa["indexer_num_heads"],
                 sa["indexer_head_dim"])
    bytes_ = flops = 0.0
    work = {}
    for kind in kinds:
        visible = work[kind] = traced(scrapes, trace, "scored_keys", kind)
        flops += 2.0 * visible * Hi * Di * L
        if kind == "decode":
            bytes_ += visible * Di * ITEMSIZE * L
        else:
            queries = traced(scrapes, trace, "scoring_tokens", kind)
            runs = traced(scrapes, trace, "scoring_dispatches", kind)
            if queries and runs:
                # each chunk reads its lane's visible keys once
                bytes_ += visible / (queries / runs) * Di * ITEMSIZE * L
    return bytes_, flops, work


def moe_least(scrapes, trace, config) -> Optional[tuple]:
    """-> (bytes, operations, {kind: assignments}) the traced dispatches'
    expert FFNs need."""
    if not config.get("num_experts"):
        return None
    D = config["hidden_size"]
    F = config.get("moe_intermediate_size") or config["intermediate_size"]
    hit = sum(traced(scrapes, trace, EXPERTS_HIT, k) for k in KINDS)
    work = {k: traced(scrapes, trace, ASSIGNMENTS, k) for k in KINDS}
    return (3.0 * D * F * ITEMSIZE * hit,
            2.0 * 3.0 * D * F * sum(work.values()), work)
