"""What the per-layer metrics of a model with STATE-SPACE layers share (a
recurrent state a lane beside the K/V cache): the program's counters of the
state pool, the work of the traced dispatches, and the function that counts
the LEAST bytes and operations any implementation must move for it. A
program without the counters (a parent commit from before they existed, a
model without such layers) reads as no value, never as an error. Beside
``harness/routed.py`` and ``harness/kinds.py``, whose readers of a trace it
uses unedited.

The counters (``docs/observability.md``), by ``kind`` (prefill / decode),
one layer's worth:

    dyn_ssm_lane_steps_total          lane-steps whose state a dispatch read
        and wrote (decode: every lane of the pool, each step; prefill: each
        row of the chunk program)
    dyn_ssm_active_lane_steps_total   those of lanes the dispatch served
    dyn_ssm_tokens_total              real tokens through the mixers
    dyn_profile_captured_work_total{counter, kind}, ``counter`` = the three
        names above, ``dispatches``, ``tokens``: the same amounts of the
        dispatches enqueued while the ``DYN_PROFILE_DIR`` capture ran

Least work of the recurrence (convolution, state update, read-out and gated
norm: the scopes ``dynamo.ssm_step`` / ``dynamo.ssm_scan``; the two
projections are outside), per state-space layer:

- bytes: a lane the dispatch SERVED has its state read once and written
  once per STEP in decode (and per chunk in prefill), ``2 x H x P x N x 4``
  bytes in float32 as the configuration keeps it (``assumed``). A step
  passes every state-space layer before the next step's token exists, and
  one layer's states of the pool's lanes are far more than the chip's fast
  memory holds beside the weights' stream (the cell's pool is 4.8 GB over 36
  layers), so no kernel keeps a layer's block on the chip from one step of
  a dispatch to the next: once in and once out a step IS the floor, a
  kernel at the peak reads 100 %. (Until PR 37 the count was a dispatch,
  ``decode_steps`` times less: a bound of which a perfect kernel read
  25 %.) The program's present form crosses HBM a third time for the
  read-out and does so for every lane of the pool, served or not, and reads
  well under 100 %. Beside it each real token's X, B, C, dt and z in and y
  out, ``(3 x I + 2 x N + H) x 2`` bytes in bfloat16. The convolution tail
  (3 x 4352 a lane) is left out: a lower bound.
- operations: the state update and the read-out are a multiply-add each a
  state element a token, 2 operations each: ``4 x H x P x N`` a token,
  whatever form computes them (the chunk form's matrix products count as
  this, not as what they execute).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .routed import ITEMSIZE, traced

LANE_STEPS = "dyn_ssm_lane_steps_total"
ACTIVE = "dyn_ssm_active_lane_steps_total"
TOKENS = "dyn_ssm_tokens_total"
STATE_ITEMSIZE = 4                                    # float32 (assumed)


def dims(config: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The sizes the least-work function needs, from the published keys;
    None for a configuration without state-space layers."""
    kinds = config.get("layer_types") or ()
    if "mamba" not in kinds:
        return None
    L = config["num_hidden_layers"]
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    return {"layers": sum(k == "mamba" for k in kinds[:L]), "H": H, "P": P,
            "N": config["mamba_d_state"], "I": H * P}


def ssm_least(scrapes, trace, run, kind: str) -> Optional[tuple]:
    """-> (bytes, operations, {kind: real tokens}) the traced dispatches of
    ``kind`` need under their scope."""
    d = dims(run["config"])
    if d is None:
        return None
    tokens = traced(scrapes, trace, TOKENS, kind)
    # decode: lane-steps, a served lane's state once in and once out a step;
    # prefill: the rows of the chunk programs, once a chunk
    served = traced(scrapes, trace, ACTIVE, kind)
    state = d["H"] * d["P"] * d["N"]
    token_bytes = (3 * d["I"] + 2 * d["N"] + d["H"]) * ITEMSIZE
    return (d["layers"] * (2.0 * served * state * STATE_ITEMSIZE
                           + tokens * token_bytes),
            d["layers"] * 4.0 * tokens * state, {kind: tokens})
