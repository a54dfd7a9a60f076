"""What a window leaves behind for whoever asks why two runs differ: every
request's record on the client's clock and what the engine's counters gained
between the two scrapes, in ``<scratch>/window_records.json`` (some tens of
KB, overwritten by the cell's next run). No metric reads it. PR 51 and PR 52
found with such records that a tail of 32 requests is ONE request's gap and
that it moves by whole prefill chunks."""

from __future__ import annotations

import json
from typing import Any, Dict

from . import launch
from .client import Result

KEPT = ("dyn_moe_", "dyn_engine_phase_seconds", "dyn_engine_dispatches",
        "dyn_engine_dispatch_tokens", "llm_decode_step_seconds_sum",
        "llm_decode_step_seconds_count")


def counters(series: launch.Series) -> Dict[str, float]:
    return {n + json.dumps(l, sort_keys=True): v for n, l, v in series
            if n.startswith(KEPT)}


def gained(before: Dict[str, float],
           after: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def request(r: Result, t0: float) -> Dict[str, Any]:
    return {"idx": r.idx, "prompt": r.want_prompt, "tokens": r.tokens,
            "ok": r.ok(), "due": r.due - t0, "sent": r.sent - t0,
            "first": None if r.first is None else r.first - t0,
            "last": None if r.last is None else r.last - t0,
            "bursts": len(r.chunks),
            "widest_gap_s": max((y[0] - x[0] for x, y in
                                 zip(r.chunks, r.chunks[1:])), default=0.0)}


def keep(path: str, window: Dict[str, Any], before: launch.Series,
         after: launch.Series) -> None:
    with open(path, "w") as f:
        json.dump({"requests": [request(r, window["t0"])
                                for r in window["results"]],
                   "ended_s": window["ended_s"],
                   "gained": gained(counters(before), counters(after))}, f)
