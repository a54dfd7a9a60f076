"""What the per-layer metrics of the engine loop and of a request's way to
its first token share: deltas of the program's own series between the two
scrapes. A program that has no such series (a parent commit from before
they existed) reads as no value, never as an error."""

from __future__ import annotations

from typing import Dict, Optional

from .launch import Series, delta

STAGES = "llm_request_stage_seconds"
PHASES = "dyn_engine_phase_seconds_total"
DISPATCHES = "dyn_engine_dispatches_total"
# the engine thread blocked on the device, and with nothing to do: every
# other phase is the host at work
WAITING = ("prefill_fetch", "decode_fetch")
IDLE = "idle"


def stage_mean_ms(scrapes: Dict[str, Series], stage: str) -> Optional[float]:
    """Mean of one stage of ``llm_request_stage_seconds`` over the requests
    that passed it between the scrapes, in milliseconds."""
    b, a = scrapes["before"], scrapes["after"]
    n = delta(b, a, STAGES + "_count", stage=stage)
    if n <= 0:
        return None
    return 1e3 * delta(b, a, STAGES + "_sum", stage=stage) / n


def phase_seconds(scrapes: Dict[str, Series]) -> Dict[str, float]:
    """Engine-thread seconds by phase between the scrapes (phases that did
    not move are left out)."""
    b, a = scrapes["before"], scrapes["after"]
    names = {l.get("phase") for n, l, _ in a if n == PHASES}
    moved = {p: delta(b, a, PHASES, phase=p) for p in names if p}
    return {p: s for p, s in moved.items() if s > 0}
