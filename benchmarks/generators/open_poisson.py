"""Arrival process ``open_poisson``: an open loop at a fixed rate.

``rate_per_s * seconds`` arrivals, rounded, all due inside the window. The
gaps are exponential and drawn from the mix's own ``lengths_seed``, scaled so
that they fill the window: every seed of a run offers the same arrivals (the
seed draws token ids and weights, see ``harness/traffic.py``). A request is
sent when it is due
whether or not earlier ones have finished; if the client itself runs late it
sends at once and the lateness is reported.

A generator file offers ``plan(params, seconds) -> {"block", "blocks"}`` (how
many requests make one block of the mix's fixed sizes, how many blocks to
build before the window) and ``async run(load)``.
"""

from __future__ import annotations

import numpy as np


def arrivals(params: dict, seconds: float) -> int:
    return max(1, round(float(params["rate_per_s"]) * seconds))


def plan(params: dict, seconds: float) -> dict:
    return {"block": arrivals(params, seconds), "blocks": 1}


def due_times(params: dict, seconds: float, fixed_seed: int) -> np.ndarray:
    n = arrivals(params, seconds)
    gaps = np.random.default_rng([int(fixed_seed), 0xa221]).exponential(
        1.0, n + 1)            # n before the arrivals, one after the last
    return np.cumsum(gaps[:n]) * (seconds / gaps.sum())


async def run(load) -> None:
    for due in due_times(load.params, load.seconds, load.fixed_seed):
        await load.sleep_until(float(due))
        load.send(load.take(), float(due))
