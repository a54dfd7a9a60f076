"""The one general generator of requests, driven by a traffic file.

A traffic file (``traffic/<mix>.json``) names an arrival generator
(``generators/<kind>.py``), a topology and the parameters of both, and gives
the prompt and output lengths as distributions. Lengths are drawn from the
file's own ``lengths_seed``, never from ``--seed``: every seed gets the SAME
multiset of sizes in another order, with other token ids, so the work of a
run does not depend on the seed. Token ids are uniform over the vocabulary
from ``--seed`` (no two prompts share a prefix beyond chance).
"""

from __future__ import annotations

import json
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List

import numpy as np

from .catalog import BenchError


@dataclass
class Request:
    idx: int
    prompt: List[int]
    out_tokens: int
    body: bytes            # the JSON payload, serialised before the window


def draw_lengths(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """n whole lengths from ``{"dist": "lognormal"|"uniform"|"fixed", ...}``,
    clipped to ``min``..``max``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(rng.normal(np.log(float(spec["median"])),
                              float(spec["sigma"]), n))
    elif dist == "uniform":
        x = rng.integers(lo, hi + 1, n).astype(float)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise BenchError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def completion_body(model: str, prompt: List[int], out_tokens: int,
                    **extra: Any) -> bytes:
    """Exact length, greedy, streamed: ``ignore_eos`` so that a sampled stop
    id cannot shorten the work."""
    return json.dumps({"model": model, "prompt": prompt,
                       "max_tokens": out_tokens, "temperature": 0,
                       "ignore_eos": True, "stream": True, **extra}).encode()


class RequestSource:
    """Requests in blocks: each block is the mix's fixed list of sizes, in its
    fixed order, with token ids from the run's seed. An open loop takes one
    block of exactly its number of arrivals; a closed loop takes block after
    block."""

    def __init__(self, mix: Dict[str, Any], vocab_size: int, model: str,
                 seed: int, block: int):
        fixed = np.random.default_rng(int(mix.get("lengths_seed", 0)))
        self.prompt_lengths = draw_lengths(mix["prompt_tokens"], block, fixed)
        self.output_lengths = draw_lengths(mix["output_tokens"], block, fixed)
        self.vocab, self.model = int(vocab_size), model
        self._rng = np.random.default_rng([int(seed), 0x7ea])
        self._n = 0
        self._pending: Deque[Request] = deque()

    def _block(self) -> List[Request]:
        out = []
        for j in range(len(self.prompt_lengths)):
            prompt = self._rng.integers(
                0, self.vocab, int(self.prompt_lengths[j])).tolist()
            n_out = int(self.output_lengths[j])
            out.append(Request(self._n, prompt, n_out,
                               completion_body(self.model, prompt, n_out)))
            self._n += 1
        return out

    def prepare(self, blocks: int) -> None:
        """Build ``blocks`` blocks now, before the window opens."""
        for _ in range(blocks):
            self._pending.extend(self._block())

    def next(self) -> Request:
        if not self._pending:
            self._pending.extend(self._block())
        return self._pending.popleft()

    def sizes(self) -> Dict[str, Any]:
        return {"requests_per_block": len(self.prompt_lengths),
                "prompt_tokens_sum": int(self.prompt_lengths.sum()),
                "output_tokens_sum": int(self.output_lengths.sum()),
                "prompt_min_max": [int(self.prompt_lengths.min()),
                                   int(self.prompt_lengths.max())]}
