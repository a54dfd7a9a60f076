"""The reduction from the client's records to numbers. Every time is on the
client's clock and counted from when the request was DUE."""

from __future__ import annotations

from typing import Iterable, List

from .client import Result


def completed(results: Iterable[Result]) -> List[Result]:
    return [r for r in results if r.ok()]


def ttft_ms(results: Iterable[Result]) -> List[float]:
    """Due time -> first token, per correctly completed request."""
    return [(r.first - r.due) * 1e3 for r in completed(results)]


def tpot_ms(results: Iterable[Result]) -> List[float]:
    """(last token - first token) / (tokens - 1), per completed request of
    two tokens or more. Tokens reach the client in bursts of the engine's
    ``decode_steps``, so the gap is taken over the request, not per token."""
    return [(r.last - r.first) * 1e3 / (r.tokens - 1)
            for r in completed(results) if r.tokens > 1]


def lateness_ms(results: Iterable[Result]) -> List[float]:
    """How late the generator itself sent each request."""
    return [(r.sent - r.due) * 1e3 for r in results]


def tokens_in_window(results: Iterable[Result], t0: float, t1: float) -> int:
    """Output tokens that reached the client inside [t0, t1], of requests
    that went on to complete correctly: all the work of the window, and
    nothing a failed request produced."""
    return sum(n for r in completed(results) for t, n in r.chunks
               if t0 <= t <= t1)
