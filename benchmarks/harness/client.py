"""The streaming client: one request over SSE, timed on this process's clock.

The SSE reading is ``bench_system._stream_one``'s (judged sound); what is new
is that every time is kept as an absolute clock reading, so that latency can
be counted from when the request was DUE, not from when it was sent.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from .modeldir import count_tokens
from .traffic import Request


@dataclass
class Result:
    idx: int
    due: float                       # when the schedule wanted it sent
    sent: float = 0.0                # when the client began to send it
    first: Optional[float] = None    # first token seen
    last: Optional[float] = None     # last token seen
    chunks: List[Tuple[float, int]] = field(default_factory=list)
    status: int = 0
    prompt_tokens: int = -1
    completion_tokens: int = -1
    finish: Optional[str] = None
    error: Optional[str] = None
    text: List[str] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    want_prompt: int = 0
    want_out: int = 0

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.chunks)

    def ok(self) -> bool:
        """(a) of ``correct``: HTTP 200, the tokens asked for, the prompt as
        sent, finished by length, and every token seen on the stream."""
        return (self.error is None and self.status == 200
                and self.finish == "length"
                and self.prompt_tokens == self.want_prompt
                and self.completion_tokens == self.want_out
                and self.tokens == self.want_out)


def new_result(req: Request, due: float) -> Result:
    return Result(req.idx, due, want_prompt=len(req.prompt),
                  want_out=req.out_tokens)


async def stream_one(session, url: str, req: Request, res: Result,
                     keep_text: bool = False) -> Result:
    """Send one request and follow its stream to the end, filling ``res``.
    Never raises for the request's own failure: that is the result. (A
    cancellation passes through; the caller marks what it cut.)"""
    res.sent = time.monotonic()
    try:
        async with session.post(url, data=req.body, headers={
                "Content-Type": "application/json"}) as resp:
            res.status = resp.status
            if resp.status != 200:
                res.error = (await resp.text())[:300]
                return res
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.monotonic()
                data = raw[5:].strip()
                if data == b"[DONE]":
                    break
                ch = json.loads(data)
                if "error" in ch:
                    res.error = str(ch["error"])[:300]
                    break
                if "usage" in ch:
                    res.prompt_tokens = ch["usage"]["prompt_tokens"]
                    res.completion_tokens = ch["usage"]["completion_tokens"]
                for c in ch.get("choices", ()):
                    text = c.get("text") or ""
                    n = count_tokens(text)
                    if n:
                        if res.first is None:
                            res.first = now
                        res.last = now
                        res.chunks.append((now, n))
                        if keep_text:
                            res.text.append(text)
                            lp = c.get("logprobs") or {}
                            res.logprobs.extend(lp.get("token_logprobs", ()))
                    if c.get("finish_reason"):
                        res.finish = c["finish_reason"]
    except Exception as e:  # noqa: BLE001 - a failed request is a result
        res.error = f"{type(e).__name__}: {e}"[:300]
    return res
