"""The measured window: one asyncio loop in the harness's own process drives
the load a generator file describes and keeps every request's record."""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List, Optional, Tuple

from . import launch
from .client import Result, new_result, stream_one
from .traffic import Request, RequestSource


class Load:
    """What a generator file sees: the clock of the window, the next request
    of the mix, and ``send``."""

    def __init__(self, session, url: str, source: RequestSource,
                 params: Dict[str, Any], seconds: float, seed: int,
                 fixed_seed: int):
        self.session, self.url, self.source = session, url, source
        self.params, self.seconds = params, float(seconds)
        self.seed, self.fixed_seed = int(seed), int(fixed_seed)
        self.results: List[Result] = []
        self.tasks: List[asyncio.Task] = []
        self.t0 = time.monotonic()

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def sleep_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            await asyncio.sleep(d)

    def take(self) -> Request:
        return self.source.next()

    def send(self, req: Request, due: float) -> asyncio.Task:
        """Start one request, due at ``due`` seconds into the window; the
        task ends when its stream does."""
        res = new_result(req, self.t0 + due)
        self.results.append(res)
        task = asyncio.ensure_future(
            stream_one(self.session, self.url, req, res))
        self.tasks.append(task)
        return task


async def _sample_metrics(base: str, every: float, out: List[Tuple[float, Any]],
                          t0: float) -> None:
    """Scrape ``/metrics`` every ``every`` seconds (traced runs only)."""
    loop = asyncio.get_running_loop()
    while True:
        await asyncio.sleep(every)
        try:
            series = await loop.run_in_executor(None, launch.scrape, base)
        except Exception:  # noqa: BLE001 - a missed sample is no failure
            continue
        out.append((time.monotonic() - t0, series))


async def drive_window(generator, base: str, source: RequestSource,
                       params: Dict[str, Any], seconds: float, seed: int,
                       fixed_seed: int, drain_s: float,
                       sample_every: Optional[float] = None) -> Dict[str, Any]:
    """Run the generator for ``seconds``, follow every request begun in the
    window to its end or to the drain limit, and return the records."""
    import aiohttp

    samples: List[Tuple[float, Any]] = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        load = Load(session, base + "/v1/completions", source, params,
                    seconds, seed, fixed_seed)
        sampler = (asyncio.ensure_future(_sample_metrics(
            base, sample_every, samples, load.t0)) if sample_every else None)
        gen_task = asyncio.ensure_future(generator.run(load))

        async def in_flight_at_close() -> int:
            await load.sleep_until(seconds)
            return sum(not t.done() for t in load.tasks)

        at_close = asyncio.ensure_future(in_flight_at_close())
        limit = seconds + drain_s
        try:
            await asyncio.wait_for(asyncio.shield(gen_task),
                                   max(0.0, limit - load.now()))
        except asyncio.TimeoutError:
            pass
        pending = [t for t in load.tasks if not t.done()]
        if pending:
            await asyncio.wait(pending, timeout=max(0.0, limit - load.now()))
        cut = [t for t in [gen_task, *load.tasks] if not t.done()]
        for t in cut:
            t.cancel()
        await asyncio.gather(gen_task, *load.tasks, return_exceptions=True)
        ended = load.now()
        in_flight = await at_close
        if sampler:
            sampler.cancel()
            await asyncio.gather(sampler, return_exceptions=True)
    for r in load.results:
        if r.finish is None and r.error is None:
            r.error = "unfinished at the drain limit"
    return {"results": load.results, "t0": load.t0, "seconds": float(seconds),
            "ended_s": ended, "samples": samples,
            "in_flight_at_close": in_flight}


async def serve_samples(base: str, requests: List[Request]) -> List[Result]:
    """The correctness sample: all at once (so decode runs them as lanes of
    one batch), text and log-probabilities kept."""
    import aiohttp

    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=300)) as session:
        results = [new_result(r, time.monotonic()) for r in requests]
        await asyncio.gather(*(
            stream_one(session, base + "/v1/completions", rq, rs,
                       keep_text=True) for rq, rs in zip(requests, results)))
    return results
