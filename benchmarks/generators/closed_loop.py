"""Arrival process ``closed_loop``: ``clients`` callers, each sending its
next request as soon as the last one has ended, until the window closes.
Requests begun inside the window are followed to their end. A request is
due the moment its client is free, so time to first token holds the wait
for a free lane: the loop's own doing, and no metric of such a cell.
"""

from __future__ import annotations

import asyncio


def plan(params: dict, seconds: float) -> dict:
    clients = int(params["clients"])
    return {"block": int(params.get("block", clients)),
            "blocks": int(params.get("prepared_blocks", 2))}


async def run(load) -> None:
    async def client() -> None:
        while load.now() < load.seconds:
            await load.send(load.take(), load.now())

    await asyncio.gather(*(client() for _ in range(int(load.params["clients"]))))
