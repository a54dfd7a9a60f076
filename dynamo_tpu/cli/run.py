"""Single-binary style launcher: ``python -m dynamo_tpu.cli.run in=... out=...``

Input modes:  http | text | stdin | batch:<file.jsonl> | none
Output modes: echo_core | echo_full | jax | pystr:<file.py> |
pytok:<file.py> | dyn://<ns.component.endpoint>

Reference capability: launch/dynamo-run (lib.rs:53-456, opt.rs, flags.rs,
input/{http,text,batch}.rs) — the in=X out=Y matrix, model flags, and the
built-in batch load generator.
"""

from __future__ import annotations

import argparse

from ..utils.dynconfig import EnvDefaultsParser
import asyncio
import gc
import json
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

from ..llm.http_service import HttpService, ModelManager, ServedModel
from ..llm.model_card import ModelDeploymentCard
from ..llm.pipeline import build_chat_engine, build_completion_engine
from ..llm.protocols.openai import (
    ChatCompletionRequest,
    aggregate_chat_chunks,
)
from ..runtime.engine import AsyncEngine, Context


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = EnvDefaultsParser(prog="dynamo-run")
    p.add_argument("positional", nargs="*",
                   help="in=<mode> out=<engine> (order-free)")
    p.add_argument("--model-path", default=None)
    p.add_argument("--model-name", default=None)
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--http-host", default="0.0.0.0")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--kv-block-size", type=int, default=64)
    p.add_argument("--context-length", type=int, default=None)
    p.add_argument("--max-tokens", type=int, default=128,
                   help="default max tokens for text/batch modes")
    p.add_argument("--concurrency", type=int, default=8,
                   help="batch mode concurrency")
    p.add_argument("--extra-engine-args", default=None,
                   help="extra engine kwargs: a JSON file path, or inline "
                        "JSON if the value starts with '{'")
    p.add_argument("--store", default="127.0.0.1:4222",
                   help="dynstore host:port (out=dyn:// remote mode)")
    p.add_argument("--connect-timeout", type=float, default=30.0,
                   help="seconds to wait for a live out=dyn:// instance")
    args = p.parse_args(argv)
    args.input, args.output = "text", "echo_core"
    for tok in args.positional:
        if tok.startswith("in="):
            args.input = tok[3:]
        elif tok.startswith("out="):
            args.output = tok[4:]
        else:
            p.error(f"unrecognized argument {tok!r}")
    return args


def make_card(args) -> ModelDeploymentCard:
    if args.model_path:
        card = ModelDeploymentCard.resolve(args.model_path, args.model_name)
    else:
        card = ModelDeploymentCard.synthetic(args.model_name or args.output)
    if args.context_length:
        card.context_length = args.context_length
    card.kv_block_size = args.kv_block_size
    return card


def make_engines(args, card: ModelDeploymentCard):
    """Returns (chat_engine, completion_engine) at the OpenAI level."""
    out = args.output
    if out in ("echo_core", "echo_full"):
        return (build_chat_engine(card, out), build_completion_engine(card, out))
    if out == "jax":
        try:
            from ..engine.engine import JaxEngine, JaxEngineConfig
        except ImportError as e:
            raise SystemExit(f"out=jax engine unavailable: {e}")

        extra: Dict[str, Any] = {}
        if args.extra_engine_args:
            if args.extra_engine_args.lstrip().startswith("{"):
                extra = json.loads(args.extra_engine_args)
            else:
                with open(args.extra_engine_args) as f:
                    extra = json.load(f)
        cfg = JaxEngineConfig.from_card(
            card, tensor_parallel=args.tensor_parallel_size, **extra)
        core = JaxEngine(cfg)
        return (build_chat_engine(card, "core", core),
                build_completion_engine(card, "core", core))
    if out.startswith(("pystr:", "pytok:")):
        from ..llm.python_engine import PythonEngineError, build_python_engines

        try:
            return build_python_engines(out, card)
        except PythonEngineError as e:
            raise SystemExit(str(e))
    if out.startswith("dyn://"):
        # async connect: handled by connect_remote_engines in amain
        raise AssertionError("dyn:// handled before make_engines")
    raise SystemExit(f"unknown out={out}")


async def connect_remote_engines(args, card: ModelDeploymentCard):
    """``out=dyn://ns.component.endpoint`` — drive a REMOTE worker's core
    engine over the runtime data plane (ref dynamo-run's remote client
    mode, launch/dynamo-run/src/lib.rs in=..., out=dyn://)."""
    from ..llm.remote import RemoteCoreEngine
    from ..runtime.component import DistributedRuntime

    path = args.output[len("dyn://"):]
    parts = path.split(".")
    if len(parts) != 3:
        raise SystemExit(f"out=dyn://{path}: expected ns.component.endpoint")
    host, _, port = args.store.partition(":")
    drt = await DistributedRuntime(store_host=host or "127.0.0.1",
                                   store_port=int(port or 4222)).connect()
    client = await (drt.namespace(parts[0]).component(parts[1])
                    .endpoint(parts[2]).client().start())
    try:
        await client.wait_for_instances(1, timeout=args.connect_timeout)
    except TimeoutError as e:
        raise SystemExit(f"out={args.output}: {e}")
    core = RemoteCoreEngine(client)
    return (build_chat_engine(card, "core", core),
            build_completion_engine(card, "core", core))


# ---------------------------------------------------------------------------
# input modes
# ---------------------------------------------------------------------------

async def run_http(args, card, chat_engine, completion_engine) -> None:
    from ..utils.tracing import configure as configure_tracing

    configure_tracing(component="http")
    manager = ModelManager()
    manager.add(ServedModel(card, chat_engine, completion_engine))
    svc = HttpService(manager, host=args.http_host, port=args.http_port)
    port = await svc.start()
    # What the process holds now lives as long as it serves: the bucket
    # programs, their jaxprs and tracing caches, the routes, hundreds of
    # thousands of objects. A full collection walks all of them with every
    # thread stopped (0.1 s with nine programs on a desktop CPU, PR 51),
    # and the first few come early in a server's life, when the survivors
    # of the first requests are a quarter of what is there: an open-loop
    # cell of 32 requests read its tpot_p90_ms 3 % apart depending on
    # whether one fell into the one request that decides it. Out of the
    # collector's sight with them; what requests leave is collected as ever
    gc.collect()
    gc.freeze()
    print(f"dynamo_tpu http frontend listening on :{port} "
          f"(model={card.name}, out={args.output})", flush=True)
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await svc.stop()


async def _ask(chat_engine: AsyncEngine, card, prompt: str, max_tokens: int,
               stream_out=True) -> str:
    req = ChatCompletionRequest.from_dict({
        "model": card.name,
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
    })
    parts: List[str] = []
    async for ch in chat_engine.generate(req, Context()):
        if "event" in ch:
            continue
        delta = ch["choices"][0].get("delta", {})
        if delta.get("content"):
            parts.append(delta["content"])
            if stream_out:
                print(delta["content"], end="", flush=True)
    if stream_out:
        print()
    return "".join(parts)


async def run_text(args, card, chat_engine, _completion_engine) -> None:
    print(f"dynamo_tpu interactive ({card.name}). Ctrl-D to exit.")
    loop = asyncio.get_running_loop()
    while True:
        try:
            line = await loop.run_in_executor(None, lambda: input("> "))
        except EOFError:
            return
        if line.strip():
            await _ask(chat_engine, card, line, args.max_tokens)


async def run_stdin(args, card, chat_engine, _c) -> None:
    data = sys.stdin.read()
    if data.strip():
        await _ask(chat_engine, card, data, args.max_tokens)


async def run_batch(args, card, chat_engine, _c, path: str) -> Dict[str, Any]:
    """JSONL load generator: one {"text": ...} (or {"prompt": ...}) per line.
    Reports latency/throughput stats (the built-in perf harness)."""
    prompts = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                d = json.loads(line)
                prompts.append(d.get("text") or d.get("prompt") or "")
    sem = asyncio.Semaphore(args.concurrency)
    latencies: List[float] = []
    ttfts: List[float] = []
    tokens_out = 0

    async def one(prompt: str):
        nonlocal tokens_out
        async with sem:
            t0 = time.monotonic()
            first: Optional[float] = None
            req = ChatCompletionRequest.from_dict({
                "model": card.name,
                "messages": [{"role": "user", "content": prompt}],
                "max_tokens": args.max_tokens,
            })
            async for ch in chat_engine.generate(req, Context()):
                if "event" in ch:
                    continue
                if first is None:
                    first = time.monotonic() - t0
                u = ch.get("usage")
                if u:
                    tokens_out += u["completion_tokens"]
            latencies.append(time.monotonic() - t0)
            ttfts.append(first if first is not None else 0.0)

    t_start = time.monotonic()
    await asyncio.gather(*(one(p) for p in prompts))
    wall = time.monotonic() - t_start
    stats = {
        "requests": len(prompts),
        "wall_s": round(wall, 3),
        "req_per_s": round(len(prompts) / wall, 2) if wall else None,
        "tokens_out": tokens_out,
        "tok_per_s": round(tokens_out / wall, 1) if wall else None,
        "p50_latency_s": round(statistics.median(latencies), 4) if latencies else None,
        "p50_ttft_s": round(statistics.median(ttfts), 4) if ttfts else None,
        "p99_latency_s": round(sorted(latencies)[int(0.99 * (len(latencies) - 1))], 4)
        if latencies else None,
    }
    print(json.dumps(stats), flush=True)
    return stats


async def amain(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    if args.output == "jax":
        from ..utils.jaxenv import init_compile_cache

        init_compile_cache()
    card = make_card(args)
    if args.output.startswith("dyn://"):
        chat_engine, completion_engine = await connect_remote_engines(args,
                                                                      card)
    else:
        chat_engine, completion_engine = make_engines(args, card)
    mode = args.input
    if mode == "http":
        await run_http(args, card, chat_engine, completion_engine)
    elif mode == "text":
        await run_text(args, card, chat_engine, completion_engine)
    elif mode == "stdin":
        await run_stdin(args, card, chat_engine, completion_engine)
    elif mode.startswith("batch:"):
        await run_batch(args, card, chat_engine, completion_engine,
                        mode.split(":", 1)[1])
    elif mode == "none":
        print("engine initialized; no input mode (in=none)")
    else:
        raise SystemExit(f"unknown in={mode}")


def main() -> None:
    from ..utils.logging_ext import init_logging

    init_logging()
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
