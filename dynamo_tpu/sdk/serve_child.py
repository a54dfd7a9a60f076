"""Per-worker child entry for the `serve` orchestrator.

    python -m dynamo_tpu.sdk.serve_child pkg.module:ServiceClass \
        --store host:port

Instantiates the @service class, connects the distributed runtime, wires
``depends()`` clients, runs @async_on_start hooks, then serves every
@dynamo_endpoint on the service's component. Prints a READY line on stdout
once all endpoints are registered (the orchestrator gates on it).

Reference capability: deploy/dynamo/sdk/cli/serve_dynamo.py:96-190.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import logging
import sys
from typing import Type

from ..runtime.component import DistributedRuntime
from ..utils.logging_ext import init_logging
from .service import BoundClient, ServiceConfig, ServiceSpec

log = logging.getLogger("dynamo_tpu.sdk.child")

READY_MARKER = "DYNAMO_SERVICE_READY"


def load_class(spec: str) -> Type:
    mod_name, _, cls_name = spec.partition(":")
    mod = importlib.import_module(mod_name)
    cls = getattr(mod, cls_name)
    if not hasattr(cls, "_dynamo_spec"):
        raise SystemExit(f"{spec} is not a @service class")
    return cls


async def run_service(cls: Type, store: str,
                      ready_event=None) -> None:
    spec: ServiceSpec = cls._dynamo_spec
    host, port = store.split(":")
    drt = await DistributedRuntime(store_host=host,
                                   store_port=int(port)).connect()
    obj = cls()
    obj.runtime = drt
    obj.config = ServiceConfig.load().for_service(cls)
    obj._dyn_clients = {}
    for attr, dep in spec.dependencies.items():
        tspec: ServiceSpec = dep.target._dynamo_spec
        client = await drt.namespace(tspec.namespace) \
            .component(tspec.name).endpoint(dep.endpoint).client().start()
        obj._dyn_clients[attr] = BoundClient(client, dep.endpoint)
    for hook in spec.on_start:
        await getattr(obj, hook)()
    component = drt.namespace(spec.namespace).component(spec.name)
    for ep_name, attr in spec.endpoints.items():
        await component.endpoint(ep_name).serve(getattr(obj, attr))
    print(f"{READY_MARKER} {spec.name} worker={drt.worker_id:x}", flush=True)
    if ready_event is not None:
        ready_event.set()
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await drt.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser("dynamo-serve-child")
    ap.add_argument("service", help="pkg.module:ServiceClass")
    ap.add_argument("--store", default="127.0.0.1:4222")
    args = ap.parse_args(argv)
    import os

    init_logging()
    if os.environ.get("TPU_VISIBLE_DEVICES"):
        # the orchestrator granted this worker chips (sdk/allocator.py): it
        # builds an engine, and it must come up on them — a granted worker
        # that lands on the CPU is an error here, not a slow deployment
        from ..utils.jaxenv import init_compile_cache, on_tpu

        import jax

        init_compile_cache()
        chips = os.environ["TPU_VISIBLE_DEVICES"]
        if not on_tpu():
            raise SystemExit(
                f"{args.service}: granted TPU chips {chips} but jax came up "
                f"on {jax.devices()[0].platform!r}")
        print(f"{args.service}: granted TPU chips {chips}; jax sees "
              f"{jax.devices()}", flush=True)
    sys.path.insert(0, ".")
    # artifact-deployed graphs: the operator extracts the bundle and hands
    # its path down (deploy/artifacts.py)
    apath = os.environ.get("DYNAMO_ARTIFACT_PATH")
    if apath:
        # appended, matching load_entry: bundles must not shadow framework
        # or stdlib imports (and the worker must resolve the same code the
        # operator resolved)
        sys.path.append(apath)
    asyncio.run(run_service(load_class(args.service), args.store))


if __name__ == "__main__":
    main()
