"""Worker binary: serve an engine as a distributed endpoint.

    python -m dynamo_tpu.cli.worker --engine jax|echo --namespace dynamo \
        --component backend --store localhost:4222 [--model-path ...] \
        [--register-model NAME]

Serves ``generate`` (BackendInput -> EngineOutput stream), publishes KV cache
events on the component event plane, and refreshes ForwardPassMetrics in the
store under its lease (the aggregator scrapes the prefix). This is the
equivalent of a reference engine worker process: serve_endpoint + KV event
publisher + metrics publisher.
"""

from __future__ import annotations

import argparse

from ..utils.dynconfig import EnvDefaultsParser
import asyncio
import json
import logging
import time
from typing import Optional

from ..llm.disagg import (DisaggConfig, DisaggRouter, PrefillQueue,
                          RemotePrefillRequest)
from ..llm.kv_router.protocols import KV_EVENT_SUBJECT, ForwardPassMetrics
from ..llm.kv_router.publisher import KvEventPublisher
from ..llm.kv_transfer import (KV_RECEIVE_ENDPOINT, KvReceiver,
                               RemotePrefillError, stream_enabled)
from ..llm.model_card import ModelDeploymentCard
from ..llm.protocols.common import BackendInput
from ..llm.remote import register_model, serve_core_engine
from ..runtime.component import DistributedRuntime
from ..runtime.store_client import StoreError
from ..utils import overload, tracing

log = logging.getLogger("dynamo_tpu.worker")

from ..llm.metrics_aggregator import METRICS_PREFIX, metrics_key  # noqa: E402
# (canonical definitions live with the aggregator; re-exported here for
# backward compatibility with existing imports)


def run_follower(args) -> None:
    """Follower node (rank > 0) of a multi-host worker: join the global
    mesh via jax.distributed, build the identical engine core, then replay
    the leader's dispatch stream forever. No endpoint, no registration —
    the multi-host slice is ONE logical worker published by the leader."""
    from ..engine.engine import EngineCore
    from ..parallel.multihost import FollowerLoop, init_distributed

    init_distributed(args.coordinator, args.num_nodes, args.node_rank)
    cfg = _engine_cfg(args)
    core = EngineCore(cfg)
    leader_host = args.coordinator.split(":")[0]
    print(f"follower {args.node_rank}/{args.num_nodes} joined mesh; "
          f"replaying dispatches from {leader_host}:{args.dispatch_port}",
          flush=True)
    FollowerLoop(core, leader_host, args.dispatch_port).run()


def _build_card(args) -> ModelDeploymentCard:
    if args.model_path:
        card = ModelDeploymentCard.resolve(args.model_path, args.model_name)
    else:
        card = ModelDeploymentCard.synthetic(args.model_name or "echo")
    card.kv_block_size = args.kv_block_size
    return card


def _engine_cfg(args, card: Optional[ModelDeploymentCard] = None):
    from ..engine.engine import JaxEngineConfig

    if card is None:
        card = _build_card(args)
    extra = json.loads(args.extra_engine_args) if args.extra_engine_args else {}
    if getattr(args, "num_nodes", 1) > 1:
        # multi-host lockstep covers exactly the dispatch-hooked programs:
        # host-tier restores / disagg injection are per-leader device ops
        # and must stay off
        extra["enable_prefix_reuse"] = False
        extra["host_cache_blocks"] = 0
        extra["disk_cache_blocks"] = 0
    from ..llm import kv_cluster

    if kv_cluster.enabled():
        # cluster sharing needs sealed blocks mirrored to the host tier
        # (write-through) so peers can fetch prefixes that never saw
        # device eviction pressure; a no-op when host_cache_blocks=0
        extra.setdefault("cluster_writethrough", True)
    return JaxEngineConfig.from_card(card, tensor_parallel=args.tp, **extra)


async def _connect_drt(args) -> DistributedRuntime:
    host, port = args.store.split(":")
    return await DistributedRuntime(
        store_host=host, store_port=int(port),
        advertise_host=args.advertise_host).connect()


async def run_worker(args, *, ready_event: Optional[asyncio.Event] = None,
                     drt: Optional[DistributedRuntime] = None,
                     token=None) -> None:
    multihost = getattr(args, "num_nodes", 1) > 1
    publisher = None
    if multihost:
        if args.engine != "jax":
            raise SystemExit("--num-nodes > 1 requires --engine jax")
        if getattr(args, "enable_disagg", False):
            raise SystemExit("--enable-disagg is not supported with "
                             "--num-nodes > 1 yet")
        from ..parallel.multihost import DispatchPublisher, init_distributed

        init_distributed(args.coordinator, args.num_nodes, args.node_rank)
        publisher = DispatchPublisher(args.dispatch_port, args.num_nodes - 1)
    own_drt = drt is None
    if own_drt:
        drt = await _connect_drt(args)
    if token is not None:
        # reference semantics (etcd.rs:55-76): losing the liveness lease
        # cancels the worker — shut down cleanly so the orchestrator
        # restarts us with a fresh lease, instead of serving unroutably.
        # With store reconnect this is now the LAST resort: transient
        # connection loss re-establishes the session (lease re-granted
        # under the same id, endpoint keys re-put) and the worker keeps
        # serving; the callback fires only when the reconnect window is
        # exhausted or the server could not preserve our identity.
        def _lease_lost(lease: int) -> None:
            log.critical("liveness lease %x unrecoverably lost; "
                         "shutting down", lease)
            token.cancel()
        drt.store.on_lease_lost = _lease_lost

    def _session_replayed() -> None:
        log.warning("store session re-established: lease %x re-granted, "
                    "endpoint/model keys re-registered", drt.worker_id)
    drt.store.on_session_replayed = _session_replayed
    ns = drt.namespace(args.namespace)
    component = ns.component(args.component)

    # tracing: span context arrives over the wire (rpc spans) and via the
    # prefill queue; finished spans flush to the store so the frontend's
    # /v1/traces endpoint can stitch the cross-process timeline
    tracing.configure(component="decode_worker")
    span_sink = await tracing.StoreSpanSink(drt.store).start()

    # flight recorder + hang watchdog + incident coordination: the rings
    # mirror every finished span (head-sampled-out ones included), the
    # watchdog turns wedged decode dispatches / transfers / drains into
    # stall:* spans, and any cluster beacon freezes our rings into the
    # coordinated bundle. SIGUSR2 = manual capture (real process only).
    from .. import obs

    obs_handle = await obs.start_process(
        "decode_worker", store=drt.store, namespace=args.namespace,
        proc_label=f"decode_worker:{drt.worker_id:x}",
        span_sink=span_sink, install_signal=token is not None)

    # --- engine -------------------------------------------------------
    card = _build_card(args)

    core = None
    if args.engine == "jax":
        from ..engine.engine import JaxEngine

        cfg = _engine_cfg(args, card)
        # engine bring-up (jax init, weight load, device_put) can exceed the
        # lease TTL — run it off-loop so lease keepalives keep flowing
        engine = await asyncio.get_running_loop().run_in_executor(
            None, lambda: JaxEngine(cfg))
        core = engine.core
        if publisher is not None:
            # every follower must see the dispatch stream from the first
            # dispatch: block until the full slice has joined
            await asyncio.get_running_loop().run_in_executor(
                None, publisher.wait_for_followers)
            core.dispatch_hook = publisher.hook
            print(f"multi-host leader: {args.num_nodes - 1} followers "
                  f"in lockstep", flush=True)
    else:
        from ..llm.engines import EchoCoreEngine

        engine = EchoCoreEngine()

    # --- KV event publishing -----------------------------------------
    async def publish(subject, payload):
        await component.publish(subject, payload)

    pub = KvEventPublisher(worker_id=drt.worker_id, publish=publish,
                           subject=KV_EVENT_SUBJECT)
    await pub.start()
    if core is not None:
        core.pool.on_block_sealed = pub.block_stored
        core.pool.on_blocks_removed = pub.blocks_removed

    # --- cluster KV sharing (DYN_KV_CLUSTER=1) -----------------------
    # serve the kv_fetch donor endpoint over the host tier, publish this
    # worker's sealed-block registry record (lease-bound), and prefetch
    # donor-stamped prefixes before requests enter the engine
    from ..llm import kv_cluster

    cluster = None
    if core is not None and kv_cluster.enabled():
        cluster = await kv_cluster.KvClusterWorker.attach(
            component, drt, args.namespace, core)

    # --- serve endpoint ----------------------------------------------
    # worker-ingress overload gate (DYN_WORKER_SLOTS / DYN_WORKER_QUEUE_
    # DEPTH, unset = off): bounded, priority-ordered slot queue with
    # predictive shedding — excess load fails in milliseconds as a typed
    # 429 naming this stage instead of queueing into a deadline burn
    gate = overload.gate_from_env()
    endpoint = component.endpoint("generate")
    engine_ref = None         # set on the simple path (model mobility)
    served = None
    if getattr(args, "enable_disagg", False) and core is not None:
        # decode worker with conditional remote prefill (SURVEY §3.2):
        # long cold prompts go to the shared queue; KV comes back on the
        # kv_receive endpoint and the request enters decode directly
        queue = PrefillQueue(drt.store, args.namespace)
        drouter = await DisaggRouter(
            args.namespace,
            config=DisaggConfig(
                max_local_prefill_length=getattr(
                    args, "max_local_prefill_length", 1000),
                max_prefill_queue_size=getattr(
                    args, "max_prefill_queue_size", 2)),
        ).start(drt.store)
        receiver = KvReceiver(worker_id=drt.worker_id)
        await component.endpoint(KV_RECEIVE_ENDPOINT).serve(receiver.handler)

        remote_timeout = getattr(args, "remote_prefill_timeout", 120.0)

        from ..llm.kv_transfer import await_remote_kv as _await_kv

        async def await_remote_kv(ctx, fut):
            return await _await_kv(ctx, fut, queue, receiver,
                                   remote_timeout)

        async def generate_handler(request, ctx):
            bi = BackendInput.from_dict(request)
            if cluster is not None:
                # donor-stamped prefix fetch BEFORE the slot gate and the
                # local probe: the peer fetch overlaps the queue wait
                # instead of holding a bounded slot through up to the
                # fetch timeout of network I/O (same invariant as the
                # non-disagg path's prefetch-outside-the-gate wrap), and
                # the deposited blocks count as local prefix hits, so a
                # cluster-warm prompt prefills locally instead of paying
                # the remote-prefill queue for KV a peer already holds
                await cluster.fetcher.ensure_prefix(bi, ctx)
            if hasattr(engine, "prefetch_tiers"):
                # placement-driven h2d prefetch: the upload of matched
                # local tier blocks runs on an executor thread WHILE this
                # request waits at the slot gate below, so admission's
                # restore is a d2d scatter, not a critical-path h2d
                from ..utils.aiotasks import spawn_blocking
                spawn_blocking(engine.prefetch_tiers, bi,
                               name="h2d-prefetch")
            if gate is not None:
                await gate.acquire(ctx.priority, ctx.deadline)
                svc_started = time.monotonic()
                try:
                    async for item in _generate_disagg(bi, request, ctx):
                        yield item
                finally:
                    gate.release(time.monotonic() - svc_started)
            else:
                async for item in _generate_disagg(bi, request, ctx):
                    yield item

        async def _generate_disagg(bi, request, ctx):
            # local prefix-cache hits count against remoting: a prompt we
            # mostly have cached prefills locally regardless of length.
            # CROSS-THREAD CONTRACT: this runs on the asyncio thread while
            # the engine thread mutates the block pool. probe_prefix and
            # TieredKvCache.__contains__ are strictly READ-ONLY (no LRU
            # reorder), which is what makes the unlocked probe safe under
            # the GIL — do not swap in tiered.lookup() (it mutates LRU
            # order) without adding a lock.
            host = core.tiered
            prefix_hit = core.pool.probe_prefix(
                bi.token_ids, (lambda h: h in host) if host else None,
                # kv_salt: the salted chain VLM blocks are actually stored
                # under (falls back to lora_id for text-only requests)
                lora_id=bi.kv_salt or bi.lora_id)
            remote = False
            if drouter.length_exceeds_local(len(bi.token_ids), prefix_hit):
                # only candidates pay the queue-depth RPC
                qsize = await queue.size()
                remote = drouter.should_prefill_remote(
                    len(bi.token_ids), prefix_hit, qsize)
            tracer = tracing.get_tracer()
            if remote:
                # layer-streamed ingest (DYN_KV_STREAM): hand the receiver
                # an engine handle so each arriving layer's device scatter
                # is enqueued while later layers are still on the wire —
                # the future then resolves to the handle (not arrays) once
                # the final scatter is enqueued, never synced
                ingest = None
                if stream_enabled() and hasattr(engine, "kv_ingest"):
                    ingest = engine.kv_ingest(bi, ctx.id)
                # register interest BEFORE enqueueing: a fast prefill worker
                # may push the KV back before we'd otherwise start listening
                fut = receiver.expect(ctx.id, ingest=ingest)
                async with tracer.span("prefill.remote_wait",
                                       trace_id=ctx.id,
                                       prompt_tokens=len(bi.token_ids),
                                       prefix_hit_tokens=prefix_hit) as wsp:
                    remote_t0 = time.monotonic()
                    try:
                        await queue.enqueue(RemotePrefillRequest(
                            ctx.id, drt.worker_id, request,
                            deadline=ctx.deadline,
                            priority=ctx.priority))
                    except overload.OverloadError as e:
                        # bounded-queue / predictive shed at enqueue: the
                        # remote path is refused in milliseconds; local
                        # prefill (deadline-bounded) takes over
                        receiver.abandon(ctx.id)
                        log.info("prefill enqueue shed for %s (%s); "
                                 "prefilling locally", ctx.id, e.reason)
                        kv = None
                    else:
                        try:
                            kv = await await_remote_kv(ctx, fut)
                        except RemotePrefillError as e:
                            log.warning("remote prefill for %s dead-"
                                        "lettered (%s); prefilling "
                                        "locally", ctx.id, e)
                            kv = None
                        if kv is not None:
                            # the predictive shed needs PER-ITEM service
                            # time; the observed turnaround includes the
                            # queue wait behind ~qsize earlier jobs, so
                            # normalize by the depth seen at the remote
                            # decision — feeding raw turnaround would
                            # double-count the queue and self-reinforce
                            # (deeper queue -> bigger estimate -> shed)
                            queue.observe_service(
                                (time.monotonic() - remote_t0)
                                / max(qsize + 1, 1))
                    if wsp is not None:
                        wsp.attrs["fallback_local"] = kv is None
                        wsp.attrs["streamed"] = kv is not None \
                            and kv is ingest
                if kv is not None and ingest is not None and kv is ingest:
                    # the sequence is already entering decode; consume
                    # its output queue. An engine-side ingest failure
                    # surfaces BEFORE the first token as a typed error —
                    # fall through to local prefill, never a user error
                    try:
                        async with tracer.span("decode.stream",
                                               trace_id=ctx.id,
                                               injected=True,
                                               streamed=True):
                            async for out in engine.generate_streamed(
                                    bi, ctx, ingest):
                                yield out.to_dict()
                        return
                    except RemotePrefillError as e:
                        log.warning("streamed KV ingest for %s failed "
                                    "(%s); prefilling locally", ctx.id, e)
                        kv = None
                if kv is not None:
                    k, v, tok, logp = kv
                    async with tracer.span("decode.stream",
                                           trace_id=ctx.id, injected=True):
                        async for out in engine.generate_prefilled(
                                bi, ctx, k, v, tok, logp):
                            yield out.to_dict()
                    return
            async with tracer.span("decode.stream", trace_id=ctx.id,
                                   injected=False):
                async for out in engine.generate(bi, ctx):
                    yield out.to_dict()

        await endpoint.serve(generate_handler)
    else:
        # model mobility (simple path only: no disagg/cluster/multihost —
        # those keep the plain cold-spawn wake): handlers stream through
        # an EngineRef so a cold-reload fallback can rebind the engine
        if core is not None and not multihost and cluster is None:
            from ..fleet.mobility import EngineRef

            engine_ref = EngineRef(engine)
        base = engine_ref if engine_ref is not None else engine
        served = (base if gate is None
                  else overload.SlotGatedEngine(base, gate))
        if cluster is not None:
            # prefetch wraps OUTSIDE the slot gate: the peer fetch overlaps
            # the queue wait instead of holding a slot while blocks
            # stream, and the local-tier h2d prefetch uploads matched
            # blocks to device staging during the same wait
            served = cluster.wrap(
                served, prefetcher=getattr(engine, "prefetch_tiers", None))
        await serve_core_engine(endpoint, served)
    if args.register_model:
        await register_model(drt.store, card, endpoint.path,
                             model_type="chat", lease=drt.lease)
        await register_model(drt.store, card, endpoint.path,
                             model_type="completion", lease=drt.lease)

    # --- metrics loop -------------------------------------------------
    from ..llm.metrics_aggregator import StagePublisher

    stage_pub = StagePublisher(drt.store, args.namespace, args.component,
                               drt.worker_id, drt.lease)

    # --- model mobility agent (simple path only) ---------------------
    mobility = None
    if engine_ref is not None:
        from ..fleet.mobility import MobilityAgent

        async def _reregister(payload):
            """Post-swap identity change: fresh lease (prepare_drain
            revoked the old one), serve ``generate`` under the new
            model's component, re-advertise the model, and move the
            metrics/KV-event identity along."""
            nonlocal component, card, stage_pub
            import os

            drt.lease = await drt.store.lease_grant(
                ttl=float(os.environ.get("DYN_LEASE_TTL", "10.0")))
            drt.worker_id = drt.lease
            drt.draining.clear()
            if token is not None:
                drt.store.on_lease_lost = _lease_lost
            args.component = payload.get("component") or args.component
            args.model_path = payload.get("model_path") or args.model_path
            args.model_name = payload.get("model") or args.model_name
            component = ns.component(args.component)
            pub.worker_id = drt.worker_id
            card = _build_card(args)
            await serve_core_engine(component.endpoint("generate"),
                                    served)
            if args.register_model:
                ep_path = component.endpoint("generate").path
                await register_model(drt.store, card, ep_path,
                                     model_type="chat", lease=drt.lease)
                await register_model(drt.store, card, ep_path,
                                     model_type="completion",
                                     lease=drt.lease)
            stage_pub = StagePublisher(drt.store, args.namespace,
                                       args.component, drt.worker_id,
                                       drt.lease)
            log.info("worker %x re-registered as %s (%s)",
                     drt.worker_id, args.model_name, args.component)

        async def _cold_reload(new_cfg):
            """Typed swap-fallback: rebuild the engine off-loop (the
            weight load can exceed the lease TTL) and re-attach the KV
            event hooks. The EngineRef rebinding is the agent's job."""
            nonlocal engine, core
            from ..engine.engine import JaxEngine

            old = engine_ref.engine

            def _build():
                try:
                    old.shutdown()
                except Exception:  # noqa: BLE001 - the reload must
                    log.exception("engine shutdown during reload")
                return JaxEngine(new_cfg)

            new_engine = await asyncio.get_running_loop(
                ).run_in_executor(None, _build)
            engine = new_engine
            core = new_engine.core
            core.pool.on_block_sealed = pub.block_stored
            core.pool.on_blocks_removed = pub.blocks_removed
            return new_engine

        mobility = await MobilityAgent(
            drt, args.namespace, args.component, engine_ref,
            reregister=_reregister, cold_reload=_cold_reload,
            model_name=args.model_name or "").start()

    async def metrics_loop():
        while True:
            # recomputed per beat: a model swap moves this worker to a
            # new component + lease mid-life
            key = metrics_key(args.namespace, args.component,
                              drt.worker_id)
            if core is not None:
                m = ForwardPassMetrics(**core.utilization())
            else:
                # echo engine: real in-flight count (the planner's
                # occupancy signal), capacity from --echo-slots
                m = ForwardPassMetrics(
                    request_active_slots=len(drt._active),
                    request_total_slots=getattr(args, "echo_slots", 64))
            try:
                await drt.store.put(key, json.dumps(m.to_dict()).encode(),
                                    lease=drt.lease)
                await stage_pub.publish()
            except StoreError:
                # store mid-outage (reconnect in flight): skip the beat —
                # the session replay re-puts the last snapshot anyway
                log.debug("metrics publish skipped (store disconnected)")
            except Exception:
                log.exception("stage metrics publish failed")
            await asyncio.sleep(args.metrics_interval)

    mtask = asyncio.create_task(metrics_loop())
    log.info("worker %x serving %s", drt.worker_id, endpoint.path)
    print(f"worker {drt.worker_id:x} serving {endpoint.path}", flush=True)
    if ready_event is not None:
        ready_event.set()
    try:
        if token is not None:
            await token.wait()     # Worker shell: serve until shutdown signal
        else:
            while True:
                await asyncio.sleep(3600)
    finally:
        # never leave the lease-lost closure pointing at a token the
        # caller may repurpose after this worker exits (shared-drt case)
        drt.store.on_lease_lost = None
        mtask.cancel()
        await obs_handle.stop()
        try:
            await span_sink.stop()
        except Exception:
            log.warning("span sink final flush failed; tail spans lost",
                        exc_info=True)
        await pub.stop()
        # deregistration cleanup: drop the published metric snapshots and
        # this engine's per-worker gauge series so aggregators/dyntop stop
        # rendering a ghost worker when the process (or a shared runtime)
        # outlives this serve loop
        from ..llm.metrics_aggregator import clear_worker_keys

        await clear_worker_keys(drt.store, args.namespace, args.component,
                                drt.worker_id)
        if cluster is not None:
            try:
                await cluster.stop()   # cancel publisher, drop registry key
            except Exception:
                log.warning("kv-cluster detach failed", exc_info=True)
        if mobility is not None:
            mobility.cache.close()     # drop pinned host weight trees
        if core is not None:
            try:
                engine.shutdown()   # joins the engine thread, clears gauges
            except Exception:
                log.exception("engine shutdown failed")
        if own_drt:
            await drt.close()


def parse_args(argv=None) -> argparse.Namespace:
    p = EnvDefaultsParser(prog="dynamo-worker")
    p.add_argument("--engine", choices=("jax", "echo"), default="jax")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--store", default="127.0.0.1:4222")
    p.add_argument("--advertise-host", default=None)
    p.add_argument("--model-path", default=None)
    p.add_argument("--model-name", default=None)
    p.add_argument("--register-model", action="store_true")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--kv-block-size", type=int, default=64)
    p.add_argument("--metrics-interval", type=float, default=1.0)
    p.add_argument("--echo-slots", type=int, default=64,
                   help="advertised request slots of the echo engine "
                        "(its occupancy signal for the planner)")
    p.add_argument("--enable-disagg", action="store_true",
                   help="decode role: remote-prefill long cold prompts")
    p.add_argument("--max-local-prefill-length", type=int, default=1000)
    p.add_argument("--max-prefill-queue-size", type=int, default=2)
    p.add_argument("--remote-prefill-timeout", type=float, default=120.0)
    p.add_argument("--extra-engine-args", default=None,
                   help="inline JSON engine kwargs")
    # multi-host slice (one process per TPU host; rank 0 is the leader)
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--coordinator", default="127.0.0.1:9731",
                   help="jax.distributed coordinator host:port")
    p.add_argument("--dispatch-port", type=int, default=9732,
                   help="leader's dispatch-replay channel port")
    return p.parse_args(argv)


def main() -> None:
    from ..utils.logging_ext import init_logging

    init_logging()
    args = parse_args()
    if args.engine == "jax":
        from ..utils.jaxenv import init_compile_cache

        init_compile_cache()
    if args.num_nodes > 1 and args.node_rank > 0:
        run_follower(args)
        return
    # Worker shell: SIGINT/SIGTERM cancel the root token, in-flight requests
    # get stop (then kill after the grace window), leases revoke on close
    from ..runtime.worker import Worker

    shell = Worker()

    async def app(token):
        drt = await _connect_drt(args)
        shell.add_runtime(drt)
        await run_worker(args, drt=drt, token=token)

    try:
        shell.execute(app)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
