"""Churn robustness: store reconnect + session re-establishment, end-to-end
deadlines at every stage, the instance circuit breaker, and graceful drain.

Everything here is deterministic and in-process (tier-1): the restartable
store fixture kills every connection on stop() — the kill -9 analogue — and
restart() brings an EMPTY server back on the same port, so session replay
must reconstruct leases, keys, watches and subscriptions from client state.
The multi-process kill -9 soak lives in scripts/chaos_soak.py (markers:
slow + chaos).
"""

import asyncio
import contextlib
import os
import time

import pytest

from dynamo_tpu.runtime import deadline as dl
from dynamo_tpu.runtime.circuit_breaker import InstanceBreaker
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.engine import Context, EngineError
from dynamo_tpu.runtime.store_client import (ReconnectConfig, StoreClient,
                                             StoreError)
from dynamo_tpu.runtime.store_server import PyStoreServer
from dynamo_tpu.utils.prometheus import stage_metrics

FAST = ReconnectConfig(enabled=True, attempts=40, base=0.02, max_delay=0.1)
OFF = ReconnectConfig(enabled=False)


@contextlib.contextmanager
def fast_reconnect_env():
    """DistributedRuntime builds its StoreClient from env: shrink the
    backoff so restart tests converge in well under a second."""
    saved = {k: os.environ.get(k) for k in
             ("DYN_STORE_RECONNECT_ATTEMPTS", "DYN_STORE_RECONNECT_BASE",
              "DYN_STORE_RECONNECT_MAX")}
    os.environ.update({"DYN_STORE_RECONNECT_ATTEMPTS": "40",
                       "DYN_STORE_RECONNECT_BASE": "0.02",
                       "DYN_STORE_RECONNECT_MAX": "0.1"})
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


class RestartableStore:
    """In-proc dynstore that can die (connections reset, state lost) and
    come back empty on the SAME port — deterministic kill -9."""

    def __init__(self):
        self.server = None
        self.port = None

    async def start(self) -> int:
        self.server = PyStoreServer(port=self.port or 0)
        self.port = await self.server.start()
        return self.port

    async def stop(self) -> None:
        await self.server.stop()

    async def restart(self, down_for: float = 0.0) -> None:
        await self.stop()
        if down_for:
            await asyncio.sleep(down_for)
        await self.start()


async def until(predicate, timeout: float = 5.0, msg: str = "condition"):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        await asyncio.sleep(0.02)


# ---------------------------------------------------------------------------
# store reconnect + session re-establishment
# ---------------------------------------------------------------------------

async def test_pending_calls_fail_fast_on_connection_loss():
    """Satellite: futures parked in _pending must be rejected the moment the
    rx loop dies — even with reconnect disabled, callers get a typed error
    instead of hanging forever."""
    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=OFF).connect()
    try:
        pull = asyncio.ensure_future(c.q_pull("never"))   # parks server-side
        await asyncio.sleep(0.05)
        await store.stop()
        with pytest.raises(StoreError) as ei:
            await asyncio.wait_for(pull, 2.0)
        assert ei.value.code == "conn_lost"
        # and NEW calls on the dead client fail fast too
        with pytest.raises(StoreError):
            await asyncio.wait_for(c.put("k", b"v"), 2.0)
    finally:
        await c.close()


async def test_reconnect_backoff_restores_service():
    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=FAST).connect()
    try:
        await c.put("a", b"1")
        await store.restart(down_for=0.1)
        # during/after the outage nothing hangs: calls either fail fast
        # (typed) or succeed once the session is back
        await asyncio.wait_for(c.wait_connected(), 5.0)
        await c.put("b", b"2")
        assert await c.get("b") == b"2"
        from dynamo_tpu.utils.prometheus import stage_metrics
        assert stage_metrics().store_reconnects.get("ok") >= 1
    finally:
        await c.close()
        await store.stop()


async def test_reconnect_window_exhaustion_fires_lease_lost():
    store = RestartableStore()
    port = await store.start()
    cfg = ReconnectConfig(enabled=True, attempts=3, base=0.02,
                          max_delay=0.05)
    c = await StoreClient(port=port, reconnect=cfg).connect()
    lost = asyncio.Event()
    c.on_lease_lost = lambda lease: lost.set()
    try:
        await c.lease_grant(ttl=0.5)     # fast keepalive beats
        await store.stop()               # and never comes back
        await asyncio.wait_for(lost.wait(), 5.0)
        assert c.closed.is_set()
    finally:
        await c.close()


async def test_deliberate_revoke_never_fires_lease_lost():
    """The model-mobility identity handoff: revoke lease A, grant lease B,
    keep serving. Lease A's orphaned keepalive beat must not read the
    revoke as a LOSS and kill the freshly swapped worker (the callback is
    re-armed by then)."""
    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=FAST).connect()
    lost = asyncio.Event()
    try:
        old = await c.lease_grant(ttl=0.3)    # beats every 0.1s
        await c.lease_revoke(old)
        new = await c.lease_grant(ttl=0.3)
        c.on_lease_lost = lambda lease: lost.set()   # swap re-arms it
        await asyncio.sleep(1.0)              # several orphaned beats
        assert not lost.is_set()
        await c.put("swap/alive", b"x", lease=new)
        assert await c.get("swap/alive") == b"x"
    finally:
        await c.close()
        await store.stop()


async def test_lease_regrant_preserves_id_and_keys():
    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=FAST).connect()
    lost = asyncio.Event()
    c.on_lease_lost = lambda lease: lost.set()
    try:
        lease = await c.lease_grant(ttl=0.6)   # several beats per second
        await c.put("lr/reg", b"me", lease=lease)
        await store.restart(down_for=0.05)
        await asyncio.wait_for(c.wait_connected(), 5.0)
        # identity preserved: same lease id, key re-put, keepalives healthy
        probe = await StoreClient(port=port, reconnect=OFF).connect()
        assert await probe.get("lr/reg") == b"me"
        # a FRESH grant on the restarted store must never collide with an
        # id a pre-restart session still holds (reuse would adopt it and
        # the lease would have two owners)
        fresh = await probe.lease_grant(ttl=5.0, auto_keepalive=False)
        assert fresh != lease
        await asyncio.sleep(1.0)               # >1 keepalive beat
        assert not lost.is_set(), "healthy re-granted lease reported lost"
        assert await probe.get("lr/reg") == b"me"   # ttl kept alive
        await probe.close()
        assert stage_metrics().lease_regrants.get() >= 1
    finally:
        await c.close()
        await store.stop()


async def test_watch_replay_synthesizes_missed_deletes():
    store = RestartableStore()
    port = await store.start()
    other = await StoreClient(port=port, reconnect=OFF).connect()
    c = await StoreClient(port=port, reconnect=FAST).connect()
    events = []
    try:
        await other.put("wr/x", b"1")          # someone else's key
        await other.put("wr/y", b"1")

        async def on_event(key, value, deleted):
            events.append((key, value, deleted))

        snap = await c.watch_prefix("wr/", on_event)
        assert len(snap) == 2
        await other.close()
        # store dies with the keys; restart comes back EMPTY: the watcher
        # missed the (implicit) deletes and must have them synthesized
        await store.restart(down_for=0.05)
        await asyncio.wait_for(c.wait_connected(), 5.0)
        await until(lambda: ("wr/x", None, True) in events
                    and ("wr/y", None, True) in events,
                    msg="synthetic deletes")
        # the re-armed watch is live: a new put still streams
        probe = await StoreClient(port=port, reconnect=OFF).connect()
        await probe.put("wr/z", b"2")
        await until(lambda: ("wr/z", b"2", False) in events,
                    msg="live event after replay")
        await probe.close()
    finally:
        await c.close()
        await store.stop()


async def test_subscribe_and_qpull_resume_after_restart():
    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=FAST).connect()
    got_msgs = []
    try:
        async def on_msg(subject, payload):
            got_msgs.append(payload)

        await c.subscribe("chan", on_msg)
        pull = asyncio.ensure_future(c.q_pull("work"))   # parks, survives
        await asyncio.sleep(0.05)
        await store.restart(down_for=0.05)
        await asyncio.wait_for(c.wait_connected(), 5.0)
        probe = await StoreClient(port=port, reconnect=OFF).connect()
        # re-subscribed: wait_connected returns only after replay, so one
        # publish must reach the pre-restart subscription
        await probe.publish("chan", b"hello")
        await until(lambda: got_msgs, msg="pub/sub resubscription")
        # resumed q_pull: a push lands in the re-issued pull
        await probe.q_push("work", b"job")
        msg_id, payload = await asyncio.wait_for(pull, 5.0)
        assert payload == b"job"
        await probe.close()
    finally:
        await c.close()
        await store.stop()


async def test_endpoint_reregistration_after_store_restart():
    """Kill -9 the store mid-traffic: the worker re-registers within the
    backoff window and the client's live set converges back."""
    store = RestartableStore()
    port = await store.start()
    with fast_reconnect_env():
        w = await DistributedRuntime(store_port=port,
                                     advertise_host="127.0.0.1").connect()
        caller = await DistributedRuntime(store_port=port).connect()
    try:
        async def handler(request, ctx):
            yield {"ok": True}

        ep = w.namespace("rr").component("c").endpoint("gen")
        await ep.serve(handler)
        client = await caller.namespace("rr").component("c") \
            .endpoint("gen").client().start()
        await client.wait_for_instances(1, timeout=5)
        worker_id = w.worker_id

        await store.restart(down_for=0.05)
        await asyncio.wait_for(w.store.wait_connected(), 5.0)
        await asyncio.wait_for(caller.store.wait_connected(), 5.0)
        # same identity re-registered; the client watch converges
        await until(lambda: worker_id in client.instances, timeout=5,
                    msg="endpoint re-registration")
        out = [item async for item in client.generate({"q": 1})]
        assert out == [{"ok": True}]
    finally:
        await caller.close()
        await w.close()
        await store.stop()


# ---------------------------------------------------------------------------
# end-to-end deadlines (ingress / rpc / queue / kv-wait)
# ---------------------------------------------------------------------------

async def test_deadline_http_ingress_504_names_stage():
    import aiohttp

    from dynamo_tpu.llm.http_service import (HttpService, ModelManager,
                                             ServedModel)
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime.engine import AsyncEngine

    class Staller(AsyncEngine):
        async def generate(self, request, context):
            await asyncio.sleep(30)
            yield {}

    manager = ModelManager()
    manager.add(ServedModel(ModelDeploymentCard.synthetic("stall"),
                            Staller(), Staller()))
    svc = HttpService(manager, host="127.0.0.1", port=0)
    base = f"http://127.0.0.1:{await svc.start()}"
    try:
        async with aiohttp.ClientSession() as s:
            body = {"model": "stall",
                    "messages": [{"role": "user", "content": "hi"}]}
            t0 = time.monotonic()
            async with s.post(f"{base}/v1/chat/completions", json=body,
                              headers={"x-request-timeout": "0.3"}) as r:
                assert r.status == 504
                data = await r.json()
            assert time.monotonic() - t0 < 5.0
            assert data["error"]["type"] == "timeout_error"
            assert "http_aggregate" in data["error"]["message"]
            # malformed header is the client's fault
            async with s.post(f"{base}/v1/chat/completions", json=body,
                              headers={"x-request-timeout": "soon"}) as r:
                assert r.status == 400
        assert stage_metrics().deadline_expiries.get("http_aggregate") >= 1
    finally:
        await svc.stop()


async def test_deadline_rpc_stream_504():
    """A worker that stalls mid-stream becomes a clean 504 naming the rpc
    stage — the inter-frame timeout in Client.generate."""
    store = RestartableStore()
    port = await store.start()
    w = await DistributedRuntime(store_port=port,
                                 advertise_host="127.0.0.1").connect()
    caller = await DistributedRuntime(store_port=port).connect()
    try:
        async def stalling(request, ctx):
            yield {"i": 0}
            await asyncio.sleep(30)
            yield {"i": 1}

        await w.namespace("ddl").component("c").endpoint("gen") \
            .serve(stalling)
        client = await caller.namespace("ddl").component("c") \
            .endpoint("gen").client().start()
        await client.wait_for_instances(1, timeout=5)
        ctx = Context(deadline=time.time() + 0.4)
        items = []
        with pytest.raises(EngineError) as ei:
            async for item in client.generate({"n": 2}, ctx):
                items.append(item)
        assert ei.value.code == 504
        assert "rpc_stream" in str(ei.value)
        assert items == [{"i": 0}]
        # an expired deadline never even dispatches
        with pytest.raises(EngineError) as ei2:
            async for _ in client.generate({}, Context(
                    deadline=time.time() - 1)):
                pass
        assert ei2.value.code == 504 and "rpc_dispatch" in str(ei2.value)
    finally:
        await caller.close()
        await w.close()
        await store.stop()


async def test_deadline_expired_job_dropped_at_dequeue():
    from dynamo_tpu.llm.disagg import PrefillQueue, RemotePrefillRequest

    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=OFF).connect()
    try:
        q = PrefillQueue(c, "ddlq")
        before = stage_metrics().deadline_expiries.get("prefill_dequeue")
        await q.enqueue(RemotePrefillRequest(
            "dead", 1, {}, deadline=time.time() - 1.0))   # expired in queue
        await q.enqueue(RemotePrefillRequest(
            "alive", 1, {}, deadline=time.time() + 30.0))
        msg_id, job = await asyncio.wait_for(q.dequeue(), 5.0)
        # the expired job was acked+dropped, never surfaced
        assert job.request_id == "alive"
        await q.ack(msg_id)
        assert await q.size() == 0
        assert stage_metrics().deadline_expiries.get(
            "prefill_dequeue") == before + 1
    finally:
        await c.close()
        await store.stop()


async def test_deadline_decode_kv_wait_504():
    from dynamo_tpu.llm.disagg import PrefillQueue
    from dynamo_tpu.llm.kv_transfer import KvReceiver, await_remote_kv

    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=OFF).connect()
    try:
        q = PrefillQueue(c, "kvddl")
        receiver = KvReceiver()
        ctx = Context("req1", deadline=time.time() + 0.2)
        fut = receiver.expect(ctx.id)
        with pytest.raises(dl.DeadlineExceeded) as ei:
            await await_remote_kv(ctx, fut, q, receiver,
                                  remote_timeout=120.0)
        assert ei.value.code == 504
        assert "decode_kv_wait" in str(ei.value)
        # the queued job was tombstoned so no prefill worker computes it
        assert await q.consume_cancelled(ctx.id)
    finally:
        await c.close()
        await store.stop()


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

async def test_breaker_eject_halfopen_recover():
    b = InstanceBreaker(threshold=2, cooldown=0.15)
    assert b.allow(7) and b.state(7) == "closed"
    b.record_failure(7)
    assert b.allow(7)                       # below threshold
    b.record_failure(7)
    assert b.state(7) == "open" and not b.allow(7)
    assert b.filter([7, 8]) == [8]          # 8 unknown => closed
    assert b.filter([7]) == [7]             # never veto EVERYONE
    await asyncio.sleep(0.2)
    assert b.state(7) == "half_open" and b.allow(7)   # probe allowed
    b.record_failure(7)                     # probe failed => re-open
    assert b.state(7) == "open"
    await asyncio.sleep(0.2)
    b.record_success(7)                     # probe succeeded => closed
    assert b.state(7) == "closed" and b.allow(7)
    b.forget(7)
    assert b.state(7) == "closed"


async def test_breaker_disabled_with_zero_threshold():
    b = InstanceBreaker(threshold=0, cooldown=0.1)
    for _ in range(10):
        b.record_failure(3)
    assert b.allow(3) and b.filter([3]) == [3]


async def test_client_ejects_dead_instance_across_requests():
    """A dead-but-still-registered instance is ejected after the breaker
    threshold: later requests stop burning connects on it."""
    from dynamo_tpu.runtime.component import EndpointInfo, endpoint_key

    store = RestartableStore()
    port = await store.start()
    w = await DistributedRuntime(store_port=port,
                                 advertise_host="127.0.0.1").connect()
    caller = await DistributedRuntime(store_port=port).connect()
    try:
        async def handler(request, ctx):
            yield {"from": "live"}

        await w.namespace("cb").component("c").endpoint("gen") \
            .serve(handler)
        # ghost: registered under its own lease but its port is closed
        ghost_lease = await caller.store.lease_grant(ttl=30)
        ghost = EndpointInfo(host="127.0.0.1", port=1, endpoint="gen",
                             lease=ghost_lease, worker_id=ghost_lease)
        await caller.store.put(
            endpoint_key("cb", "c", "gen", ghost_lease), ghost.to_bytes(),
            lease=ghost_lease)
        client = await caller.namespace("cb").component("c") \
            .endpoint("gen").client().start()
        await client.wait_for_instances(2, timeout=5)
        client.breaker = InstanceBreaker(threshold=2, cooldown=30.0)
        for _ in range(8):
            # round robin: the ghost is tried every other request until it
            # is ejected (a random pick leaves it under the threshold of 2
            # in 9 of 256 runs of eight requests, and this test failed so)
            out = [i async for i in client.generate({},
                                                    mode="round_robin")]
            assert out == [{"from": "live"}]
        assert client.breaker.state(ghost_lease) == "open"
        # deregistration clears the accounting
        await caller.store.delete(endpoint_key("cb", "c", "gen",
                                               ghost_lease))
        await until(lambda: ghost_lease not in client.instances,
                    msg="ghost deregistration")
        assert client.breaker.state(ghost_lease) == "closed"
    finally:
        await caller.close()
        await w.close()
        await store.stop()


async def test_pool_evicted_when_instance_deregisters():
    """Satellite: pooled sockets to a deregistered instance are dropped in
    the watch delete path — the next request opens fresh elsewhere."""
    store = RestartableStore()
    port = await store.start()
    w = await DistributedRuntime(store_port=port,
                                 advertise_host="127.0.0.1").connect()
    caller = await DistributedRuntime(store_port=port).connect()
    try:
        async def handler(request, ctx):
            yield {"ok": 1}

        await w.namespace("pe").component("c").endpoint("gen") \
            .serve(handler)
        client = await caller.namespace("pe").component("c") \
            .endpoint("gen").client().start()
        await client.wait_for_instances(1, timeout=5)
        out = [i async for i in client.generate({})]
        assert out == [{"ok": 1}]
        key = (w.dp_host, w.dp_port)
        assert client._pool.get(key), "expected a pooled connection"
        await w.close()      # revokes lease => key deleted => watch fires
        await until(lambda: not client.instances, msg="live set shrink")
        assert not client._pool.get(key), "pool kept a dead socket"
    finally:
        await caller.close()
        await store.stop()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

async def test_prepare_drain_deregisters_but_finishes_streams():
    store = RestartableStore()
    port = await store.start()
    w = await DistributedRuntime(store_port=port,
                                 advertise_host="127.0.0.1").connect()
    caller = await DistributedRuntime(store_port=port).connect()
    try:
        release = asyncio.Event()

        async def handler(request, ctx):
            yield {"i": 0}
            await release.wait()
            yield {"i": 1}

        await w.namespace("dr").component("c").endpoint("gen") \
            .serve(handler)
        client = await caller.namespace("dr").component("c") \
            .endpoint("gen").client().start()
        await client.wait_for_instances(1, timeout=5)

        agen = client.generate({})
        assert (await agen.__anext__()) == {"i": 0}   # in flight
        await w.prepare_drain()
        assert w.draining.is_set()
        # invisible: registration gone from the store...
        probe = await StoreClient(port=port, reconnect=OFF).connect()
        assert await probe.get_prefix("dr/components/") == []
        await probe.close()
        # ...but the in-flight stream still completes
        release.set()
        assert (await agen.__anext__()) == {"i": 1}
        with pytest.raises(StopAsyncIteration):
            await agen.__anext__()
    finally:
        await caller.close()
        await w.close()
        await store.stop()


# ---------------------------------------------------------------------------
# faults + static check
# ---------------------------------------------------------------------------

async def test_fault_points_fire_and_disarm():
    from dynamo_tpu.utils import faults

    try:
        faults.configure("p.refuse:refuse,p.delay:delay:0.01")
        with pytest.raises(ConnectionRefusedError):
            await faults.fire("p.refuse")
        t0 = time.monotonic()
        await faults.fire("p.delay")
        assert time.monotonic() - t0 >= 0.01
        await faults.fire("p.unarmed")      # no-op
        faults.disarm("p.refuse")
        await faults.fire("p.refuse")       # disarmed => no-op
        assert stage_metrics().faults_injected.get("p.refuse",
                                                   "refuse") >= 1
    finally:
        faults.disarm()


async def test_store_driven_faults_toggle_live():
    from dynamo_tpu.utils import faults

    store = RestartableStore()
    port = await store.start()
    c = await StoreClient(port=port, reconnect=OFF).connect()
    try:
        await faults.watch_store_faults(c)
        ctl = await StoreClient(port=port, reconnect=OFF).connect()
        await ctl.put("faults/sd.point", b"drop")
        await until(lambda: faults.is_active("sd.point") is not None,
                    msg="fault armed via store")
        with pytest.raises(ConnectionResetError):
            await faults.fire("sd.point")
        await ctl.delete("faults/sd.point")
        await until(lambda: faults.is_active("sd.point") is None,
                    msg="fault disarmed via store")
        await ctl.close()
    finally:
        faults.disarm()
        await c.close()
        await store.stop()


def test_no_unbounded_network_awaits():
    """CI gate: network awaits in runtime/ must be deadline-guarded or
    explicitly annotated."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_unbounded_awaits.py")
    spec = importlib.util.spec_from_file_location("check_unbounded", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = mod.run(mod.DEFAULT_PATHS)
    assert findings == [], "\n".join(findings)


# ---------------------------------------------------------------------------
# kill -9 chaos soak (multi-process; excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
async def test_chaos_soak_short():
    import importlib.util
    import tempfile

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "chaos_soak.py")
    spec = importlib.util.spec_from_file_location("chaos_soak", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    stats = await mod.soak(duration=15.0, n_workers=2, concurrency=3,
                           request_deadline=8.0, min_success=0.9,
                           store_kills=1,
                           logdir=tempfile.mkdtemp(prefix="chaos_test_"))
    print(stats.summary())
    assert stats.hung == 0, stats.summary()
    assert stats.submitted > 0
    assert stats.ok / stats.submitted >= 0.9, stats.summary()
