"""Multi-host worker model (VERDICT round-1 missing #3): a two-process
worker pair — leader + follower over jax.distributed — serves ONE endpoint.

Each process owns one virtual CPU device; tensor parallelism tp=2 spans the
two processes, so every matmul all-reduce crosses the process boundary.
Completion of a generation is therefore PROOF of lockstep: if the follower
failed to replay any leader dispatch, the leader's collectives would hang.
"""

import asyncio
import json
import os
import socket
import subprocess
import sys
import time

import pytest


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
@pytest.mark.parametrize("overlap", [False, True],
                         ids=["one_request", "prefill_behind_decode"])
async def test_two_process_worker_pair_serves_one_endpoint(tmp_path, overlap):
    """``overlap``: a three-chunk prompt arrives while another request
    decodes, so the leader enqueues its chunks behind decode dispatches
    still in flight and chains decodes behind them. The follower replays
    the hook's sequence, which is the enqueue order: a dispatch it missed
    or reordered would hang the leader's collectives, and one it ran with
    other inputs would change the greedy streams."""
    store_port = free_port()
    coord_port = free_port()
    dispatch_port = free_port()

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "DYN_LOG": "info"}
    store = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.runtime.store_server",
         "--port", str(store_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", store_port), 0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    workers = []
    logs = []
    try:
        common = ["--engine", "jax", "--store", f"127.0.0.1:{store_port}",
                  "--advertise-host", "127.0.0.1",
                  "--num-nodes", "2",
                  "--coordinator", f"127.0.0.1:{coord_port}",
                  "--dispatch-port", str(dispatch_port),
                  "--tp", "2",
                  "--extra-engine-args",
                  json.dumps({"preset": "tiny-byte", "max_batch": 2,
                              "max_context": 128, "prefill_chunk": 32,
                              "decode_steps": 4})]
        for rank in (0, 1):
            lf = open(tmp_path / f"node{rank}.log", "w")
            logs.append(lf)
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.cli.worker",
                 *common, "--node-rank", str(rank)],
                env=env, stdout=lf, stderr=subprocess.STDOUT))

        from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                     StopConditions)
        from dynamo_tpu.runtime.component import DistributedRuntime

        caller = await DistributedRuntime(store_port=store_port).connect()
        cl = await caller.namespace("dynamo").component("backend") \
            .endpoint("generate").client().start()
        deadline = time.monotonic() + 120
        while not cl.instances and time.monotonic() < deadline:
            dead = [w for w in workers if w.poll() is not None]
            if dead:
                for lf in logs:
                    lf.flush()
                raise AssertionError(
                    "worker died during bring-up:\n" +
                    "\n".join((tmp_path / f"node{r}.log").read_text()[-2000:]
                              for r in (0, 1)))
            await asyncio.sleep(0.25)
        # exactly ONE endpoint instance: the leader (followers are silent)
        assert len(cl.instances) == 1

        req = BackendInput(token_ids=[5, 6, 7, 8],
                           stop=StopConditions(max_tokens=6,
                                               ignore_eos=True)).to_dict()
        outs = []
        async def run():
            async for item in cl.generate(req):
                outs.append(item)
        await asyncio.wait_for(run(), 120)
        toks = [t for o in outs for t in o.get("token_ids", [])]
        assert len(toks) == 6 and all(0 <= t < 259 for t in toks)
        assert outs[-1].get("finish_reason") == "length"

        # determinism across the pair: a second identical request decodes
        # the same greedy tokens (device state stayed consistent)
        outs2 = []
        async def run2():
            async for item in cl.generate(req):
                outs2.append(item)
        await asyncio.wait_for(run2(), 60)
        toks2 = [t for o in outs2 for t in o.get("token_ids", [])]
        assert toks2 == toks

        if overlap:
            await _prefill_behind_decode(cl, caller)
        await caller.close()
    finally:
        for w in workers:
            w.terminate()
        for w in workers:
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()
        store.terminate()
        for lf in logs:
            lf.close()


async def _prefill_behind_decode(cl, caller):
    from dynamo_tpu.llm.metrics_aggregator import fetch_stage_states
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
    from dynamo_tpu.utils.prometheus import render_states

    def request(prompt, n):
        return BackendInput(token_ids=prompt, stop=StopConditions(
            max_tokens=n, ignore_eos=True)).to_dict()

    async def stream(req, started=None):
        toks = []
        async for item in cl.generate(req):
            toks += item.get("token_ids", [])
            if started is not None and toks:
                started.set()
        return toks

    dec = request([9, 8, 7], 40)                    # ten decode dispatches
    long = request(list(range(10, 80)), 6)          # three chunks of 32
    started = asyncio.Event()
    decoding = asyncio.create_task(stream(dec, started))
    await asyncio.wait_for(started.wait(), 60)
    both = (await asyncio.wait_for(stream(long), 120),
            await asyncio.wait_for(decoding, 120))
    assert [len(t) for t in both] == [6, 40]
    # each alone (its prefix restored, not prefilled again): same streams
    assert await asyncio.wait_for(stream(long), 60) == both[0]
    assert await asyncio.wait_for(stream(dec), 60) == both[1]
    # and the leader did put chunks behind dispatches in flight
    deadline = time.monotonic() + 20
    behind = 0.0
    while not behind and time.monotonic() < deadline:
        text = render_states(await fetch_stage_states(caller.store,
                                                      "dynamo"))
        behind = sum(float(line.rsplit(" ", 1)[1])
                     for line in text.splitlines()
                     if line.startswith("dyn_engine_dispatches_behind_total")
                     and 'kind="prefill"' in line)
        await asyncio.sleep(0.5)
    assert behind >= 2


@pytest.mark.slow
async def test_follower_death_kills_slice_and_client_fails_over(tmp_path):
    """SURVEY §5.3 / multihost failure story: kill the follower mid-stream;
    the leader must die hard (dispatch channel), its lease must expire, and
    a client must carry on against a replacement worker."""
    store_port = free_port()
    coord_port = free_port()
    dispatch_port = free_port()

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "DYN_LOG": "info"}
    store = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.runtime.store_server",
         "--port", str(store_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", store_port), 0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    procs = {}
    logs = []
    try:
        common = ["--engine", "jax", "--store", f"127.0.0.1:{store_port}",
                  "--advertise-host", "127.0.0.1",
                  "--num-nodes", "2",
                  "--coordinator", f"127.0.0.1:{coord_port}",
                  "--dispatch-port", str(dispatch_port),
                  "--tp", "2",
                  "--extra-engine-args",
                  json.dumps({"preset": "tiny-byte", "max_batch": 2,
                              "max_context": 256, "prefill_chunk": 32,
                              "decode_steps": 2})]
        for rank in (0, 1):
            lf = open(tmp_path / f"node{rank}.log", "w")
            logs.append(lf)
            procs[rank] = subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.cli.worker",
                 *common, "--node-rank", str(rank)],
                env=env, stdout=lf, stderr=subprocess.STDOUT)

        from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                     StopConditions)
        from dynamo_tpu.runtime.component import DistributedRuntime

        caller = await DistributedRuntime(store_port=store_port).connect()
        cl = await caller.namespace("dynamo").component("backend") \
            .endpoint("generate").client().start()
        deadline = time.monotonic() + 120
        while not cl.instances and time.monotonic() < deadline:
            assert all(p.poll() is None for p in procs.values()), \
                "worker died during bring-up"
            await asyncio.sleep(0.25)
        assert len(cl.instances) == 1

        # long-running stream, then kill the follower mid-generation
        req = BackendInput(token_ids=[5, 6, 7, 8],
                           stop=StopConditions(max_tokens=400,
                                               ignore_eos=True)).to_dict()
        got_any = asyncio.Event()
        stream_dead = asyncio.Event()

        async def consume():
            try:
                async for item in cl.generate(req):
                    got_any.set()
            except Exception:
                pass
            finally:
                stream_dead.set()

        task = asyncio.create_task(consume())
        await asyncio.wait_for(got_any.wait(), 120)
        procs[1].kill()                       # follower dies mid-stream

        # leader detects the dead dispatch channel and exits hard
        deadline = time.monotonic() + 60
        while procs[0].poll() is None and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
        assert procs[0].poll() is not None, "leader survived follower death"
        await asyncio.wait_for(stream_dead.wait(), 30)

        # lease expiry drops the instance from the watched live set
        deadline = time.monotonic() + 30
        while cl.instances and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
        assert not cl.instances, "dead leader still in the live set"

        # a replacement worker comes up; the client serves against it
        # without being rebuilt (failover at the watched-live-set level)
        lf = open(tmp_path / "replacement.log", "w")
        logs.append(lf)
        procs["r"] = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cli.worker",
             "--engine", "jax", "--store", f"127.0.0.1:{store_port}",
             "--advertise-host", "127.0.0.1",
             "--extra-engine-args",
             json.dumps({"preset": "tiny-byte", "max_batch": 2,
                         "max_context": 256, "prefill_chunk": 32,
                         "decode_steps": 2})],
            env=env, stdout=lf, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 120
        while not cl.instances and time.monotonic() < deadline:
            assert procs["r"].poll() is None, "replacement died"
            await asyncio.sleep(0.25)
        assert len(cl.instances) == 1

        req2 = BackendInput(token_ids=[9, 10, 11],
                            stop=StopConditions(max_tokens=5,
                                                ignore_eos=True)).to_dict()
        outs = []

        async def run2():
            async for item in cl.generate(req2):
                outs.append(item)

        await asyncio.wait_for(run2(), 120)
        toks = [t for o in outs for t in o.get("token_ids", [])]
        assert len(toks) == 5

        await caller.close()
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        store.terminate()
        for lf in logs:
            lf.close()


@pytest.mark.slow
async def test_pp_stages_across_process_boundary(tmp_path):
    """Pipeline parallelism with stages on SEPARATE PROCESSES (VERDICT r3
    missing #1): a two-process pair serves pp=2 (one layer-stage per
    process over the jax.distributed mesh; stage hops = cross-process
    collectives), and its greedy tokens match a single-process pp=1 worker
    token for token. The reference's pp exists exactly for this shape
    (vllm_inc.py:38 pipeline_parallel_size = num_nodes, ray.rs:66-229)."""
    store_port = free_port()
    coord_port = free_port()
    dispatch_port = free_port()

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "DYN_LOG": "info"}
    store = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.runtime.store_server",
         "--port", str(store_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", store_port), 0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    eng = {"preset": "tiny-byte", "max_batch": 2, "max_context": 128,
           "prefill_chunk": 32, "decode_steps": 4, "pp": 2}
    workers = []
    logs = []
    try:
        common = ["--engine", "jax", "--store", f"127.0.0.1:{store_port}",
                  "--advertise-host", "127.0.0.1",
                  "--num-nodes", "2",
                  "--coordinator", f"127.0.0.1:{coord_port}",
                  "--dispatch-port", str(dispatch_port),
                  "--tp", "1",
                  "--extra-engine-args", json.dumps(eng)]
        for rank in (0, 1):
            lf = open(tmp_path / f"pp-node{rank}.log", "w")
            logs.append(lf)
            workers.append(subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.cli.worker",
                 *common, "--node-rank", str(rank)],
                env=env, stdout=lf, stderr=subprocess.STDOUT))

        from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                     StopConditions)
        from dynamo_tpu.runtime.component import DistributedRuntime

        caller = await DistributedRuntime(store_port=store_port).connect()
        cl = await caller.namespace("dynamo").component("backend") \
            .endpoint("generate").client().start()
        deadline = time.monotonic() + 180
        while not cl.instances and time.monotonic() < deadline:
            dead = [w for w in workers if w.poll() is not None]
            if dead:
                for lf in logs:
                    lf.flush()
                raise AssertionError(
                    "pp worker died during bring-up:\n" +
                    "\n".join(
                        (tmp_path / f"pp-node{r}.log").read_text()[-2000:]
                        for r in (0, 1)))
            await asyncio.sleep(0.25)
        assert len(cl.instances) == 1, "leader must be the only instance"

        req = BackendInput(token_ids=[5, 6, 7, 8],
                           stop=StopConditions(max_tokens=6,
                                               ignore_eos=True)).to_dict()
        outs = []

        async def run():
            async for item in cl.generate(req):
                outs.append(item)

        await asyncio.wait_for(run(), 180)
        toks_pp = [t for o in outs for t in o.get("token_ids", [])]
        assert len(toks_pp) == 6
        assert outs[-1].get("finish_reason") == "length"
        await caller.close()
    finally:
        for w in workers:
            w.terminate()
        for w in workers:
            try:
                w.wait(timeout=10)
            except subprocess.TimeoutExpired:
                w.kill()
        store.terminate()
        for lf in logs:
            lf.close()

    # token-for-token reference: the SAME model served pp=1 in-process
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                 StopConditions)
    from dynamo_tpu.models import llama

    core = EngineCore(JaxEngineConfig(
        model=llama.preset("tiny-byte"), max_batch=2, max_context=128,
        prefill_chunk=32, decode_steps=4, attn_impl="xla"))
    core.submit("ref", BackendInput(
        token_ids=[5, 6, 7, 8],
        stop=StopConditions(max_tokens=6, ignore_eos=True)))
    ref = []
    for _ in range(200):
        for so in core.step():
            assert so.error is None
            ref.append(so.token)
        if not core.has_work:
            break
    assert toks_pp == ref, (toks_pp, ref)


@pytest.mark.slow
async def test_follower_death_during_pp_kills_slice(tmp_path):
    """Follower death while pp stages span the process pair: the leader
    must die hard (stage hops would otherwise hang forever on the dead
    peer's collectives) and its lease must expire (VERDICT r3 next #4)."""
    store_port = free_port()
    coord_port = free_port()
    dispatch_port = free_port()

    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "DYN_LOG": "info"}
    store = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.runtime.store_server",
         "--port", str(store_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", store_port), 0.5).close()
            break
        except OSError:
            time.sleep(0.1)
    procs = {}
    logs = []
    try:
        common = ["--engine", "jax", "--store", f"127.0.0.1:{store_port}",
                  "--advertise-host", "127.0.0.1",
                  "--num-nodes", "2",
                  "--coordinator", f"127.0.0.1:{coord_port}",
                  "--dispatch-port", str(dispatch_port),
                  "--tp", "1",
                  "--extra-engine-args",
                  json.dumps({"preset": "tiny-byte", "max_batch": 2,
                              "max_context": 256, "prefill_chunk": 32,
                              "decode_steps": 2, "pp": 2})]
        for rank in (0, 1):
            lf = open(tmp_path / f"ppd-node{rank}.log", "w")
            logs.append(lf)
            procs[rank] = subprocess.Popen(
                [sys.executable, "-m", "dynamo_tpu.cli.worker",
                 *common, "--node-rank", str(rank)],
                env=env, stdout=lf, stderr=subprocess.STDOUT)

        from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                     StopConditions)
        from dynamo_tpu.runtime.component import DistributedRuntime

        caller = await DistributedRuntime(store_port=store_port).connect()
        cl = await caller.namespace("dynamo").component("backend") \
            .endpoint("generate").client().start()
        deadline = time.monotonic() + 180
        while not cl.instances and time.monotonic() < deadline:
            dead = [r for r, p in procs.items() if p.poll() is not None]
            if dead:
                for lf in logs:
                    lf.flush()
                raise AssertionError(
                    "pp worker died during bring-up:\n" +
                    "\n".join(
                        (tmp_path / f"ppd-node{r}.log").read_text()[-2000:]
                        for r in (0, 1)))
            await asyncio.sleep(0.25)
        assert len(cl.instances) == 1

        req = BackendInput(token_ids=[5, 6, 7, 8],
                           stop=StopConditions(max_tokens=400,
                                               ignore_eos=True)).to_dict()
        got_any = asyncio.Event()
        stream_dead = asyncio.Event()

        async def consume():
            try:
                async for item in cl.generate(req):
                    got_any.set()
            except Exception:
                pass
            finally:
                stream_dead.set()

        task = asyncio.create_task(consume())
        await asyncio.wait_for(got_any.wait(), 120)
        procs[1].kill()                 # stage-1 process dies mid-decode

        deadline = time.monotonic() + 60
        while procs[0].poll() is None and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
        assert procs[0].poll() is not None, \
            "stage-0 leader survived stage-1 death (would hang on ppermute)"
        await asyncio.wait_for(stream_dead.wait(), 30)
        await task

        deadline = time.monotonic() + 30
        while cl.instances and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
        assert not cl.instances, "dead pp leader still in the live set"
        await caller.close()
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        store.terminate()
        for lf in logs:
            lf.close()
