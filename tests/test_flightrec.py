"""Flight recorder, hang watchdog, and coordinated incident bundles.

Covers: ring/eviction accounting (satellite: loss must be visible), the
heartbeat/stall model (EWMA and budget paths, re-arming, zero false
positives on a clean run), trace-id-consistent head sampling with error
retro-flush at ``DYN_TRACE_SAMPLE=0.01``, the incident round trip (torn
stream + breaker trip through the REAL hooks -> one coordinated bundle
with ring dumps from two "processes" and the complete trace), the ctl /
tracectl inspection surfaces over that bundle, and the two new lint-side
satellites (metric type check, ``loop-blocking-path`` rule).
"""

import argparse
import asyncio
import json
import textwrap
import time

import pytest

from dynamo_tpu.obs import incidents as incidents_mod
from dynamo_tpu.obs.flightrec import (MAX_HEARTBEATS, FlightRecorder, Ring)
from dynamo_tpu.obs.watchdog import Watchdog
from dynamo_tpu.utils.prometheus import stage_metrics
from dynamo_tpu.utils.tracing import (StoreSpanSink, Tracer, trace_sampled)


def _unsampled_ids(rate: float, n: int, prefix: str = "req"):
    """Deterministic trace ids the head sampler DROPS at ``rate``."""
    out = []
    i = 0
    while len(out) < n:
        tid = f"{prefix}-{i}"
        if not trace_sampled(tid, rate):
            out.append(tid)
        i += 1
    return out


def _sampled_id(rate: float, prefix: str = "req") -> str:
    i = 0
    while True:
        tid = f"{prefix}-{i}"
        if trace_sampled(tid, rate):
            return tid
        i += 1


class _MemStore:
    """In-memory stand-in with the store-client surface the sink and the
    incident read side use (the round-trip test uses the real server)."""

    def __init__(self):
        self.data = {}
        self._lease = 0

    async def lease_grant(self, ttl=5.0, auto_keepalive=True, bind=True):
        self._lease += 1
        return self._lease

    async def put(self, key, value, lease=None):
        self.data[key] = value

    async def get(self, key):
        return self.data.get(key)

    async def get_prefix(self, prefix):
        return [(k, v) for k, v in sorted(self.data.items())
                if k.startswith(prefix)]

    async def watch_prefix(self, prefix, callback):
        return []


# ---------------------------------------------------------------------------
# rings + eviction accounting (satellite: loss is counted and visible)
# ---------------------------------------------------------------------------

def test_ring_eviction_counted():
    sm = stage_metrics()
    before = sm.flightrec_evicted.get("testring")
    r = Ring("testring", 4)
    for i in range(7):
        r.append(i)
    assert len(r) == 4
    assert r.snapshot() == [3, 4, 5, 6]          # drop-oldest
    assert r.evicted == 3
    assert sm.flightrec_evicted.get("testring") == before + 3


def test_recorder_disabled_is_noop():
    rec = FlightRecorder("t", enabled=False)
    rec.note("anything", x=1)
    rec.hb_begin("op")
    assert len(rec.events) == 0 and rec.heartbeats == {}
    snap = rec.snapshot()
    assert snap["rings"]["events"]["n"] == 0


def test_recorder_span_mirror_window_and_trace_pin():
    rec = FlightRecorder("t", enabled=True)
    t = Tracer(component="t", enabled=True)
    rec.attach(t)
    old = t.record("old", start=time.time() - 900, end=time.time() - 899,
                   trace_id="pinned")
    with t.span("fresh", trace_id="other"):
        pass
    assert len(rec.spans) == 2
    # window slicing drops the old span...
    now = time.time()
    snap = rec.snapshot(window=(now - 60, now))
    assert [s["name"] for s in snap["rings"]["spans"]["items"]] == ["fresh"]
    # ...unless its trace is the incident's trace: then it is always kept
    snap = rec.snapshot(window=(now - 60, now), trace_id="pinned")
    names = {s["name"] for s in snap["rings"]["spans"]["items"]}
    assert names == {"old", "fresh"}
    assert old.span_id in {s["span_id"]
                           for s in snap["rings"]["spans"]["items"]}
    rec.detach()
    with t.span("after-detach", trace_id="x"):
        pass
    assert len(rec.spans) == 2


def test_log_tail_ring():
    import logging

    rec = FlightRecorder("t", enabled=True)
    rec.attach_logging(level=logging.INFO)
    try:
        # warning: not gated by the root logger's default level
        logging.getLogger("dynamo_tpu.test_flightrec").warning(
            "black box caught %s", "this")
    finally:
        rec.detach()
    msgs = [e["msg"] for e in rec.logtail.snapshot()]
    assert "black box caught this" in msgs


def test_heartbeat_table_bounded_sheds_idle_first():
    rec = FlightRecorder("t", enabled=True)
    rec.hb_begin("busy")                          # depth 1, must survive
    for i in range(MAX_HEARTBEATS + 20):
        rec.hb_begin(f"hb-{i}")
        rec.hb_done(f"hb-{i}")                    # idle transient
    assert len(rec.heartbeats) <= MAX_HEARTBEATS
    assert "busy" in rec.heartbeats


# ---------------------------------------------------------------------------
# watchdog: detection semantics (pure check() API)
# ---------------------------------------------------------------------------

def _wd(rec, **kw):
    kw.setdefault("tracer", Tracer(component="wd", enabled=True))
    kw.setdefault("interval", 99.0)
    kw.setdefault("loop_stall", 99.0)
    kw.setdefault("enabled", False)               # never start the loop
    return Watchdog(recorder=rec, **kw)


def test_watchdog_ewma_stall_fires_once_and_rearms():
    rec = FlightRecorder("t", enabled=True)
    wd = _wd(rec, mult=8.0, floor=0.05)
    # completed units seed the EWMA at ~10ms
    rec.hb_begin("engine.decode", stall="decode")
    rec.hb_done("engine.decode", elapsed=0.01)
    rec.hb_begin("engine.decode")
    hb = rec.heartbeats["engine.decode"]
    assert hb.ewma == pytest.approx(0.01)
    # wedged: nothing moved for >> max(mult*ewma, floor)
    now = hb.last_activity + 1.0
    fired = wd.check(now)
    assert [f["kind"] for f in fired] == ["decode"]
    assert fired[0]["deadline"] == pytest.approx(0.08)   # 8 x ewma
    assert fired[0]["waited"] >= 1.0
    # one firing per wedged period
    assert wd.check(now + 5.0) == []
    # progress re-arms; going wedged again fires again
    rec.hb_done("engine.decode", elapsed=0.01)
    rec.hb_begin("engine.decode")
    assert wd.check(rec.heartbeats["engine.decode"].last_activity
                    + 0.01) == []                 # moving: clean
    assert [f["kind"] for f in wd.check(
        rec.heartbeats["engine.decode"].last_activity + 2.0)] == ["decode"]


def test_watchdog_budget_stall_and_progress():
    rec = FlightRecorder("t", enabled=True)
    wd = _wd(rec)
    rec.hb_begin("kv.recv:r1", stall="transfer", budget=0.2,
                 trace_id="r1")
    hb = rec.heartbeats["kv.recv:r1"]
    # layers still arriving: progress touches, no stall
    rec.hb_progress("kv.recv:r1")
    assert wd.check(hb.last_activity + 0.1) == []
    # then the stream wedges past its explicit budget
    fired = wd.check(hb.last_activity + 0.5)
    assert len(fired) == 1
    assert fired[0]["kind"] == "transfer"
    assert fired[0]["trace_id"] == "r1"
    assert fired[0]["deadline"] == pytest.approx(0.2)
    rec.hb_end("kv.recv:r1")
    assert wd.check(time.monotonic() + 99) == []


def test_watchdog_silent_paths():
    rec = FlightRecorder("t", enabled=True)
    wd = _wd(rec)
    # no budget and no EWMA yet (first unit may be compiling): silent
    rec.hb_begin("engine.decode", stall="decode")
    assert wd.check(time.monotonic() + 1e6) == []
    # nothing in flight: silent no matter how old
    rec.hb_done("engine.decode", elapsed=0.01)
    assert wd.check(time.monotonic() + 1e6) == []


def test_watchdog_emit_forced_error_span_and_metrics():
    rec = FlightRecorder("t", enabled=True)
    tr = Tracer(component="wd", enabled=True)
    wd = _wd(rec, tracer=tr)
    before = stage_metrics().watchdog_stalls.get("transfer")
    rec.hb_begin("kv.recv:r9", stall="transfer", budget=0.01,
                 trace_id="r9")
    fired = wd.check(rec.heartbeats["kv.recv:r9"].last_activity + 1.0)
    assert len(fired) == 1
    wd._emit(fired[0])
    assert wd.stalls == 1
    spans = tr.spans_for("r9")
    assert [s.name for s in spans] == ["stall:transfer"]
    # never-sampled: error status AND an explicit force_trace attribute
    assert spans[0].status == "error"
    assert spans[0].attrs.get("force_trace") is True
    assert stage_metrics().watchdog_stalls.get("transfer") == before + 1
    kinds = [e["kind"] for e in rec.events.snapshot()]
    assert "watchdog.stall" in kinds


async def test_watchdog_clean_run_zero_false_positives():
    """A healthy process doing real work never produces a stall span."""
    rec = FlightRecorder("t", enabled=True)
    tr = Tracer(component="wd", enabled=True)
    wd = Watchdog(recorder=rec, tracer=tr, interval=0.02, mult=8.0,
                  floor=0.5, loop_stall=5.0, enabled=True)
    await wd.start()
    try:
        for _ in range(10):
            rec.hb_begin("engine.decode", stall="decode")
            await asyncio.sleep(0.005)
            rec.hb_done("engine.decode", elapsed=0.005)
        rec.hb_begin("kv.recv:ok", stall="transfer", budget=5.0)
        for _ in range(5):
            await asyncio.sleep(0.005)
            rec.hb_progress("kv.recv:ok")
        rec.hb_end("kv.recv:ok")
    finally:
        await wd.stop()
    assert wd.stalls == 0
    assert len(tr) == 0                           # no stall:* spans at all


# ---------------------------------------------------------------------------
# head sampling at 1%: error retro-flush + force-retain (satellite)
# ---------------------------------------------------------------------------

async def test_head_sampling_error_retroflush_at_one_percent():
    rate = 0.01
    tid, ctrl = _unsampled_ids(rate, 2)
    store = _MemStore()
    tr = Tracer(component="t", enabled=True)
    sink = StoreSpanSink(store, sample=rate)
    await sink.start(tr)
    try:
        sm = stage_metrics()
        dropped0 = sm.spans_sampled_out.get()
        # ok spans in unsampled traces are withheld from the store export
        early = tr.record("early_ok", start=time.time() - 1,
                          end=time.time(), trace_id=tid)
        tr.record("ctrl_ok", start=time.time() - 1, end=time.time(),
                  trace_id=ctrl)
        assert sm.spans_sampled_out.get() == dropped0 + 2
        # ...but a sampled trace exports as usual
        tr.record("lucky", start=time.time() - 1, end=time.time(),
                  trace_id=_sampled_id(rate))
        # an ERROR span retro-flushes the earlier withheld span of ITS
        # trace (still in the local ring) and force-retains later ones
        boom = tr.record("boom", start=time.time() - 1, end=time.time(),
                         trace_id=tid, status="error")
        late = tr.record("late_ok", start=time.time() - 1,
                         end=time.time(), trace_id=tid)
    finally:
        await sink.stop()                          # drains everything
    keys = [k for k, _ in await store.get_prefix(f"traces/{tid}/")]
    assert {k.rsplit("/", 1)[-1] for k in keys} == \
        {early.span_id, boom.span_id, late.span_id}
    # the control trace (no error) stayed sampled out end to end
    assert await store.get_prefix(f"traces/{ctrl}/") == []
    # ...until the incident plane force-traces it: the ring retro-exports
    sink.force_trace(ctrl)
    await sink.flush()
    got = await store.get_prefix(f"traces/{ctrl}/")
    assert len(got) == 1
    assert json.loads(got[0][1].decode())["name"] == "ctrl_ok"


# ---------------------------------------------------------------------------
# the incident round trip: real hooks -> one coordinated bundle
# ---------------------------------------------------------------------------

async def test_incident_roundtrip_torn_stream_plus_breaker(tmp_path,
                                                           capsys):
    """At 1% head sampling, a torn disagg stream followed by a breaker
    trip yields ONE incident whose bundle holds ring dumps from two
    distinct processes and the complete retro-assembled trace; ``ctl
    incident show`` and ``tracectl --bundle --chrome`` both consume it."""
    from dynamo_tpu.cli.ctl import run_incident
    from dynamo_tpu.cli.tracectl import run_bundle
    from dynamo_tpu.llm.kv_transfer import KvReceiver, KvStreamError
    from dynamo_tpu.runtime.circuit_breaker import InstanceBreaker
    from dynamo_tpu.runtime.store_client import StoreClient
    from dynamo_tpu.runtime.store_server import StoreServer

    rate = 0.01
    rid = _unsampled_ids(rate, 1, prefix="inc")[0]
    ns = "incns"
    srv = StoreServer()
    port = await srv.start()
    clients = []
    mgr_a = mgr_b = sink = None
    try:
        ca = await StoreClient(port=port).connect()
        cb = await StoreClient(port=port).connect()
        clients += [ca, cb]

        # "process" A: the decode worker (trigger side, owns the sink)
        rec_a = FlightRecorder("decode_worker", enabled=True)
        tr_a = Tracer(component="decode_worker", enabled=True)
        rec_a.attach(tr_a)
        sink = StoreSpanSink(ca, sample=rate)
        await sink.start(tr_a)
        mgr_a = incidents_mod.IncidentManager(
            ca, namespace=ns, component="decode_worker", recorder=rec_a,
            span_sink=sink, proc_label="decode_worker:a", ttl=60.0,
            cooldown=30.0, window=30.0)
        await mgr_a.start()
        # "process" B: the frontend (dumps purely via the beacon watch)
        rec_b = FlightRecorder("http", enabled=True)
        tr_b = Tracer(component="http", enabled=True)
        rec_b.attach(tr_b)
        mgr_b = incidents_mod.IncidentManager(
            cb, namespace=ns, component="http", recorder=rec_b,
            proc_label="http:b", ttl=60.0, cooldown=30.0, window=30.0)
        await mgr_b.start()
        incidents_mod.install_manager(mgr_a)

        # both processes saw the request; at 1% sampling NONE of these
        # spans reached the store
        with tr_b.span("http:completions", trace_id=rid):
            pass
        with tr_a.span("rpc:generate", trace_id=rid):
            pass
        assert await ca.get_prefix(f"traces/{rid}/") == []

        # trigger 1, through the REAL hook: the KV receiver's torn-stream
        # cleanup path
        recv = KvReceiver(worker_id=0xA)
        fut = recv.expect(rid)
        recv._fail(rid, None, KvStreamError("torn", "donor died"))
        with pytest.raises(KvStreamError):
            await fut

        async def _beacons():
            return await incidents_mod.list_incidents(ca, ns)

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not await _beacons():
            await asyncio.sleep(0.05)
        beacons = await _beacons()
        assert len(beacons) == 1
        assert beacons[0]["reason"] == "torn_stream"
        assert beacons[0]["trace_id"] == rid
        iid = beacons[0]["id"]

        # trigger 2, through the REAL hook: breaker trip inside the
        # cooldown ATTACHES to the open incident instead of a new beacon
        brk = InstanceBreaker(threshold=1, cooldown=5.0)
        brk.record_failure(0xBEEF)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(e["kind"] == "incident.attach"
                   for e in rec_a.events.snapshot()):
                break
            await asyncio.sleep(0.05)
        attaches = [e for e in rec_a.events.snapshot()
                    if e["kind"] == "incident.attach"]
        assert attaches and attaches[0]["reason"] == "breaker_trip"
        assert len(await _beacons()) == 1          # coordinated, not chatty

        # every process dumped its rings under the one bundle
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            dumps = await ca.get_prefix(
                incidents_mod.incident_dump_prefix(ns, iid))
            if len(dumps) >= 2:
                break
            await asyncio.sleep(0.05)
        procs = {k.rsplit("/", 1)[-1] for k, _ in dumps}
        assert {"decode_worker:a", "http:b"} <= procs

        await sink.flush()                         # drain the retro-export
        bundle = await incidents_mod.fetch_bundle(ca, ns, iid)
        assert bundle is not None
        assert set(bundle["processes"]) >= {"decode_worker:a", "http:b"}
        # the trace is COMPLETE despite 1% sampling: A's span via the
        # force-traced store export, B's via its ring dump
        names = {s["name"] for s in bundle["trace"]}
        assert {"rpc:generate", "http:completions"} <= names
        comps = {s["component"] for s in bundle["trace"]}
        assert {"decode_worker", "http"} <= comps
        summary = "\n".join(incidents_mod.bundle_summary(bundle))
        assert "decode_worker:a" in summary and "http:b" in summary
        assert "torn_stream" in summary

        # inspection surface 1: ctl incident show / export
        assert await run_incident(ca, argparse.Namespace(
            action="show", incident_id=iid, namespace=ns)) == 0
        shown = capsys.readouterr().out
        assert f"incident {iid}" in shown and "processes (" in shown
        out_file = tmp_path / "bundle.json"
        assert await run_incident(ca, argparse.Namespace(
            action="export", incident_id=iid, namespace=ns,
            out=str(out_file))) == 0
        capsys.readouterr()

        # inspection surface 2: tracectl --bundle, waterfall and chrome
        assert run_bundle(argparse.Namespace(
            bundle=str(out_file), json=False, chrome=None)) == 0
        rendered = capsys.readouterr().out
        assert "rpc:generate" in rendered
        chrome_file = tmp_path / "chrome.json"
        assert run_bundle(argparse.Namespace(
            bundle=str(out_file), json=False,
            chrome=str(chrome_file))) == 0
        chrome = json.loads(chrome_file.read_text())
        evs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
        tracks = {e["args"]["name"] for e in chrome["traceEvents"]
                  if e["ph"] == "M"}
        assert {e["name"] for e in evs} >= {"rpc:generate",
                                            "http:completions"}
        assert len(tracks) >= 2                    # one track per process
    finally:
        incidents_mod.install_manager(None)
        if mgr_a is not None:
            await mgr_a.stop()
        if mgr_b is not None:
            await mgr_b.stop()
        if sink is not None:
            await sink.stop()
        for c in clients:
            await c.close()
        await srv.stop()


async def test_manual_capture_and_ls(capsys):
    """``ctl incident capture`` publishes a beacon with no local rings;
    ``ls`` lists it newest-first."""
    from dynamo_tpu.cli.ctl import run_incident

    store = _MemStore()
    assert await run_incident(store, argparse.Namespace(
        action="capture", namespace="m", reason="manual",
        trace_id=None, window=30.0)) == 0
    out = capsys.readouterr().out
    assert "captured" in out
    assert await run_incident(store, argparse.Namespace(
        action="ls", namespace="m")) == 0
    assert "manual" in capsys.readouterr().out
    beacons = await incidents_mod.list_incidents(store, "m")
    assert len(beacons) == 1 and beacons[0]["reason"] == "manual"
    # show on an expired/unknown id fails cleanly
    assert await run_incident(store, argparse.Namespace(
        action="show", incident_id="nope", namespace="m")) == 1


async def test_incident_data_survives_producer_death():
    """The black box must outlive its producer: a beacon published by a
    short-lived ``ctl`` process, a dying worker's ring dump, and its
    exported trace spans all ride UNBOUND (TTL-only) leases — while
    ordinary session leases still die with their connection."""
    from dynamo_tpu.runtime.store_client import StoreClient
    from dynamo_tpu.runtime.store_server import StoreServer

    srv = StoreServer()
    port = await srv.start()
    try:
        # the short-lived publisher: beacon + a ring dump + a trace span,
        # plus a session-bound control key for contrast
        pub = await StoreClient(port=port).connect()
        beacon = await incidents_mod.publish_beacon(
            pub, "d", "crash_probe", ttl=60.0)
        lease = await pub.lease_grant(ttl=60.0, auto_keepalive=False,
                                      bind=False)
        await pub.put(incidents_mod.incident_dump_key(
            "d", beacon["id"], "w:1"), b'{"rings": {}}', lease=lease)
        bound = await pub.lease_grant(ttl=60.0, auto_keepalive=False)
        await pub.put("d/session-key", b"x", lease=bound)
        await pub.close()                       # the producer dies
        await asyncio.sleep(0.1)

        reader = await StoreClient(port=port).connect()
        try:
            beacons = await incidents_mod.list_incidents(reader, "d")
            assert [b["id"] for b in beacons] == [beacon["id"]]
            bundle = await incidents_mod.fetch_bundle(reader, "d",
                                                      beacon["id"])
            assert set(bundle["processes"]) == {"w:1"}
            # the connection-bound key died with its session
            assert await reader.get("d/session-key") is None
        finally:
            await reader.close()
    finally:
        await srv.stop()


def test_bundle_summary_surfaces_ring_loss():
    """Satellite: eviction loss reads differently from a quiet window."""
    bundle = {
        "manifest": {"id": "i1", "reason": "stall_decode", "at": 0.0,
                     "window": [0.0, 30.0], "trace_id": None, "by": "w"},
        "processes": {"w:1": {"rings": {
            "spans": {"n": 5, "evicted": 123, "items": []},
            "events": {"n": 0, "evicted": 0, "items": []},
            "logtail": {"n": 0, "evicted": 0, "items": []}}}},
        "trace": [],
    }
    text = "\n".join(incidents_mod.bundle_summary(bundle))
    assert "LOSS: 123 evicted" in text and "ring too small" in text


# ---------------------------------------------------------------------------
# satellite: metric TYPE column check (counter/gauge/histogram vs docs)
# ---------------------------------------------------------------------------

def test_metrics_catalog_type_mismatch(tmp_path):
    from dynamo_tpu.analysis.core import Module
    from dynamo_tpu.analysis.rules.metrics_catalog import (
        catalog_findings, documented_types, registered_in_module,
        registered_types_in_module)

    src = tmp_path / "m.py"
    src.write_text(textwrap.dedent("""\
        c = reg.counter("dyn_good_total", "d")
        g = reg.gauge("dyn_lying_doc", "d")
        h = reg.histogram
        h("dyn_hist_seconds", "d")
    """))
    mod = Module(str(src), repo=str(tmp_path))
    kinds = registered_types_in_module(mod)
    assert kinds == {"dyn_good_total": {"counter"},
                     "dyn_lying_doc": {"gauge"},
                     "dyn_hist_seconds": {"histogram"}}   # alias resolved
    doc = tmp_path / "obs.md"
    doc.write_text(textwrap.dedent("""\
        | metric | type | notes |
        |---|---|---|
        | `dyn_good_total` | counter (ring) | fine |
        | `dyn_lying_doc` | counter | WRONG: registered as gauge |
        | `dyn_hist_seconds` | histogram, wide buckets | fine |
        plain prose mention of dyn_good_total carries no type claim
    """))
    claimed = documented_types(str(doc))
    assert claimed == {"dyn_good_total": "counter",
                       "dyn_lying_doc": "counter",
                       "dyn_hist_seconds": "histogram"}
    fs = catalog_findings(
        registered_in_module(mod),
        {"dyn_good_total", "dyn_lying_doc", "dyn_hist_seconds"},
        registered_kinds=kinds, claimed_types=claimed)
    assert [f.key for f in fs] == ["type-mismatch:dyn_lying_doc"]
    assert "documented as 'counter'" in fs[0].message
    assert "registered as gauge" in fs[0].message


def test_metrics_catalog_type_check_on_real_tree():
    """The live doc's type column matches every registration."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_metrics_catalog",
        os.path.join(repo, "scripts", "check_metrics_catalog.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    claimed = mod.documented_types()
    kinds = mod.registered_types()
    assert claimed, "type-annotated catalog rows must parse"
    # the four incident-plane metrics are documented with correct types
    for name in ("dyn_flightrec_evicted_total", "dyn_watchdog_stalls_total",
                 "dyn_incidents_captured_total", "dyn_incident_dumps_total"):
        assert claimed.get(name) == "counter"
        assert kinds.get(name) == {"counter"}
    assert mod.run() == []


# ---------------------------------------------------------------------------
# satellite: loop-blocking-path rule (transitive blocking through helpers)
# ---------------------------------------------------------------------------

def _lint_mod(tmp_path, src):
    from dynamo_tpu.analysis.core import Module

    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent(src))
    return Module(str(p), repo=str(tmp_path))


def test_loop_blocking_path_transitive_chain(tmp_path):
    from dynamo_tpu.analysis.rules.loop_blocking_path import \
        LoopBlockingPathRule

    m = _lint_mod(tmp_path, """\
        import asyncio
        import time

        def _inner():
            time.sleep(1)

        def helper():
            _inner()

        def clean_helper():
            return 2 + 2

        async def handler():
            helper()                 # flagged: reaches time.sleep via 2 hops
            clean_helper()           # not flagged: no blocking reachable
            time.sleep(0.1)          # NOT this rule's finding (blocking-async)
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, lambda: helper())  # off-loop
    """)
    fs = LoopBlockingPathRule().check_module(m)
    assert [f.key for f in fs] == ["handler->helper:time.sleep"]
    assert "via helper -> _inner" in fs[0].message


def test_loop_blocking_path_self_method_and_async_callee(tmp_path):
    from dynamo_tpu.analysis.rules.loop_blocking_path import \
        LoopBlockingPathRule

    m = _lint_mod(tmp_path, """\
        import time

        class Svc:
            def _hop(self):
                time.sleep(0.5)

            async def _adelegate(self):
                pass

            async def serve(self):
                self._hop()          # flagged: method chain blocks
                await self._adelegate()   # async callee: not followed
    """)
    assert [f.key for f in LoopBlockingPathRule().check_module(m)] == \
        ["serve->_hop:time.sleep"]


def test_loop_blocking_path_recursion_and_extra_calls(tmp_path):
    from dynamo_tpu.analysis.rules.loop_blocking_path import \
        LoopBlockingPathRule

    m = _lint_mod(tmp_path, """\
        def ping():
            pong()

        def pong():
            ping()

        def sync_read():
            legacy_io.read_all()

        async def h():
            ping()                   # recursive but never blocking: clean
            sync_read()              # flagged only via extra_calls option
    """)
    assert LoopBlockingPathRule().check_module(m) == []
    rule = LoopBlockingPathRule(
        options={"extra_calls": ["legacy_io.read_all"]})
    assert [f.key for f in rule.check_module(m)] == \
        ["h->sync_read:legacy_io.read_all"]
