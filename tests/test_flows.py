"""Byte-flow ledger (obs/flows.py): the per-process accounting
chokepoint for every byte the cluster moves.

- record/snapshot/totals mechanics, per-class default link identity
  (host:/dev:/disk edges adopt the worker hex id), the DYN_FLOWS kill
  switch
- windowed rate over a FIXED DYN_LINK_WINDOW denominator (a single
  burst cannot read as congestion) + measured-peak capacity fallback
- calibrated-capacity saturation with rising-edge congestion: the
  dyn_link_congested_total counter, the flight-recorder link.congested
  event, and re-arming after the link drains
- every flow kind with measured seconds feeds the router's per-pair
  bandwidth EWMA (the blind-spot fix: paged/h2d traffic prices pairs)
- trace spans: a flow with a trace_id drops a flow.<kind> span
- flows_from_states: the pure fold dyntop/ctl/HTTP share — bytes
  accumulate across publishers, rates take max, absent series degrade
  to [] (never crash)
- ledger totals survive worker churn: clear_worker_keys drops one
  worker's published links without touching the survivors'
- GET /v1/flows serves the folded link table
"""

import asyncio
import json

import pytest

from dynamo_tpu.obs import flightrec
from dynamo_tpu.obs.flows import (FlowLedger, KIND_CLASS, flow_ledger,
                                  flows_from_states, fmt_bytes, link_name,
                                  record_flow)
from dynamo_tpu.utils.prometheus import stage_metrics

_SEP = "\x1f"


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# ledger mechanics
# ---------------------------------------------------------------------------

def test_record_snapshot_totals_reset():
    led = FlowLedger(local="7a")
    led.record("disagg_push", 1000, 0.5, src="7a", dst="b1")
    led.record("disagg_push", 500, 0.25, src="7a", dst="b1")
    led.record("kv_fetch_rx", 200, 0.1, src="c2", dst="7a")
    snap = led.snapshot()
    assert [(e["src"], e["dst"]) for e in snap] == [("7a", "b1"),
                                                    ("c2", "7a")]
    assert snap[0]["bytes"] == 1500
    assert snap[0]["kinds"] == {"disagg_push": 1500}
    assert snap[0]["peak_bw"] == pytest.approx(2000.0)
    assert led.total_bytes() == 1700
    assert led.total_bytes("kv_fetch_rx") == 200
    led.reset()
    assert led.snapshot() == [] and led.total_bytes() == 0


def test_default_links_adopt_worker_identity():
    led = FlowLedger(local="feed")
    led.record("kvpage_pagein", 10)       # h2d: host -> dev
    led.record("d2h_writethrough", 20)    # d2h: dev -> host
    led.record("weight_prefetch", 30)     # disk -> host
    links = {(e["src"], e["dst"]) for e in led.snapshot()}
    assert links == {("host:feed", "dev:feed"), ("dev:feed", "host:feed"),
                     ("disk", "host:feed")}
    led.set_local(0xabc)
    led.record("h2d_prefetch", 5)
    assert ("host:abc", "dev:abc") in {(e["src"], e["dst"])
                                       for e in led.snapshot()}
    # zero/negative byte counts never create links
    led.record("disagg_push", 0, 1.0, src="x", dst="y")
    assert ("x", "y") not in {(e["src"], e["dst"])
                              for e in led.snapshot()}


def test_every_kind_has_a_class():
    assert set(KIND_CLASS.values()) == {"net", "h2d", "d2h", "disk"}
    # the exact kind vocabulary the instrumented call sites use
    assert set(KIND_CLASS) == {
        "disagg_push", "disagg_stream_rx", "kv_fetch_tx", "kv_fetch_rx",
        "kvpage_pagein", "kvpage_pageout", "h2d_prefetch",
        "d2h_writethrough", "weight_prefetch", "swap_slab"}


def test_kill_switch_disables_accounting(monkeypatch):
    monkeypatch.setenv("DYN_FLOWS", "0")
    led = FlowLedger()
    assert not led.enabled
    led.record("disagg_push", 1000, 0.5, src="a", dst="b")
    assert led.snapshot() == [] and led.total_bytes() == 0


# ---------------------------------------------------------------------------
# rates, capacity, congestion
# ---------------------------------------------------------------------------

def test_windowed_rate_fixed_denominator(monkeypatch):
    """One 2 MB/s burst in a 10 s window reads as 100 KB/s of window
    bandwidth — sub-window bursts cannot fake saturation."""
    monkeypatch.setenv("DYN_LINK_WINDOW", "10.0")
    clock = _Clock()
    led = FlowLedger(now=clock)
    led.record("disagg_push", 1_000_000, 0.5, src="a", dst="b")
    (e,) = led.snapshot()
    assert e["peak_bw"] == pytest.approx(2_000_000.0)
    # capacity fallback = measured peak; sat = (1MB/10s) / 2MB/s = 0.05
    assert e["saturation"] == pytest.approx(0.05)
    assert e["congested"] == 0
    # samples age out of the window
    clock.t += 11.0
    led.record("disagg_push", 1_000_000, 0.5, src="a", dst="b")
    (e,) = led.snapshot()
    assert e["saturation"] == pytest.approx(0.05)   # not 0.1


def test_saturation_edge_emits_congestion(monkeypatch):
    """A throttled link that stays busy all window crosses the
    calibrated threshold exactly once per rising edge: counter + ring
    event fire on the edge, re-arm only after the link drains."""
    monkeypatch.setenv("DYN_LINK_WINDOW", "1.0")
    monkeypatch.setenv("DYN_LINK_CAPACITY_NET", "1000")
    stage = stage_metrics()
    link = link_name("slow", "peer")
    c0 = stage.link_congested.get(link)
    ev0 = sum(1 for e in flightrec.flight_recorder().events.snapshot()
              if e.get("kind") == "link.congested")
    clock = _Clock()
    led = FlowLedger(now=clock)
    led.record("disagg_push", 500, 0.4, src="slow", dst="peer")
    (e,) = led.snapshot()
    assert e["saturation"] == pytest.approx(0.5) and e["congested"] == 0
    led.record("disagg_push", 450, 0.4, src="slow", dst="peer")
    (e,) = led.snapshot()
    assert e["saturation"] >= 0.9 and e["congested"] == 1
    # still saturated: no second edge
    led.record("disagg_push", 100, 0.1, src="slow", dst="peer")
    assert led.snapshot()[0]["congested"] == 1
    assert stage.link_congested.get(link) == c0 + 1
    assert sum(1 for e in flightrec.flight_recorder().events.snapshot()
               if e.get("kind") == "link.congested") == ev0 + 1
    # drain below threshold, then rise again: a second edge
    clock.t += 2.0
    led.record("disagg_push", 100, 0.1, src="slow", dst="peer")
    assert led.snapshot()[0]["congested"] == 1      # re-armed, not fired
    led.record("disagg_push", 900, 0.9, src="slow", dst="peer")
    assert led.snapshot()[0]["congested"] == 2
    # saturation is clamped even past physical capacity
    assert led.snapshot()[0]["saturation"] <= 1.0


def test_all_kinds_feed_pair_ewma():
    """The EWMA blind-spot fix: h2d paging traffic (and every other
    kind with measured seconds) updates llm_kv_pair_bw_bytes_per_s, so
    the TransferCostModel prices pairs it never saw a disagg stream
    on."""
    from dynamo_tpu.llm.kv_cluster.registry import TransferCostModel

    stage = stage_metrics()
    led = FlowLedger(local="77")
    led.record("kvpage_pagein", 4096, 0.002)
    assert stage.kv_pair_bw.get("host:77", "dev:77") > 0
    led.record("kv_fetch_rx", 8192, 0.004, src="d0", dst="77")
    assert stage.kv_pair_bw.get("d0", "77") > 0
    m = TransferCostModel()
    m.update_from_states([("backend", stage.registry.state_dump())])
    bw, source = m.bandwidth_info("d0", "77")
    assert source == "pair" and bw > 0
    # seconds unknown -> bytes still counted, EWMA not polluted
    led.record("kv_fetch_rx", 1, 0.0, src="d9", dst="77")
    assert stage.kv_pair_bw.get("d9", "77") == 0.0
    assert led.total_bytes("kv_fetch_rx") == 8193


def test_flow_with_trace_id_drops_span():
    from dynamo_tpu.utils import tracing

    led = FlowLedger(local="5")
    led.record("disagg_stream_rx", 2048, 0.01, src="a", dst="5",
               trace_id="trace-flows-1")
    spans = tracing.get_tracer().spans_for("trace-flows-1")
    (span,) = [s for s in spans if s.name == "flow.disagg_stream_rx"]
    d = span.to_dict()
    attrs = d.get("attrs") or d.get("fields") or d
    assert int(attrs["bytes"]) == 2048
    assert attrs["src"] == "a" and attrs["dst"] == "5"


# ---------------------------------------------------------------------------
# the cluster-wide fold (dyntop / ctl flows / GET /v1/flows backend)
# ---------------------------------------------------------------------------

def _dump(pairs, bw=None, sat=None, cong=None):
    d = {"dyn_link_bytes_total": {"kind": "counter", "series": {
        _SEP.join((s, t, k)): v for (s, t, k), v in pairs.items()}}}
    if bw:
        d["dyn_link_bw_bytes_per_s"] = {"kind": "gauge", "series": {
            _SEP.join(p): v for p, v in bw.items()}}
    if sat:
        d["dyn_link_saturation"] = {"kind": "gauge", "series": dict(sat)}
    if cong:
        d["dyn_link_congested_total"] = {"kind": "counter",
                                         "series": dict(cong)}
    return d


def test_flows_from_states_fold():
    # both ends of one wire publish the same pair under different kinds:
    # bytes accumulate (each view intact), rates take max (same wire)
    states = [
        ("backend", _dump({("a", "b", "disagg_push"): 100},
                          bw={("a", "b"): 50.0},
                          sat={"a>b": 0.25})),
        ("backend", _dump({("a", "b", "disagg_stream_rx"): 100,
                           ("c", "d", "kv_fetch_rx"): 900},
                          bw={("a", "b"): 75.0},
                          sat={"a>b": 0.5}, cong={"a>b": 2.0})),
    ]
    links = flows_from_states(states)
    assert [(e["src"], e["dst"]) for e in links] == [("c", "d"),
                                                     ("a", "b")]
    ab = links[1]
    assert ab["bytes"] == 200
    assert ab["kinds"] == {"disagg_push": 100, "disagg_stream_rx": 100}
    assert ab["bw"] == 75.0 and ab["saturation"] == 0.5
    assert ab["congested"] == 2
    # fleets that never moved a byte degrade to [] — never crash
    assert flows_from_states([]) == []
    assert flows_from_states([("backend", {})]) == []
    assert flows_from_states(None) == []


def test_fmt_bytes():
    assert fmt_bytes(512) == "512B"
    assert fmt_bytes(2048) == "2.0KB"
    assert fmt_bytes(3 << 20) == "3.0MB"
    assert fmt_bytes(5 << 30) == "5.0GB"


# ---------------------------------------------------------------------------
# churn: one worker's deregistration never erases the survivors' ledger
# ---------------------------------------------------------------------------

async def test_ledger_totals_survive_worker_churn():
    from dynamo_tpu.llm.metrics_aggregator import (StagePublisher,
                                                   clear_worker_keys,
                                                   fetch_stage_states)
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store_server import StoreServer

    srv = StoreServer()
    port = await srv.start()
    try:
        wa = await DistributedRuntime(store_port=port).connect()
        wb = await DistributedRuntime(store_port=port).connect()
        for drt, src in ((wa, "a"), (wb, "b")):
            dump = _dump({(src, "peer", "disagg_push"): 1000})
            pub = StagePublisher(drt.store, "dyn", "backend",
                                 drt.worker_id, drt.lease,
                                 dump_fn=lambda d=dump: d)
            assert await pub.publish() == "full"
        links = flows_from_states(
            await fetch_stage_states(drt.store, "dyn"))
        assert {e["src"] for e in links} == {"a", "b"}

        # worker A deregisters (lease lives on): its links drop, B's
        # totals are untouched
        await clear_worker_keys(wa.store, "dyn", "backend", wa.worker_id)
        links = flows_from_states(
            await fetch_stage_states(wb.store, "dyn"))
        assert [(e["src"], e["bytes"]) for e in links] == [("b", 1000)]
        await wa.close()
        await wb.close()
    finally:
        await srv.stop()


# ---------------------------------------------------------------------------
# GET /v1/flows
# ---------------------------------------------------------------------------

async def test_http_flows_endpoint():
    import aiohttp

    from dynamo_tpu.llm.http_service import HttpService, ModelManager

    record_flow("disagg_push", 4242, 0.01, src="httpflows", dst="sink")
    svc = HttpService(ModelManager(), host="127.0.0.1", port=0)
    base = f"http://127.0.0.1:{await svc.start()}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/flows") as r:
                assert r.status == 200
                data = await r.json()
        assert data["count"] == len(data["links"])
        (e,) = [x for x in data["links"] if x["src"] == "httpflows"]
        assert e["dst"] == "sink" and e["bytes"] >= 4242
        assert e["kinds"]["disagg_push"] >= 4242
    finally:
        await svc.stop()


def test_singleton_chokepoint():
    n0 = flow_ledger().total_bytes("swap_slab")
    record_flow("swap_slab", 77, 0.001)
    assert flow_ledger().total_bytes("swap_slab") == n0 + 77
