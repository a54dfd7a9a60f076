"""The per-layer metrics that read the engine's own series (loop phases,
dispatch counters, request stages, compiles): each ``reduce`` on hand-made
scrapes, and ``None`` where the program's counter did not move or does not
exist (a parent commit from before these series has none of them)."""

import pytest

from benchmarks.harness.catalog import Catalog

STAGE, PHASE = "llm_request_stage_seconds", "dyn_engine_phase_seconds_total"
DISP, TOK = "dyn_engine_dispatches_total", "dyn_engine_dispatch_tokens_total"


def series(stages=(), phases=(), dispatches=(), tokens=(), compiles=None):
    out = []
    for stage, total, n in stages:
        out += [(STAGE + "_sum", {"stage": stage}, total),
                (STAGE + "_count", {"stage": stage}, n)]
    out += [(PHASE, {"phase": p}, v) for p, v in phases]
    out += [(DISP, {"kind": k}, v) for k, v in dispatches]
    out += [(TOK, {"kind": k}, v) for k, v in tokens]
    if compiles is not None:
        out.append(("dyn_xla_compiles_total", {}, compiles))
    return out


BEFORE = series(
    stages=[("pre_engine", 1.0, 10), ("queue", 2.0, 10),
            ("lane_wait", 0.5, 10), ("prefill", 3.0, 10),
            ("post_engine", 0.1, 10)],
    phases=[("inbox", 1.0), ("admit", 1.0), ("prefill_fetch", 2.0),
            ("decode_fetch", 10.0), ("emit", 3.0), ("idle", 100.0)],
    dispatches=[("prefill", 10), ("decode", 40)],
    tokens=[("prefill", 1000), ("decode", 1280)], compiles=60)
AFTER = series(
    stages=[("pre_engine", 1.04, 30), ("queue", 4.0, 30),
            ("lane_wait", 0.52, 30), ("prefill", 9.0, 30),
            ("post_engine", 0.2, 30)],
    phases=[("inbox", 1.5), ("admit", 2.0), ("prefill_fetch", 4.0),
            ("decode_fetch", 24.0), ("emit", 5.5), ("idle", 130.0)],
    dispatches=[("prefill", 70), ("decode", 140)],
    tokens=[("prefill", 7000), ("decode", 4480)], compiles=63)
# host at work: inbox 0.5 + admit 1.0 + emit 2.5 = 4.0 s; waiting for the
# device: 2.0 + 14.0 = 16.0 s; idle 30 s is in neither
WANT = {
    "frontend.pre_engine_ms": 2.0, "frontend.post_engine_ms": 5.0,
    "engine.queue_wait_ms": 100.0, "engine.lane_wait_ms": 1.0,
    "engine.prefill_span_ms": 300.0,
    "engine.prefill_tokens_per_dispatch": 100.0,
    "engine.host_ms_per_dispatch": 25.0,
    "engine.device_wait_share": 80.0,
    "program.compiles_in_window": 3.0,
}


@pytest.fixture(scope="module")
def cat():
    return Catalog()


@pytest.mark.parametrize("name", sorted(WANT))
def test_value_from_the_deltas(cat, name):
    reduce = cat.module("layer_metrics", name).reduce
    assert reduce({"before": BEFORE, "after": AFTER}, None, {}) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_none_when_nothing_moved_or_the_program_has_no_such_series(cat, name):
    reduce = cat.module("layer_metrics", name).reduce
    if name != "program.compiles_in_window":   # a count that stood still is 0
        assert reduce({"before": AFTER, "after": AFTER}, None, {}) is None
    other = [("llm_ttft_seconds_count", {"model": "m"}, 3.0)]
    assert reduce({"before": other, "after": other}, None, {}) is None


def test_no_compile_in_the_window_reads_zero(cat):
    reduce = cat.module("layer_metrics", "program.compiles_in_window").reduce
    assert reduce({"before": AFTER, "after": AFTER}, None, {}) == 0.0


def test_every_new_metric_is_in_the_manifest_with_a_file(cat):
    by_name = {m["name"]: m for m in cat.manifest["per_layer"]}
    assert set(WANT) <= set(by_name)
    # the stages are read in every cell that reports ttft_p50_ms, open loop
    # or closed (since PR 37; the two open-loop cells until then)
    first_token = next(m for m in cat.manifest["end_to_end"]
                       if m["name"] == "ttft_p50_ms")["workloads"]
    for name in WANT:
        cells = by_name[name].get("workloads")
        assert cells in (None, first_token), name
