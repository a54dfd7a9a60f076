"""The readers of the per-layer metrics of a model with state-space layers
on counters and a trace summary written by hand: what each divides by what,
that a served lane's state counts once in and once out a STEP, that the
scope lists name the cell's own state pool, and that a program
without the counters (the parent commit, a model without such layers) reads
as no value."""

import json
import os

import pytest

from benchmarks.harness import state
from benchmarks.harness.catalog import BENCH, BenchError, Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "granite-4.0-h-micro.manylanes"
NEW = ("kernel.ssm_step_roofline_share", "kernel.ssm_scan_roofline_share",
       "ssm.active_state_share")
STATE = 64 * 64 * 128                      # a lane's state a layer, elements
TOKEN = (3 * 4096 + 2 * 128 + 64) * 2      # X, z, y, B, C, dt in bfloat16
POOL = "f32[36,64,64,64,128]"


def series(counters=None):
    out = [("dyn_engine_info", {"platform": "tpu",
                                "device_kind": "TPU v5 lite"}, 1.0)]
    for (name, labels), v in (counters or {}).items():
        out.append((name, dict(labels), float(v)))
    return out


def captured(kind, **amounts):
    return {(CAP, (("counter", c), ("kind", kind))): v
            for c, v in amounts.items()}


@pytest.fixture(scope="module")
def cat():
    return Catalog()


@pytest.fixture(scope="module")
def config(cat):
    return cat.data("configs", "granite-4.0-h-micro")


def reduce(cat, name, scrapes, trace, config):
    return cat.module("layer_metrics", name).reduce(
        scrapes, trace, {"config": config,
                         "engine": config["benchmark"]["engine"]})


def test_the_counter_share_and_a_program_without_counters(cat, config):
    after = series({
        (state.LANE_STEPS, (("kind", "decode"),)): 100 * 64 * 4,
        (state.ACTIVE, (("kind", "decode"),)): 100 * 48 * 4,
        (state.LANE_STEPS, (("kind", "prefill"),)): 400.0,
        (state.ACTIVE, (("kind", "prefill"),)): 400.0})
    s = {"before": series(), "after": after}
    got = reduce(cat, "ssm.active_state_share", s, None, config)
    assert got == pytest.approx(100 * (19200 + 400) / (25600 + 400))
    none = {"before": series(), "after": series()}
    for name in NEW:
        assert reduce(cat, name, none, {"ops": {}, "modules": {}},
                      config) is None
    # another configuration's file: nothing to read, whatever the counters
    other = cat.data("configs", "qwen2-1.5b")
    assert state.dims(other) is None
    assert state.ssm_least(s, None, {"config": other, "engine": {}},
                           "decode") is None


def test_the_decode_share_counts_a_state_once_a_step(cat, config):
    """One traced decode dispatch of 4 steps that served 48 of the pool's
    64 lanes: the least is 48 states in and out EACH STEP (192 served
    lane-steps: not 64 lanes, and not once a dispatch as until PR 37) and
    192 tokens' activations, in each of 36 layers; the program's time is
    what the listed operations took, of which the keys it shares with the
    norms outside the scope count nothing."""
    work = captured("decode", dispatches=1, tokens=192,
                    **{state.ACTIVE: 192, state.TOKENS: 192,
                       state.LANE_STEPS: 256})
    s = {"before": series(), "after": series(work)}
    update = f"select_dynamic-update-slice_fusion {POOL}"
    trace = {"modules": {"jit_step": {"runs": 1}}, "ops": {
        update: {"events": 144, "total_s": 144 * 400e-6},
        "fusion f32[64,64,64]": {"events": 144, "total_s": 144 * 50e-6},
        "multiply_reduce_fusion f32[64]": {"events": 600,
                                           "total_s": 600 * 300e-6},
        "tpu_custom_call bf16[64,8,4,64]": {"events": 16,
                                            "total_s": 16 * 80e-6}}}
    least = 36 * (2 * 192 * STATE * 4 + 192 * TOKEN) / 819e9
    got = reduce(cat, "kernel.ssm_step_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * least / (144 * 450e-6))
    assert 0 < got < 100
    # the same operations at the peak read 100: a served lane's state once
    # in and once out a step is the floor
    update_at = dict(trace, ops={update: {"events": 144, "total_s": least}})
    assert reduce(cat, "kernel.ssm_step_roofline_share", s, update_at,
                  config) == pytest.approx(100.0)
    # the chunk's metric reads nothing of a run that traced no chunk
    assert reduce(cat, "kernel.ssm_scan_roofline_share", s, trace,
                  config) is None
    # decode programs ran with such work and the trace holds no write of
    # the state pool: the list no longer describes the programs
    with pytest.raises(BenchError, match="stale"):
        reduce(cat, "kernel.ssm_step_roofline_share", s,
               {"modules": {"jit_step": {"runs": 1}},
                "ops": {"fusion f32[64,64,64]": {"events": 1,
                                                 "total_s": 1e-3}}}, config)


def test_the_chunk_share_by_hand(cat, config):
    """Two traced chunks of one row each, 256 + 100 real tokens: a state in
    and out a chunk; operations 4 a state element a token."""
    work = captured("prefill", dispatches=2, tokens=356,
                    **{state.ACTIVE: 2, state.TOKENS: 356,
                       state.LANE_STEPS: 2})
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_fn": {"runs": 2}}, "ops": {
        f"fusion {POOL}": {"events": 72, "total_s": 72 * 20e-6},
        "fusion f32[256,64,64]": {"events": 36, "total_s": 36 * 90e-6},
        "fusion f32[128,64,64]": {"events": 36, "total_s": 36 * 40e-6}}}
    bytes_ = 36 * (2 * 2 * STATE * 4 + 356 * TOKEN)
    flops = 36 * 4 * 356 * STATE
    least = max(bytes_ / 819e9, flops / 197e12)
    got = reduce(cat, "kernel.ssm_scan_roofline_share", s, trace, config)
    assert got == pytest.approx(
        100 * least / (72 * 20e-6 + 36 * 130e-6))
    # a capture cut at its last dispatch: the work is scaled down, not up
    half = {**trace, "modules": {"jit_fn": {"runs": 1}}}
    assert reduce(cat, "kernel.ssm_scan_roofline_share", s, half,
                  config) == pytest.approx(got / 2)


def test_the_scope_lists_name_this_cells_state_pool(cat, config):
    eng = config["benchmark"]["engine"]
    for scope, kind in (("ssm_step", "decode"), ("ssm_scan", "prefill")):
        with open(os.path.join(
                BENCH, "layer_metrics",
                f"kernel.{scope}_roofline_share.ops.json")) as f:
            listed = json.load(f)
        assert listed["scope"] == f"dynamo.{scope}"
        assert listed["config"] == "granite-4.0-h-micro"
        assert listed["lanes"] == eng["max_batch"] == 64
        assert len(listed["required"][kind]) == 1
        assert listed["required"][kind][0].endswith(POOL)
        assert set(listed["required"][kind]) <= set(listed["ops"])
        # what the scope shares with the norms outside it counts 0
        assert listed["shared"]["multiply_reduce_fusion f32[64]"] == 0.0
        assert not any(listed["shared"].values())
        assert set(listed["shared"]) <= set(listed["ops"])
    assert set(NEW) <= {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert state.dims(config) == {"layers": 36, "H": 64, "P": 64, "N": 128,
                                  "I": 4096}
