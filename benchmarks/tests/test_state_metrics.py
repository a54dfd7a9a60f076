"""The readers of the per-layer metrics of a model with state-space layers
on counters and a trace summary written by hand: what the counters' share
divides by what, that a served lane's state counts once in and once out a
STEP in the least work under the two roofline shares, and that a program
without the counters (the parent commit, a model without such layers) reads
as no value. (What the shares divide the least work by, the seconds under
``dynamo.ssm_step`` / ``dynamo.ssm_scan``: ``test_scopes.py``.)"""

import pytest

from benchmarks.harness import state
from benchmarks.harness.catalog import Catalog
from benchmarks.harness.routed import roofline_share

CAP = "dyn_profile_captured_work_total"
CELL = "granite-4.0-h-micro.manylanes"
NEW = ("scope.ssm_step_roofline_share", "scope.ssm_scan_roofline_share",
       "ssm.active_state_share")
STATE = 64 * 64 * 128                      # a lane's state a layer, elements
TOKEN = (3 * 4096 + 2 * 128 + 64) * 2      # X, z, y, B, C, dt in bfloat16


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
        assert reduce(cat, name, none, {"modules": {}}, config) is None
    # another configuration's file: nothing to read, whatever the counters
    other = cat.data("configs", "qwen2-1.5b")
    assert state.dims(other) is None
    assert state.ssm_least(s, None, {"config": other, "engine": {}},
                           "decode") is None


def least(scrapes, trace, config, kind):
    return state.ssm_least(scrapes, trace, {
        "config": config, "engine": config["benchmark"]["engine"]}, kind)


def test_the_decode_least_counts_a_state_once_a_step(config):
    """One traced decode dispatch of 4 steps that served 48 of the pool's
    64 lanes: the least is 48 states in and out EACH STEP (192 served
    lane-steps: not 64 lanes, and not once a dispatch as until PR 37) and
    192 tokens' activations, in each of 36 layers."""
    work = captured("decode", dispatches=1, tokens=192,
                    **{state.ACTIVE: 192, state.TOKENS: 192,
                       state.LANE_STEPS: 256})
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1}}}
    bytes_, flops, spent = least(s, trace, config, "decode")
    assert bytes_ == 36 * (2 * 192 * STATE * 4 + 192 * TOKEN)
    assert flops == 36 * 4 * 192 * STATE
    assert spent == {"decode": 192}
    # memory-bound: a kernel that takes the bytes' time reads 100 (a served
    # lane's state once in and once out a step is the floor), one that takes
    # 450 us a layer and step reads 55
    at = bytes_ / 819e9
    assert at > flops / 197e12
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    assert roofline_share(bytes_, flops, at, peaks) == pytest.approx(100.0)
    assert roofline_share(bytes_, flops, 144 * 450e-6, peaks) == \
        pytest.approx(100 * at / (144 * 450e-6))
    # the chunk's least reads nothing of a run that traced no chunk
    assert least(s, trace, config, "prefill")[:2] == (0.0, 0.0)


def test_the_chunk_least_by_hand(config):
    """Two traced chunks of one row each, 256 + 100 real tokens: a state in
    and out a chunk; operations 4 a state element a token."""
    work = captured("prefill", dispatches=2, tokens=356,
                    **{state.ACTIVE: 2, state.TOKENS: 356,
                       state.LANE_STEPS: 2})
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_fn": {"runs": 2}}}
    got = least(s, trace, config, "prefill")
    assert got == (36 * (2 * 2 * STATE * 4 + 356 * TOKEN),
                   36 * 4 * 356 * STATE, {"prefill": 356})
    # a capture cut at its last dispatch: the work is scaled down, not up
    half = least(s, {"modules": {"jit_fn": {"runs": 1}}}, config, "prefill")
    assert half[:2] == (got[0] / 2, got[1] / 2)
    assert least(s, {"modules": {"jit_fn": {"runs": 7}}}, config,
                 "prefill") == got


def test_the_manifest_lists_the_three_for_this_cell(cat, config):
    assert set(NEW) <= {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert config["benchmark"]["engine"]["max_batch"] == 64
    assert state.dims(config) == {"layers": 36, "H": 64, "P": 64, "N": 128,
                                  "I": 4096}
