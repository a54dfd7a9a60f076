"""The readers of the per-layer metrics of a model with gated
short-convolution layers on counters and a trace summary written by hand:
each least-work function against a hand count, what each metric divides by
what, and that a program without the counters (the parent commit, another
model) reads as no value."""

import pytest

from benchmarks.harness import scopes, shortconv
from benchmarks.harness.catalog import Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "lfm2-24b-a2b-8l.toolcalls"
NEW = ("program.shortconv_decode_step_mfu_share",
       "scope.conv_step_roofline_share", "scope.conv_scan_roofline_share",
       "moe.expert_read_share")
PEAK_B, PEAK_F = 819e9, 197e12
FIXED, EXPERT = 401_342_464, 9_437_184


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
    return cat.data("configs", "lfm2-24b-a2b-8l")


def reduce(cat, name, scrapes, trace, config):
    return cat.module("layer_metrics", name).reduce(
        scrapes, trace,
        {"config": config, "engine": config["benchmark"]["engine"]})


def scoped(monkeypatch, kinds, runs):
    """What ``scopes.of`` would read from a capture: seconds by kind of
    program and scope."""
    monkeypatch.setattr(scopes, "of",
                        lambda trace: {"kinds": kinds, "runs": runs})


def test_the_weights_a_step_reads_are_the_issues_count(config):
    assert shortconv.weights(config) == (FIXED, EXPERT)
    d = shortconv.dims(config)
    assert (d["conv"], d["attn"], d["dense"], d["routed"], d["E"]) == (
        6, 2, 2, 6, 64)
    # 6 conv operators, 2 attention operators, 2 dense feed-forwards, 6
    # routers, the tied head once
    assert FIXED == (6 * 16_777_216 + 2 * 10_485_760 + 2 * 72_351_744
                     + 6 * 131_072 + 134_217_728)
    assert EXPERT == 3 * 2048 * 1536
    # the published 40 layers: 30 conv, 10 attention, 38 routed
    whole = {**config, "num_hidden_layers": 40,
             "layer_types": config["layer_types"] * 5}
    d40 = shortconv.dims(whole)
    assert (d40["conv"], d40["attn"], d40["routed"]) == (30, 10, 38)


@pytest.mark.parametrize("kind, served, tokens", [
    ("decode", 48, 40), ("prefill", 3, 1400)])
def test_the_recurrences_least_is_the_hand_count(config, kind, served,
                                                 tokens):
    """A served lane-step's two tail rows once in and once out (2 x 8,192
    B), a real token's B, C, z in and y out (4 x 4,096 B), 8 operations a
    channel a token; x 6 conv layers."""
    work = captured(kind, dispatches=2, tokens=tokens,
                    dyn_ssm_active_lane_steps_total=served,
                    dyn_ssm_tokens_total=tokens)
    s = {"before": series(), "after": series(work)}
    module = {"decode": "jit_step", "prefill": "jit_fn"}[kind]
    trace = {"modules": {module: {"runs": 2, "total_s": 0.1}}}
    run = {"config": config, "engine": config["benchmark"]["engine"]}
    bytes_, flops, by_kind = shortconv.conv_least(s, trace, run, kind)
    assert bytes_ == 6 * (served * 2 * 8192 + tokens * 4 * 4096)
    assert flops == 6 * tokens * 8 * 2048
    assert by_kind == {kind: tokens}


def test_another_configuration_reads_as_nothing(cat):
    for name in ("qwen2-1.5b", "mimo-v2-flash-7l", "granite-4.0-h-micro",
                 "deepseek-v2-5l"):
        other = cat.data("configs", name)
        run = {"config": other, "engine": other["benchmark"]["engine"]}
        assert shortconv.dims(other) is None
        assert shortconv.weights(other) is None
        assert shortconv.conv_least({}, None, run, "decode") is None
        assert shortconv.expert_read_share({}, run) is None
        s = {"before": series(), "after": series()}
        trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1}}}
        for metric in NEW:
            assert reduce(cat, metric, s, trace, other) is None


def test_a_program_without_the_counters_reads_none(cat, config, monkeypatch):
    scoped(monkeypatch, {"decode": {"dynamo.ssm_step": 1.0},
                         "prefill": {"dynamo.ssm_scan": 1.0}},
           {"decode": 1, "prefill": 1})
    none = {"before": series(), "after": series()}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                         "jit_fn": {"runs": 2, "total_s": 0.1}},
             "ops": {}}
    for name in NEW:
        assert reduce(cat, name, none, trace, config) is None
        assert reduce(cat, name, none, None, config) is None


def test_the_step_and_scan_shares_by_hand(cat, config, monkeypatch):
    """Two traced decode dispatches (4 steps, 10 served lanes) and one
    1,000-token chunk."""
    work = {**captured("decode", dispatches=2, tokens=80,
                       dyn_ssm_active_lane_steps_total=80,
                       dyn_ssm_tokens_total=80),
            **captured("prefill", dispatches=1, tokens=1000,
                       dyn_ssm_active_lane_steps_total=1,
                       dyn_ssm_tokens_total=1000)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 80e-3},
                         "jit_fn": {"runs": 1, "total_s": 20e-3}}}
    scoped(monkeypatch, {"decode": {"dynamo.ssm_step": 4e-4},
                         "prefill": {"dynamo.ssm_scan": 2e-4}},
           {"decode": 2, "prefill": 1})
    step = 6 * 80 * (2 * 8192 + 4 * 4096) / PEAK_B
    assert 6 * 80 * 8 * 2048 / PEAK_F < step          # bound by bytes
    got = reduce(cat, "scope.conv_step_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * step / 4e-4)
    scan = 6 * (2 * 8192 + 1000 * 4 * 4096) / PEAK_B
    got = reduce(cat, "scope.conv_scan_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * scan / 2e-4)
    assert 0 < got < 100


def test_the_whole_steps_share_by_hand(cat, config):
    """Two traced decode dispatches of 4 steps, 10 lanes: 150 experts hit by
    the 48 routed-layer calls, 1,920 assignments."""
    work = captured("decode", dispatches=2, tokens=80,
                    dyn_ssm_active_lane_steps_total=80,
                    dyn_ssm_tokens_total=80,
                    dyn_moe_experts_hit_total=150,
                    dyn_moe_assignments_total=1920)
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 80e-3}}}
    bytes_ = (8 * FIXED + 150 * EXPERT) * 2 + 6 * 80 * (2 * 8192 + 4 * 4096)
    flops = 2 * FIXED * 80 + 2 * EXPERT * 1920 + 6 * 80 * 8 * 2048
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "program.shortconv_decode_step_mfu_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 80e-3)
    assert 0 < got < 100
    # a capture cut short: fewer runs than dispatches scale the work DOWN
    cut = {"modules": {"jit_step": {"runs": 1, "total_s": 40e-3}}}
    half = reduce(cat, "program.shortconv_decode_step_mfu_share", s, cut,
                  config)
    assert half == pytest.approx(got, rel=1e-9)


def test_the_share_of_the_experts_a_step_had_to_read(cat, config):
    after = series({
        ("dyn_moe_experts_hit_total", (("kind", "decode"),)): 300 * 24 * 21,
        ("dyn_moe_experts_hit_total", (("kind", "prefill"),)): 9e9,
        ("dyn_moe_layer_calls_total", (("kind", "decode"),)): 300.0 * 24,
        ("dyn_moe_layer_calls_total", (("kind", "prefill"),)): 500.0 * 6})
    s = {"before": series(), "after": after}
    got = reduce(cat, "moe.expert_read_share", s, None, config)
    assert got == pytest.approx(100 * 21 / 64)


def test_the_manifest_lists_the_four_for_this_cell_alone(cat):
    listed = {m["name"]: m for m in cat.manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    mine = {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert set(NEW) <= mine
    assert {"scope.moe_ffn_roofline_share", "moe.rows_per_expert_hit",
            "attn.live_page_share"} <= mine
    # step.py calls this configuration unknown: its share is not listed here
    assert "program.decode_step_mfu_share" not in mine
    from benchmarks.harness import step
    assert step.unknown(cat.data("configs", "lfm2-24b-a2b-8l"))
    # the routed layers' least reads this configuration's own key names
    from benchmarks.harness import routed
    cfg = cat.data("configs", "lfm2-24b-a2b-8l")
    s = {"before": series(), "after": series()}
    assert routed.moe_least(s, None, cfg)[:2] == (0.0, 0.0)
