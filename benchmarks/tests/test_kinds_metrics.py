"""The readers of a per-kind model's per-layer metrics on counters and a
trace summary written by hand: what each divides by what, that the program's
scope lists name the cell's own kernels, and that a program without the
counters (the parent commit, a model of one law) reads as no value."""

import json
import os

import pytest

from benchmarks.harness import kinds
from benchmarks.harness.catalog import BENCH, Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "mimo-v2-flash-7l.mixedqueue"


def series(counters=None):
    counters = counters or {}
    out = [("dyn_engine_info", {"platform": "tpu",
                                "device_kind": "TPU v5 lite"}, 1.0)]
    for (name, labels), v in counters.items():
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
    return cat.data("configs", "mimo-v2-flash-7l")


def reduce(cat, name, scrapes, trace, config):
    return cat.module("layer_metrics", name).reduce(
        scrapes, trace, {"config": config})


def test_the_two_counter_shares(cat, config):
    before = series()
    after = series({
        (kinds.RESIDENT, (("pool", "window"),)): 160.0,
        (kinds.RESIDENT, (("pool", "global"),)): 2000.0,
        ("dyn_moe_assignments_total", (("kind", "decode"),)): 50.0,
        ("dyn_moe_assignments_total", (("kind", "prefill"),)): 75.0,
        (kinds.ROUTED, (("kind", "decode"),)): 800.0,
        (kinds.ROUTED, (("kind", "prefill"),)): 1200.0})
    s = {"before": before, "after": after}
    assert reduce(cat, "cache.window_resident_share", s, None, config) == 8.0
    assert reduce(cat, "moe.held_assignment_share", s, None, config) == 6.25
    # a program without the counters: no value, no error
    none = {"before": before, "after": series()}
    for name in ("cache.window_resident_share", "moe.held_assignment_share",
                 "kernel.attn_window_roofline_share",
                 "kernel.attn_full_roofline_share",
                 "kernel.moe_share_ffn_roofline_share"):
        assert reduce(cat, name, none, {"ops": {}, "modules": {}},
                      config) is None


def test_attention_roofline_shares_by_hand(cat, config):
    """One traced decode dispatch of 32 lanes x 4 steps at length 1000: a
    window layer must read 128 keys a query, a full one 1000-1003."""
    n_q = 32 * 4
    full_keys = 32 * (1000 + 1001 + 1002 + 1003)
    work = {**captured("decode", dispatches=1, tokens=n_q,
                       attn_full_keys=full_keys, attn_full_pairs=full_keys,
                       attn_window_keys=n_q * 128,
                       attn_window_pairs=n_q * 128)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1}}, "ops": {
        "tpu_custom_call bf16[32,4,16,128]":
            {"events": 8, "total_s": 8 * 100e-6},
        "tpu_custom_call bf16[32,8,8,128]":
            {"events": 20, "total_s": 20 * 50e-6},
        "pad_bitcast_fusion bf16[32,8,8,256]":
            {"events": 20, "total_s": 20 * 2e-6}}}
    # full: 2 layers x 4 heads x (192 + 128) x 2 B a key
    least = full_keys * 2 * 4 * 320 * 2 / 819e9
    got = reduce(cat, "kernel.attn_full_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * least / 800e-6)
    # window: 5 layers x 8 heads x 320 x 2 B, over kernel + the pad of q
    least = n_q * 128 * 5 * 8 * 320 * 2 / 819e9
    got = reduce(cat, "kernel.attn_window_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * least / (1000e-6 + 40e-6))
    assert 0 < got < 100


def test_held_experts_roofline_share_by_hand(cat, config):
    # every program of the cell dispatches DENSE (32 rows and more are at
    # least as many expected assignments as experts held): a layer's
    # down-projection shares fusion f32[rows] with attention-out (part
    # 0.7273), in a decode step as in a chunk
    work = {**captured("decode", dispatches=1, tokens=128,
                       dyn_moe_assignments_total=100,
                       dyn_moe_experts_hit_total=140),
            **captured("prefill", dispatches=1, tokens=256,
                       dyn_moe_assignments_total=300,
                       dyn_moe_experts_hit_total=100)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1}, "jit_fn": {"runs": 1}},
             "ops": {
        "fusion bf16[32,16,2048]": {"events": 48, "total_s": 48 * 0.5e-3},
        "fusion f32[32]": {"events": 60, "total_s": 60 * 0.4e-3},
        "fusion bf16[256,16,2048]": {"events": 12, "total_s": 12 * 1e-3},
        "fusion f32[256]": {"events": 15, "total_s": 15 * 0.4e-3}}}
    least = 240 * 3 * 4096 * 2048 * 2 / 819e9       # memory-bound
    got = reduce(cat, "kernel.moe_share_ffn_roofline_share", s, trace, config)
    assert got == pytest.approx(
        100 * least / (24e-3 + 0.7273 * 24e-3 + 12e-3 + 0.7273 * 6e-3))
    # a trace that ran decode programs with such work and holds none of
    # the scope's gate / up fusions of that kind: the list is stale
    from benchmarks.harness.catalog import BenchError
    with pytest.raises(BenchError, match="stale"):
        reduce(cat, "kernel.moe_share_ffn_roofline_share", s,
               {"modules": {"jit_step": {"runs": 1}, "jit_fn": {"runs": 1}},
                "ops": {"fusion bf16[256,16,2048]": {"events": 1,
                                                      "total_s": 1e-3}}},
               config)


def test_the_scope_lists_name_this_cells_kernels(cat, config):
    eng = config["benchmark"]["engine"]
    for kind, heads in (("full", 4), ("window", 8)):
        with open(os.path.join(BENCH, "layer_metrics",
                               f"kernel.attn_{kind}_roofline_share.ops.json"
                               )) as f:
            listed = json.load(f)
        assert listed["scope"] == f"dynamo.attn_{kind}"
        assert listed["config"] == "mimo-v2-flash-7l"
        assert listed["lanes"] == eng["max_batch"]
        # [lanes, Hkv, G, Dv] a decode step; [Hkv, G, chunk, Dv] a chunk
        assert listed["required"]["decode"] == [
            f"tpu_custom_call bf16[32,{heads},{64 // heads},128]"]
        assert len(listed["required"]["prefill"]) == 4
    assert {"cache.window_resident_share", "moe.held_assignment_share",
            "kernel.attn_window_roofline_share",
            "kernel.attn_full_roofline_share",
            "kernel.moe_share_ffn_roofline_share"} <= {
        m["name"] for m in cat.metrics("per_layer", CELL)}


def test_dims_of_another_configuration_read_as_nothing(cat):
    assert kinds.dims(cat.data("configs", "qwen2-1.5b")) is None
    d = kinds.dims(cat.data("configs", "mimo-v2-flash-7l"))
    assert d["layers"] == {True: 5, False: 2}
    assert d["Hkv"] == {True: 8, False: 4}
