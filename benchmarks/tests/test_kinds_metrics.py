"""The readers of a per-kind model's per-layer metrics on counters and a
trace summary written by hand: what the two counters' shares divide by
what, the least work under the three roofline shares, and that a program
without the counters (the parent commit, a model of one law) reads as no
value. (What the shares divide the least work by, the seconds under
``dynamo.attn_full`` / ``dynamo.attn_window`` / ``dynamo.moe_ffn``:
``test_scopes.py``.)"""

import pytest

from benchmarks.harness import kinds
from benchmarks.harness.catalog import Catalog

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
                 "scope.attn_window_roofline_share",
                 "scope.attn_full_roofline_share",
                 "scope.moe_share_ffn_roofline_share"):
        assert reduce(cat, name, none, {"modules": {}}, config) is None


def test_attention_least_by_hand(config):
    """One traced decode dispatch of 32 lanes x 4 steps at length 1000: a
    window layer must read 128 keys a query, a full one 1000-1003."""
    n_q = 32 * 4
    full_keys = 32 * (1000 + 1001 + 1002 + 1003)
    work = {**captured("decode", dispatches=1, tokens=n_q,
                       attn_full_keys=full_keys, attn_full_pairs=full_keys,
                       attn_window_keys=n_q * 128,
                       attn_window_pairs=n_q * 128)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1}}}
    # full: 2 layers x 4 K/V heads x (192 + 128) x 2 B a key; 64 query heads
    assert kinds.attn_least(s, trace, config, window=False) == (
        full_keys * 2 * 4 * 320 * 2, 2.0 * full_keys * 64 * 320 * 2,
        {"prefill": 0.0, "decode": full_keys})
    # window: 5 layers x 8 K/V heads; decode alone, as the step's bound asks
    assert kinds.attn_least(s, trace, config, True, ("decode",)) == (
        n_q * 128 * 5 * 8 * 320 * 2, 2.0 * n_q * 128 * 64 * 320 * 5,
        {"decode": n_q * 128})


def test_held_experts_least_by_hand(config):
    work = {**captured("decode", dispatches=1, tokens=128,
                       dyn_moe_assignments_total=100,
                       dyn_moe_experts_hit_total=140),
            **captured("prefill", dispatches=1, tokens=256,
                       dyn_moe_assignments_total=300,
                       dyn_moe_experts_hit_total=100)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1}, "jit_fn": {"runs": 1}}}
    one = 3.0 * 4096 * 2048
    bytes_, flops, assigned = kinds.moe_share_least(s, trace, config)
    assert (bytes_, flops) == (240 * one * 2, 2.0 * one * 400)
    assert assigned == {"prefill": 300.0, "decode": 100.0}
    assert bytes_ / 819e9 > flops / 197e12          # memory-bound
    # a configuration that holds all its experts is not this function's
    assert kinds.moe_share_least(s, trace, {
        **config, "n_routed_experts": 0}) is None


def test_the_manifest_lists_the_five_for_this_cell(cat, config):
    assert {"cache.window_resident_share", "moe.held_assignment_share",
            "scope.attn_window_roofline_share",
            "scope.attn_full_roofline_share",
            "scope.moe_share_ffn_roofline_share"} <= {
        m["name"] for m in cat.metrics("per_layer", CELL)}
    assert config["benchmark"]["engine"]["max_batch"] == 32


def test_dims_of_another_configuration_read_as_nothing(cat):
    assert kinds.dims(cat.data("configs", "qwen2-1.5b")) is None
    d = kinds.dims(cat.data("configs", "mimo-v2-flash-7l"))
    assert d["layers"] == {True: 5, False: 2}
    assert d["Hkv"] == {True: 8, False: 4}
