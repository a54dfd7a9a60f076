"""The readers of a latent-attention model's per-layer metrics on counters
and a trace summary written by hand: each least-work function against a hand
count, what each metric divides by what, and that a program without the
counters (the parent commit, another model) reads as no value."""

import pytest

from benchmarks.harness import latent, scopes
from benchmarks.harness.catalog import Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "deepseek-v2-5l.longctx"
NEW = ("program.latent_decode_step_mfu_share",
       "scope.attn_latent_decode_roofline_share",
       "scope.attn_latent_prefill_roofline_share",
       "scope.moe_shared_ffn_roofline_share", "attn.latent_keys_per_step")
PEAK_B, PEAK_F = 819e9, 197e12


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
    return cat.data("configs", "deepseek-v2-5l")


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
    assert latent.weights(config) == (1_257_973_760, 23_592_960)
    d = latent.dims(config)
    assert (d["L"], d["routed"], d["R"], d["Fs"], d["Fe"]) == (
        5, 4, 160, 3072, 1536)
    assert (d["Rkv"] + d["rope"]) * 2 == 1152
    # a (lane, key, layer) in decode: 278,528 operations over 1,152 bytes
    assert 2 * d["Hq"] * (2 * d["Rkv"] + d["rope"]) == 278_528


def test_another_configuration_reads_as_nothing(cat):
    for name in ("qwen2-1.5b", "mimo-v2-flash-7l", "keye-vl2-30b-a3b-6l"):
        other = cat.data("configs", name)
        assert latent.dims(other) is None and latent.weights(other) is None
        assert latent.attn_least({}, None, other, "decode") is None
        assert latent.moe_shared_least({}, None, other, 4) is None


def test_a_program_without_the_counters_reads_none(cat, config, monkeypatch):
    scoped(monkeypatch, {"decode": {"dynamo.attn": 1.0}}, {"decode": 1})
    none = {"before": series(), "after": series()}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1}},
             "ops": {}}
    for name in NEW:
        assert reduce(cat, name, none, trace, config) is None
        assert reduce(cat, name, none, None, config) is None


def test_decode_attention_share_by_hand(cat, config, monkeypatch):
    """One traced decode dispatch of 12 lanes x 4 steps at length 4000."""
    rows = 12 * (4000 + 4001 + 4002 + 4003)
    work = captured("decode", dispatches=1, tokens=48,
                    dyn_attn_latent_keys_total=rows,
                    dyn_attn_latent_pairs_total=rows)
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1, "total_s": 40e-3}}}
    scoped(monkeypatch, {"decode": {"dynamo.attn": 2e-3}}, {"decode": 1})
    by_bytes = rows * 1152 * 5 / PEAK_B
    by_flops = rows * 278_528 * 5 / PEAK_F
    assert by_flops > by_bytes          # 242 op/B: just past the ridge
    got = reduce(cat, "scope.attn_latent_decode_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * by_flops / 2e-3)
    assert 0 < got < 100


def test_prefill_attention_share_by_hand(cat, config, monkeypatch):
    """One traced 256-row chunk at positions 1024 .. 1279."""
    pairs = 256 * 1024 + 256 * 257 // 2
    work = captured("prefill", dispatches=1, tokens=256,
                    dyn_attn_latent_keys_total=1280,
                    dyn_attn_latent_pairs_total=pairs)
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_fn": {"runs": 1, "total_s": 20e-3}}}
    scoped(monkeypatch, {"prefill": {"dynamo.attn": 3e-3}}, {"prefill": 1})
    least = max(1280 * 1152 * 5 / PEAK_B,
                2 * pairs * 128 * (192 + 128) * 5 / PEAK_F)
    got = reduce(cat, "scope.attn_latent_prefill_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 3e-3)
    # the decode programs' share reads nothing of a prefill-only capture
    assert reduce(cat, "scope.attn_latent_decode_roofline_share", s, trace,
                  config) is None


def test_shared_and_routed_ffn_share_by_hand(cat, config, monkeypatch):
    """One decode dispatch (4 steps, 48 tokens, 60 held experts hit, 70 held
    assignments) and one chunk (256 tokens, all 160 hit, 380 held)."""
    work = {**captured("decode", dispatches=1, tokens=48,
                       dyn_moe_experts_hit_total=60,
                       dyn_moe_assignments_total=70),
            **captured("prefill", dispatches=1, tokens=256,
                       dyn_moe_experts_hit_total=160,
                       dyn_moe_assignments_total=380)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 1, "total_s": 40e-3},
                         "jit_fn": {"runs": 1, "total_s": 20e-3}}}
    scoped(monkeypatch, {"decode": {"dynamo.moe_ffn": 20e-3},
                         "prefill": {"dynamo.moe_ffn": 10e-3}},
           {"decode": 1, "prefill": 1})
    every = 5120 * 160 + 3 * 5120 * 3072            # router + shared
    bytes_ = ((4 + 1) * 4 * every + 220 * 23_592_960) * 2
    flops = 2 * 3 * 5120 * (3072 * 304 * 4 + 1536 * 450)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "scope.moe_shared_ffn_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 30e-3)
    assert 0 < got < 100


def test_the_whole_steps_share_by_hand(cat, config):
    """Two traced decode dispatches of 4 steps, 10 lanes at length 2000."""
    rows = 2 * 10 * (2000 + 2001 + 2002 + 2003)
    work = captured("decode", dispatches=2, tokens=80,
                    dyn_attn_latent_keys_total=rows,
                    dyn_attn_latent_pairs_total=rows,
                    dyn_moe_experts_hit_total=100,
                    dyn_moe_assignments_total=120)
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 80e-3}}}
    bytes_ = (8 * 1_257_973_760 + 100 * 23_592_960) * 2 + rows * 1152 * 5
    flops = (2 * 1_257_973_760 * 80 + 2 * 23_592_960 * 120
             + rows * 278_528 * 5)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "program.latent_decode_step_mfu_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 80e-3)
    assert 0 < got < 100
    # a capture cut short: fewer runs than dispatches scale the work DOWN
    cut = {"modules": {"jit_step": {"runs": 1, "total_s": 40e-3}}}
    half = reduce(cat, "program.latent_decode_step_mfu_share", s, cut,
                  config)
    assert half == pytest.approx(got, rel=1e-9)


def test_rows_a_step_over_the_window(cat, config):
    after = series({
        ("dyn_attn_latent_keys_total", (("kind", "decode"),)): 4.8e6,
        ("dyn_attn_latent_keys_total", (("kind", "prefill"),)): 9e9,
        ("dyn_engine_dispatches_total", (("kind", "decode"),)): 300.0,
        ("dyn_engine_dispatches_total", (("kind", "prefill"),)): 500.0})
    s = {"before": series(), "after": after}
    assert reduce(cat, "attn.latent_keys_per_step", s, None,
                  config) == 4.8e6 / (300 * 4)


def test_the_manifest_lists_the_five_for_this_cell_alone(cat):
    listed = {m["name"]: m for m in cat.manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    mine = {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert set(NEW) <= mine
    assert {"moe.held_assignment_share", "moe.rows_per_expert_hit"} <= mine
    # step.py calls this configuration unknown: its share is not listed here
    assert "program.decode_step_mfu_share" not in mine
    from benchmarks.harness import step
    assert "low-rank" in step.unknown(cat.data("configs", "deepseek-v2-5l"))
