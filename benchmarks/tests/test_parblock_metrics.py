"""The readers of the per-layer metrics of a parallel-block model of two
cache kinds, on counters and a trace summary written by hand: the weight
count against ``init_params``' own, each least-work function against a hand
count, what each metric divides by what, and that a program without the
counters (the parent commit, another model) reads as no value."""

import jax
import pytest

from benchmarks.harness import parblock, scopes
from benchmarks.harness.catalog import Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "command-a-plus-4l.ragdocs"
NEW = ("program.parblock_decode_step_mfu_share",
       "program.parblock_prefill_chunk_mfu_share",
       "scope.parblock_attn_window_roofline_share",
       "scope.parblock_attn_full_roofline_share",
       "scope.parblock_moe_ffn_roofline_share",
       "attn.window_key_share")
PEAK_B, PEAK_F = 819e9, 197e12
D, F = 4096, 4096
EXPERT = 3 * D * F
ATTN = D * 128 * 2 * (128 + 8)      # q, k, v, o of a layer: 142.6 M
LAYER = ATTN + 4 * EXPERT + D * 128 + D     # 344.5 M outside the experts
EMBED = 32768 * D
FIXED = 4 * LAYER + EMBED + D


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
    return cat.data("configs", "command-a-plus-4l")


def reduce(cat, name, scrapes, trace, config):
    return cat.module("layer_metrics", name).reduce(
        scrapes, trace,
        {"config": config, "engine": config["benchmark"]["engine"]})


def scoped(monkeypatch, kinds, runs):
    monkeypatch.setattr(scopes, "of",
                        lambda trace: {"kinds": kinds, "runs": runs})
    monkeypatch.setattr(parblock, "scope_seconds", scopes.scope_seconds)


def test_the_weights_a_pass_reads_are_init_params_own(config):
    """``weights(config)`` against the shapes of the program's own seeded
    init: everything but the routed experts is read by every pass, and
    fixed + layers x held experts x one expert is the whole tree."""
    assert parblock.weights(config) == (FIXED, EMBED, EXPERT)
    assert round(LAYER / 1e6, 1) == 344.5 and round(ATTN / 1e6, 1) == 142.6
    from dynamo_tpu.models import llama
    cfg = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    st = shapes["stacks"]
    experts = size([st["routed"][n] for n in ("wg", "wu", "wd")])
    assert experts == 4 * 16 * EXPERT
    assert size(shapes) == FIXED + experts == 4733292544    # 9.47 GB
    d = parblock.dims(config)
    assert (d["L"], d["E"], d["R"], d["S"], d["V"]) == (4, 16, 128, 4, 32768)
    assert d["layers"] == {True: 3, False: 1}


def test_the_attentions_least_is_by_kind(config):
    pairs_f = 512 * 9728 + 512 * 513 // 2           # a chunk behind 9,728
    pairs_w = 512 * 4096
    work = {**captured("decode", dispatches=2, tokens=24,
                       attn_full_keys=240_000, attn_full_pairs=240_000,
                       attn_window_keys=98_304, attn_window_pairs=98_304),
            **captured("prefill", dispatches=1, tokens=512,
                       attn_full_keys=10_240, attn_full_pairs=pairs_f,
                       attn_window_keys=4095 + 512,
                       attn_window_pairs=pairs_w)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                         "jit_fn": {"runs": 1, "total_s": 0.1}}}
    row = 8 * 256 * 2                               # K and V of a key: 4 KiB
    b, f, by = parblock.attn_least(s, trace, config, False)
    assert b == (240_000 + 10_240) * row * 1
    assert f == 2 * (240_000 + pairs_f) * 128 * 256 * 1
    assert by == {"prefill": 10_240, "decode": 240_000}
    b, f, by = parblock.attn_least(s, trace, config, True, kinds=("decode",))
    assert b == 98_304 * row * 3 and f == 2 * 98_304 * 128 * 256 * 3
    # the witness: three window layers of 4,096 keys beside the whole context
    got = parblock.window_key_share(
        s, trace, {"config": config})
    w, f_ = 3 * (98_304 + 4607), 240_000 + 10_240
    assert got == pytest.approx(100 * w / (w + f_)) and got < 75


def test_another_configuration_reads_as_nothing(cat):
    for name in ("qwen2-1.5b", "mimo-v2-flash-7l", "granite-4.0-h-micro",
                 "deepseek-v2-5l", "lfm2-24b-a2b-8l",
                 "longcat-flash-omni-4l"):
        other = cat.data("configs", name)
        assert parblock.dims(other) is None and parblock.weights(other) is None
        assert parblock.attn_least({}, None, other, True) is None
        assert parblock.ffn_least({}, None, other, 4) is None
        s = {"before": series(), "after": series()}
        trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                             "jit_fn": {"runs": 2, "total_s": 0.1}}}
        for metric in NEW:
            assert reduce(cat, metric, s, trace, other) is None


def test_a_program_without_the_counters_reads_none(cat, config, monkeypatch):
    every = {"dynamo.attn_window": 1.0, "dynamo.attn_full": 1.0,
             "dynamo.ffn": 1.0, "dynamo.moe_ffn": 1.0}
    scoped(monkeypatch, {"decode": every, "prefill": every},
           {"decode": 1, "prefill": 1})
    none = {"before": series(), "after": series()}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                         "jit_fn": {"runs": 2, "total_s": 0.1}},
             "ops": {}}
    for name in NEW:
        assert reduce(cat, name, none, trace, config) is None
        assert reduce(cat, name, none, None, config) is None


WORK = {**captured("decode", dispatches=2, tokens=24,
                   attn_full_keys=240_000, attn_full_pairs=240_000,
                   attn_window_keys=98_304, attn_window_pairs=98_304,
                   dyn_moe_experts_hit_total=80,
                   dyn_moe_assignments_total=26),
        **captured("prefill", dispatches=1, tokens=512,
                   attn_full_keys=10_240,
                   attn_full_pairs=512 * 9728 + 512 * 513 // 2,
                   attn_window_keys=4607, attn_window_pairs=512 * 4096,
                   dyn_moe_experts_hit_total=64,
                   dyn_moe_assignments_total=520)}
ROW = 8 * 256 * 2


def test_the_three_shares_of_a_scope_by_hand(cat, config, monkeypatch):
    """Two traced decode dispatches (4 steps, 3 lanes at 10,000 tokens) and
    one 512-row chunk behind 9,728 tokens."""
    s = {"before": series(), "after": series(WORK)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 60e-3},
                         "jit_fn": {"runs": 1, "total_s": 40e-3}}}
    scoped(monkeypatch,
           {"decode": {"dynamo.attn_window": 3e-3, "dynamo.attn_full": 2e-3,
                       "dynamo.ffn": 15e-3, "dynamo.moe_ffn": 25e-3},
            "prefill": {"dynamo.attn_window": 5e-3, "dynamo.attn_full": 4e-3,
                        "dynamo.ffn": 9e-3, "dynamo.moe_ffn": 9e-3}},
           {"decode": 2, "prefill": 1})
    pairs = 240_000 + 512 * 9728 + 512 * 513 // 2
    least = max(250_240 * ROW / PEAK_B, 2 * pairs * 128 * 256 / PEAK_F)
    got = reduce(cat, "scope.parblock_attn_full_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 6e-3) and 0 < got < 100
    pairs = 98_304 + 512 * 4096
    least = max((98_304 + 4607) * ROW * 3 / PEAK_B,
                2 * pairs * 128 * 256 * 3 / PEAK_F)
    got = reduce(cat, "scope.parblock_attn_window_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 8e-3) and 0 < got < 100
    # the feed-forward branch in DECODE: 8 steps x 4 layers of router and
    # shared experts, 80 held experts hit; both scopes' seconds
    every = D * 128 + 4 * EXPERT
    bytes_ = (8 * 4 * every + 80 * EXPERT) * 2
    flops = 2 * (every * 4 * 24 + EXPERT * 26)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "scope.parblock_moe_ffn_roofline_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 40e-3) and 0 < got < 100
    # a scope that left the program RAISES
    scoped(monkeypatch, {"decode": {"dynamo.moe_ffn": 12e-3}}, {"decode": 2})
    with pytest.raises(Exception, match="scope"):
        reduce(cat, "scope.parblock_moe_ffn_roofline_share", s, trace, config)


def test_the_whole_programs_shares_by_hand(cat, config):
    s = {"before": series(), "after": series(WORK)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 60e-3},
                         "jit_fn": {"runs": 1, "total_s": 40e-3}}}
    bytes_ = ((8 * FIXED + 80 * EXPERT) * 2 + 240_000 * ROW
              + 98_304 * ROW * 3)
    flops = (2 * FIXED * 24 + 2 * EXPERT * 26
             + 2 * 240_000 * 128 * 256 + 2 * 98_304 * 128 * 256 * 3)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    decode = reduce(cat, "program.parblock_decode_step_mfu_share", s, trace,
                    config)
    assert decode == pytest.approx(100 * least / 60e-3) and 0 < decode < 100
    # a step reads 3.03 GB of fixed weights: at least 3.7 ms
    assert FIXED * 2 / PEAK_B == pytest.approx(3.70e-3, rel=1e-2)
    # the chunk: the layers' matrices once, no head
    layers = FIXED - EMBED
    bytes_ = ((layers + 64 * EXPERT) * 2 + 10_240 * ROW + 4607 * ROW * 3)
    flops = (2 * layers * 512 + 2 * EXPERT * 520
             + 2 * (512 * 9728 + 512 * 513 // 2) * 128 * 256
             + 2 * 512 * 4096 * 128 * 256 * 3)
    # (at 512 rows the two least times lie within 2 % of each other: 11.2
    # ms of operations, 11.4 ms of bytes; the share is of the larger)
    least = max(flops / PEAK_F, bytes_ / PEAK_B)
    assert abs(flops / PEAK_F - bytes_ / PEAK_B) < 0.05 * least
    got = reduce(cat, "program.parblock_prefill_chunk_mfu_share", s, trace,
                 config)
    assert got == pytest.approx(100 * least / 40e-3)
    assert 0 < got < 100
    # a capture cut short: fewer runs than dispatches scale the work DOWN
    cut = {"modules": {"jit_step": {"runs": 1, "total_s": 30e-3}}}
    half = reduce(cat, "program.parblock_decode_step_mfu_share", s, cut,
                  config)
    assert half == pytest.approx(decode, rel=1e-9)


def test_the_manifest_lists_the_six_for_this_cell_alone(cat):
    listed = {m["name"]: m for m in cat.manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    mine = {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert set(NEW) <= mine
    assert {"moe.rows_per_expert_hit", "moe.held_assignment_share",
            "cache.window_resident_share", "attn.live_page_share",
            "program.prefill_chunk_ms", "client.ttft_p90_ms"} <= mine
    # nothing that goes through harness/kinds.py, which knows a per-kind
    # model by ``hybrid_layer_pattern`` and reads this file as None
    from benchmarks.harness import kinds, step
    config = cat.data("configs", "command-a-plus-4l")
    assert kinds.dims(config) is None
    assert not {m for m in mine if ("attn_window" in m or "attn_full" in m
                                    or "moe_share" in m)
                and "parblock" not in m}
    assert "program.decode_step_mfu_share" not in mine
    assert step.unknown(config)
    cell = cat.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "command-a-plus-4l", "ragdocs", 1)
    mix = cat.data("traffic", "ragdocs")
    assert mix["generator"] == "open_poisson"
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                    "sigma": 0.7, "min": 2048, "max": 24576}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 64, "max": 384}
    a = mix["arrivals"]
    assert a["rate_per_s"] == pytest.approx(0.8 * a["knee_per_s"], rel=0.02)
