"""The readers of the per-layer metrics of a model whose layer is two
latent-attention sublayers with a routed branch across them, on counters and
a trace summary written by hand: the weight count against ``init_params``'
own, each least-work function against a hand count, what each metric divides
by what, and that a program without the counters (the parent commit, another
model) reads as no value."""

import jax
import pytest

from benchmarks.harness import scmoe, scopes
from benchmarks.harness.catalog import Catalog

CAP = "dyn_profile_captured_work_total"
CELL = "longcat-flash-omni-4l.avturns"
NEW = ("program.scmoe_decode_step_mfu_share",
       "scope.scmoe_moe_ffn_roofline_share",
       "scope.scmoe_attn_latent_decode_roofline_share",
       "scope.scmoe_attn_latent_prefill_roofline_share",
       "moe.zero_assignment_share")
PEAK_B, PEAK_F = 819e9, 197e12
LAYER = 638_844_928                 # a published layer outside its experts
FIXED, EXPERT = 4 * LAYER + 16384 * 6144, 3 * 6144 * 2048


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
    return cat.data("configs", "longcat-flash-omni-4l")


def reduce(cat, name, scrapes, trace, config):
    return cat.module("layer_metrics", name).reduce(
        scrapes, trace,
        {"config": config, "engine": config["benchmark"]["engine"]})


def scoped(monkeypatch, kinds, runs):
    monkeypatch.setattr(scopes, "of",
                        lambda trace: {"kinds": kinds, "runs": runs})


def test_the_weights_a_step_reads_are_init_params_own(config):
    """``weights(config)`` against the shapes of the program's own seeded
    init (norm weights and the selection bias, which the least leaves out,
    taken off; the embedding is a row gather, not a matrix a step reads)."""
    assert scmoe.weights(config) == (FIXED, EXPERT)
    proj = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 64 * 128 * 6144)
    assert LAYER == 2 * proj + 2 * 3 * 6144 * 12288 + 6144 * 768
    from dynamo_tpu.models import llama
    cfg = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    st = shapes["stacks"]
    matrices = lambda stack: size({n: a for n, a in stack.items()
                                   if not n.startswith("ln")})
    experts = size([st["routed"][n] for n in ("wg", "wu", "wd")])
    assert experts == 4 * 16 * EXPERT
    fixed = (matrices(st["full"]) + matrices(st["dense"])
             + size(st["routed"]["wr"]) + size(shapes["lm_head"]))
    assert fixed == FIXED
    # everything the program holds: 10.35 GB at 2 B a parameter
    held = size({k: v for k, v in shapes.items()})
    assert 5.15e9 < held < 5.20e9
    d = scmoe.dims(config)
    assert (d["L"], d["E"], d["R"], d["Z"], d["V"]) == (4, 16, 512, 256, 16384)


def test_the_attentions_least_counts_two_sublayers_a_layer(config):
    work = {**captured("decode", dispatches=2, tokens=80,
                       dyn_attn_latent_keys_total=160_000,
                       dyn_attn_latent_pairs_total=160_000),
            **captured("prefill", dispatches=1, tokens=512,
                       dyn_attn_latent_keys_total=2048,
                       dyn_attn_latent_pairs_total=512 * 1536 + 512 * 513 // 2)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                         "jit_fn": {"runs": 1, "total_s": 0.1}}}
    b, f, by = scmoe.attn_least(s, trace, config, "decode")
    assert b == 160_000 * 1152 * 8 and by == {"decode": 160_000}
    assert f == 2 * 160_000 * 64 * (576 + 512) * 8
    b, f, by = scmoe.attn_least(s, trace, config, "prefill")
    assert b == 2048 * 1152 * 8
    assert f == 2 * (512 * 1536 + 512 * 513 // 2) * 64 * 320 * 8


def test_another_configuration_reads_as_nothing(cat):
    for name in ("qwen2-1.5b", "mimo-v2-flash-7l", "granite-4.0-h-micro",
                 "deepseek-v2-5l", "lfm2-24b-a2b-8l"):
        other = cat.data("configs", name)
        run = {"config": other, "engine": other["benchmark"]["engine"]}
        assert scmoe.dims(other) is None and scmoe.weights(other) is None
        assert scmoe.attn_least({}, None, other, "decode") is None
        assert scmoe.moe_least({}, None, other, 4) is None
        assert scmoe.zero_assignment_share({}, run) is None
        s = {"before": series(), "after": series()}
        trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1}}}
        for metric in NEW:
            assert reduce(cat, metric, s, trace, other) is None


def test_a_program_without_the_counters_reads_none(cat, config, monkeypatch):
    scoped(monkeypatch, {"decode": {"dynamo.attn": 1.0, "dynamo.moe_ffn": 1.0},
                         "prefill": {"dynamo.attn": 1.0}},
           {"decode": 1, "prefill": 1})
    none = {"before": series(), "after": series()}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 0.1},
                         "jit_fn": {"runs": 2, "total_s": 0.1}},
             "ops": {}}
    for name in NEW:
        assert reduce(cat, name, none, trace, config) is None
        assert reduce(cat, name, none, None, config) is None


def test_the_three_shares_of_a_scope_by_hand(cat, config, monkeypatch):
    """Two traced decode dispatches (4 steps, 10 lanes at 2,000 tokens) and
    one 512-row chunk at a context of 2,048."""
    pairs = 512 * 1536 + 512 * 513 // 2
    work = {**captured("decode", dispatches=2, tokens=80,
                       dyn_attn_latent_keys_total=160_000,
                       dyn_attn_latent_pairs_total=160_000,
                       dyn_moe_experts_hit_total=90,
                       dyn_moe_assignments_total=82,
                       dyn_moe_zero_assignments_total=1270),
            **captured("prefill", dispatches=1, tokens=512,
                       dyn_attn_latent_keys_total=2048,
                       dyn_attn_latent_pairs_total=pairs)}
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 80e-3},
                         "jit_fn": {"runs": 1, "total_s": 30e-3}}}
    scoped(monkeypatch, {"decode": {"dynamo.attn": 6e-3,
                                    "dynamo.moe_ffn": 12e-3},
                         "prefill": {"dynamo.attn": 4e-3}},
           {"decode": 2, "prefill": 1})
    # decode attention: bound by its bytes and its operations alike
    least = max(160_000 * 1152 * 8 / PEAK_B,
                2 * 160_000 * 64 * 1088 * 8 / PEAK_F)
    got = reduce(cat, "scope.scmoe_attn_latent_decode_roofline_share", s,
                 trace, config)
    assert got == pytest.approx(100 * least / 6e-3) and 0 < got < 100
    least = max(2048 * 1152 * 8 / PEAK_B, 2 * pairs * 64 * 320 * 8 / PEAK_F)
    got = reduce(cat, "scope.scmoe_attn_latent_prefill_roofline_share", s,
                 trace, config)
    assert got == pytest.approx(100 * least / 4e-3) and 0 < got < 100
    # the branch in decode: 8 steps x 4 routers, 90 experts hit; the
    # identity assignments cost D multiply-adds and no bytes
    router = 6144 * 768
    bytes_ = (8 * 4 * router + 90 * EXPERT) * 2
    flops = 2 * (router * 4 * 80 + EXPERT * 82 + 6144 * 1270)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "scope.scmoe_moe_ffn_roofline_share", s, trace, config)
    assert got == pytest.approx(100 * least / 12e-3) and 0 < got < 100


def test_the_whole_steps_share_by_hand(cat, config):
    work = captured("decode", dispatches=2, tokens=80,
                    dyn_attn_latent_keys_total=160_000,
                    dyn_attn_latent_pairs_total=160_000,
                    dyn_moe_experts_hit_total=90,
                    dyn_moe_assignments_total=82,
                    dyn_moe_zero_assignments_total=1270)
    s = {"before": series(), "after": series(work)}
    trace = {"modules": {"jit_step": {"runs": 2, "total_s": 80e-3}}}
    bytes_ = (8 * FIXED + 90 * EXPERT) * 2 + 160_000 * 1152 * 8
    flops = (2 * FIXED * 80 + 2 * EXPERT * 82 + 2 * 6144 * 1270
             + 2 * 160_000 * 64 * 1088 * 8)
    least = max(bytes_ / PEAK_B, flops / PEAK_F)
    got = reduce(cat, "program.scmoe_decode_step_mfu_share", s, trace, config)
    assert got == pytest.approx(100 * least / 80e-3)
    assert 0 < got < 100
    # 8 steps of 5.31 GB of fixed weights: at least 6.5 ms a step
    assert 8 * FIXED * 2 / PEAK_B / 8 == pytest.approx(6.49e-3, rel=1e-2)
    # a capture cut short: fewer runs than dispatches scale the work DOWN
    cut = {"modules": {"jit_step": {"runs": 1, "total_s": 40e-3}}}
    half = reduce(cat, "program.scmoe_decode_step_mfu_share", s, cut, config)
    assert half == pytest.approx(got, rel=1e-9)


def test_the_share_of_the_choices_that_were_identity_experts(cat, config):
    routed, zero = "dyn_moe_routed_assignments_total", \
        "dyn_moe_zero_assignments_total"
    after = series({(routed, (("kind", "decode"),)): 48_000.0,
                    (routed, (("kind", "prefill"),)): 480_000.0,
                    (zero, (("kind", "decode"),)): 16_500.0,
                    (zero, (("kind", "prefill"),)): 159_500.0})
    s = {"before": series(), "after": after}
    got = reduce(cat, "moe.zero_assignment_share", s, None, config)
    assert got == pytest.approx(100 * 176_000 / 528_000)


def test_the_manifest_lists_the_five_for_this_cell_alone(cat):
    listed = {m["name"]: m for m in cat.manifest["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    mine = {m["name"] for m in cat.metrics("per_layer", CELL)}
    assert set(NEW) <= mine
    assert {"moe.rows_per_expert_hit", "moe.held_assignment_share",
            "attn.live_page_share"} <= mine
    # (its decode program is ``sorted``: the share would read a constant 100,
    # and ``test_sorted_call_share.py`` holds that metric's list to one cell)
    assert "moe.sorted_call_share" not in mine
    # nothing that goes through harness/latent.py, which reads another
    # family's key names, nor the counts that know this configuration not
    from benchmarks.harness import latent, shortconv, step
    config = cat.data("configs", "longcat-flash-omni-4l")
    with pytest.raises(KeyError):
        latent.dims(config)
    assert not {m for m in mine if "latent" in m and "scmoe" not in m}
    assert "scope.moe_shared_ffn_roofline_share" not in mine
    assert "program.decode_step_mfu_share" not in mine
    assert step.unknown(config)
    assert shortconv.dims(config) is None        # moe.expert_read_share
    assert "moe.expert_read_share" not in mine
