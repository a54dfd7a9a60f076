"""``program.decode_step_mfu_share`` of each of the five configurations on a
made-up trace against a count made by hand from the published keys: the
weights a decode step must read once, of routed experts only those hit, the
cell's own scopes' least work; never over 100 for a step that takes exactly
the time its least bytes take, and nothing read where no decode program
ran."""

import pytest

from benchmarks.harness import routed, state, step
from benchmarks.harness.catalog import Catalog

NAME = "program.decode_step_mfu_share"
INFO = ("dyn_engine_info", {"platform": "tpu", "device_kind": "TPU v5 lite"},
        1.0)
PEAKS = routed.peaks_for("TPU v5 lite")
BW, FLOPS = PEAKS["hbm_bytes_per_s"], PEAKS["bf16_flops"]


def captured(kind, **amounts):
    """The traced dispatches' work of one kind, as the program counts it
    while a capture runs."""
    return {(routed.CAPTURED, (("counter", c), ("kind", kind))): v
            for c, v in amounts.items()}


def scrapes(*work):
    after = [INFO] + [(name, dict(labels), float(v)) for w in work
                      for (name, labels), v in w.items()]
    return {"before": [INFO], "after": after}


def trace(**programs):
    """``programs``: {program: {"runs": n, "seconds": device seconds of one
    run}} -> the part of a trace's summary the metric reads."""
    return {"ops": {}, "modules": {
        name: {"runs": p["runs"], "total_s": p["runs"] * p.get("seconds", 1.0),
               "median_s": p.get("seconds", 1.0)}
        for name, p in programs.items()}}


# weights every decode step reads, by hand from the published keys
QWEN2 = 28 * (2 * 1536 * 12 * 128 + 2 * 1536 * 2 * 128
              + 3 * 1536 * 8960) + 151936 * 1536
MISTRAL = 16 * (2 * 4096 * 32 * 128 + 2 * 4096 * 8 * 128
                + 3 * 4096 * 14336) + 32000 * 4096
KEYE = 6 * (2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
            + 2048 * 128) + 151936 * 2048            # attention, router; head
KEYE_EXPERT = 3 * 2048 * 768
MIMO_FULL = 4096 * 64 * 192 + 4096 * 4 * (192 + 128) + 64 * 128 * 4096
MIMO_WINDOW = 4096 * 64 * 192 + 4096 * 8 * (192 + 128) + 64 * 128 * 4096
MIMO = (2 * MIMO_FULL + 5 * MIMO_WINDOW     # layers 0 and 5 are full
        + 3 * 4096 * 16384                  # layer 0's dense feed-forward
        + 6 * 4096 * 256                    # six routers over 256 experts
        + 19072 * 4096)
MIMO_EXPERT = 3 * 4096 * 2048
GRANITE = (36 * (2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048)
           + 4 * (2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64)
           + 40 * 3 * 2048 * 8192 + 100352 * 2048)


@pytest.fixture(scope="module")
def cat():
    return Catalog()


def run_of(cat, name):
    config = cat.data("configs", name)
    return {"config": config, "engine": config["benchmark"]["engine"]}


def share(cat, s, summary, run):
    return cat.module("layer_metrics", NAME).reduce(s, summary, run)


@pytest.mark.parametrize("name, fixed, expert", [
    ("qwen2-1.5b", QWEN2, 0), ("mistral-7b-16l", MISTRAL, 0),
    ("keye-vl2-30b-a3b-6l", KEYE, KEYE_EXPERT),
    ("mimo-v2-flash-7l", MIMO, MIMO_EXPERT),
    ("granite-4.0-h-micro", GRANITE, 0)])
def test_the_weights_a_step_reads_by_hand(cat, name, fixed, expert):
    assert step.weights(cat.data("configs", name)) == (fixed, expert)


@pytest.mark.parametrize("name, fixed", [("qwen2-1.5b", QWEN2),
                                         ("mistral-7b-16l", MISTRAL)])
def test_a_dense_cell_reads_its_weights_once_a_step(cat, name, fixed):
    """No counter of the traced dispatches in a dense cell: the steps are
    the trace's own runs x ``decode_steps``, the operations are left out."""
    run = run_of(cat, name)
    n = run["engine"]["decode_steps"]
    summary = trace(jit_step={"runs": 3, "seconds": n * 6e-3},
                    jit_fn={"runs": 2, "seconds": 1.0})
    least = fixed * 2 / BW
    assert share(cat, scrapes(), summary, run) == \
        pytest.approx(100 * least / 6e-3)
    # a step that takes exactly the weights' time reads 100, never more
    at = trace(jit_step={"runs": 3, "seconds": n * least})
    assert share(cat, scrapes(), at, run) == pytest.approx(100.0)
    # no decode program in the trace, or a run off a TPU: nothing to read
    assert share(cat, scrapes(), trace(jit_fn={"runs": 2}), run) is None
    assert share(cat, {"before": [], "after": []}, summary, run) is None


def test_a_routed_cell_reads_only_the_experts_hit(cat):
    """Two traced decode dispatches of 4 steps, 3 lanes: 20 of 128 experts
    hit a layer and step, the index scores of 24 queries over 5000 keys."""
    run = run_of(cat, "keye-vl2-30b-a3b-6l")
    hit, tokens, keys = 2 * 4 * 6 * 20, 24, 24 * 5000
    s = scrapes(captured(
        "decode", dispatches=2, tokens=tokens, scored_keys=keys,
        scoring_dispatches=2, scoring_tokens=tokens,
        dyn_moe_experts_hit_total=hit,
        dyn_moe_assignments_total=tokens * 8 * 6),
        captured("prefill", dispatches=1, tokens=256,
                 dyn_moe_experts_hit_total=6 * 128, scored_keys=256 * 9000,
                 scoring_dispatches=1, scoring_tokens=256))
    summary = trace(jit_step={"runs": 2, "seconds": 4 * 5e-3},
                    jit_fn={"runs": 1, "seconds": 0.1})
    bytes_ = (8 * KEYE + hit * KEYE_EXPERT) * 2 + keys * 64 * 2 * 6
    flops = (2 * KEYE * tokens + 2 * KEYE_EXPERT * tokens * 8 * 6
             + 2 * keys * 16 * 64 * 6)
    assert bytes_ / BW > flops / FLOPS
    assert share(cat, s, summary, run) == \
        pytest.approx(100 * bytes_ / BW / (8 * 5e-3))
    at = trace(jit_step={"runs": 2, "seconds": bytes_ / BW / 2})
    assert share(cat, s, at, run) == pytest.approx(100.0)


def test_a_per_kind_cell_adds_its_keys(cat):
    run = run_of(cat, "mimo-v2-flash-7l")
    n_q = 32 * 4
    full = 32 * (1000 + 1001 + 1002 + 1003)
    s = scrapes(captured(
        "decode", dispatches=1, tokens=n_q, dyn_moe_experts_hit_total=4 * 6 * 7,
        dyn_moe_assignments_total=60, attn_full_keys=full,
        attn_full_pairs=full, attn_window_keys=n_q * 128,
        attn_window_pairs=n_q * 128),
        captured("prefill", dispatches=1, tokens=256, attn_full_keys=256,
                 attn_full_pairs=256 * 129))
    summary = trace(jit_step={"runs": 1, "seconds": 4 * 10e-3},
                    jit_fn={"runs": 1, "seconds": 0.1})
    bytes_ = ((4 * MIMO + 4 * 6 * 7 * MIMO_EXPERT) * 2
              + full * 2 * 4 * 320 * 2 + n_q * 128 * 5 * 8 * 320 * 2)
    assert share(cat, s, summary, run) == \
        pytest.approx(100 * bytes_ / BW / 40e-3)
    assert 5 < share(cat, s, summary, run) < 100


def test_a_state_cell_adds_a_served_lanes_state_a_step(cat):
    run = run_of(cat, "granite-4.0-h-micro")
    served = 48 * 4                                   # lane-steps
    s = scrapes(captured("decode", dispatches=1, tokens=served,
                         **{state.ACTIVE: served, state.TOKENS: served,
                            state.LANE_STEPS: 256}))
    summary = trace(jit_step={"runs": 1, "seconds": 4 * 30e-3})
    token = (3 * 4096 + 2 * 128 + 64) * 2
    bytes_ = (4 * GRANITE * 2
              + 36 * (2 * served * 64 * 64 * 128 * 4 + served * token))
    assert share(cat, s, summary, run) == \
        pytest.approx(100 * bytes_ / BW / 120e-3)
    # weights 7.8 ms + 48 lanes' states in and out 8.9 ms of a 30 ms step
    assert share(cat, s, summary, run) == pytest.approx(55.6, abs=0.1)
    at = trace(jit_step={"runs": 1, "seconds": bytes_ / BW})
    assert share(cat, s, at, run) == pytest.approx(100.0)


@pytest.mark.parametrize("change, why", [
    ({"q_lora_rank": 1536}, "low-rank"), ({"kv_lora_rank": 512}, "low-rank"),
    ({"num_key_value_heads": None}, "num_key_value_heads"),
    ({"intermediate_size": None}, "intermediate_size"),
    ({"layer_types": ["attention", "linear_attention"]}, "layer types"),
    ({"shared_intermediate_size": 1024}, "shared width")])
def test_a_configuration_it_does_not_know_reads_as_no_value(cat, change, why):
    """A weight structure that ``weights`` does not count (a later
    configuration's: this file may not be edited for it) is left out of the
    line: no KeyError inside ``run_cell``, no guess that could read over
    100."""
    config = dict(cat.data("configs", "qwen2-1.5b"), **change)
    assert why in step.unknown(config)
    assert step.weights(config) is None
    run = {"config": config, "engine": {"decode_steps": 4}}
    summary = trace(jit_step={"runs": 3, "seconds": 4 * 6e-3})
    assert share(cat, scrapes(), summary, run) is None
    for name in ("qwen2-1.5b", "mistral-7b-16l", "keye-vl2-30b-a3b-6l",
                 "mimo-v2-flash-7l", "granite-4.0-h-micro"):
        assert step.unknown(cat.data("configs", name)) is None


def test_the_manifest_lists_the_cells_whose_count_is_pinned_here(cat):
    """The list is exactly the cells whose configuration ``step.weights``
    knows (each of the five above, pinned by hand); every other cell runs a
    configuration ``step.unknown`` names a reason for, and reports a whole-
    step share of its own, ``program.*_mfu_share``, that moves the same
    end-to-end metric: no cell is without the share that bounds a claim."""
    entry = next(m for m in cat.manifest["per_layer"] if m["name"] == NAME)
    known = [w["name"] for w in cat.manifest["workloads"]
             if step.unknown(cat.data("configs", w["config"])) is None]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "bucket programs",
                     "moves": "tpot_p90_ms", "workloads": known}
    pinned = {"qwen2-1.5b", "mistral-7b-16l", "keye-vl2-30b-a3b-6l",
              "mimo-v2-flash-7l", "granite-4.0-h-micro"}
    assert {w["config"] for w in cat.manifest["workloads"]
            if w["name"] in known} == pinned
    for w in cat.manifest["workloads"]:
        if w["name"] in known:
            continue
        own = [m for m in cat.metrics("per_layer", w["name"])
               if m["name"].startswith("program.")
               and m["name"].endswith("_mfu_share") and m["name"] != NAME
               and m["moves"] == entry["moves"]]
        assert own and all(m["workloads"] == [w["name"]] for m in own), w
