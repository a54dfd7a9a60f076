"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny PER-KIND configuration (window and
full layers with page pools of their own, a chip's share of sigmoid-routed
experts) under a scaled-down ``mixedqueue`` mix, with the benchmark's own
reference ``mimo_v2_flash``, generator, topology and the five per-layer
metrics this configuration brought, found by name beside a manifest of the
test's own. The result can never look like a pass."""

import json
import os

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell

NEW = ["cache.window_resident_share", "moe.held_assignment_share",
       "scope.attn_window_roofline_share", "scope.attn_full_roofline_share",
       "scope.moe_share_ffn_roofline_share"]
DEVICE = {"scope.attn_window_roofline_share",
          "scope.attn_full_roofline_share",
          "scope.moe_share_ffn_roofline_share"}
TINY = {
    "model_type": "mimo_v2_flash", "hidden_size": 64, "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 24,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "layernorm_epsilon": 1e-5, "vocab_size": 259,
    "tie_word_embeddings": False, "max_position_embeddings": 1024,
    "attention_bias": False, "hidden_act": "silu",
    "partial_rotary_factor": 0.334, "sliding_window": 16,
    "sliding_window_size": 16, "attention_chunk_size": 16,
    "attention_value_scale": 0.707,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "expert_shard": {"router_experts": 8, "first_expert": 2},
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of mimo-v2-flash-7l",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "mimo_v2_flash",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "mixedqueue")
    # the mix's own generator, topology and distributions, at a CPU's size
    mix.update(arrivals={"clients": 4}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 40,
                              "min": 8, "max": 180},
               output_tokens={"dist": "uniform", "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-mimo", TINY),
                            ("traffic", "mixedqueue-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-mimo.mixedqueue-tiny"
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-mimo", "source": "tests",
                     "file": "configs/tiny-mimo.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-mimo",
                       "traffic": "mixedqueue-tiny", "chips": 1,
                       "why": "CPU rehearsal only"}],
        "end_to_end": keep("end_to_end", ["ttft_p50_ms", "tpot_p90_ms",
                                          "output_tok_s", "setup_s"]),
        "per_layer": keep("per_layer", NEW + ["moe.rows_per_expert_hit",
                                              "engine.batch_occupancy"]),
    }
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    cat = Catalog(str(tmp_path / "BENCHMARK.json"),
                  roots=[str(tmp_path), BENCH])
    import time
    for trace in (False, True):
        code, line = run_cell(cell, 2147483659, 3.0, trace, time.monotonic(),
                              catalog=cat, rehearsal=True)
        assert code == 2 and line["correct"] is False and line["rehearsal"]
        assert line["failed"] == 0 and line["attempted"] > 0
        assert line["checks"]["sample"]["ok"], line["checks"]["sample"]
        assert line["checks"]["compiled_in_window"] == 0
        got = line["metrics"]
        if not trace:
            assert {"ttft_p50_ms", "tpot_p90_ms", "output_tok_s",
                    "setup_s"} <= set(got)
            continue
        # a CPU trace has no device to read: the three roofline shares
        # return nothing and raise nothing; the counters' metrics read
        assert not DEVICE & set(got)
        val = lambda name: got[name]["value"]
        assert 5.0 < val("cache.window_resident_share") < 100.0
        assert 20.0 < val("moe.held_assignment_share") < 80.0   # 4 of 8
        assert val("moe.rows_per_expert_hit") > 0
