"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny LATENT-ATTENTION configuration (one
compressed row a token for all heads, YaRN rotary, a leading dense layer,
group-limited routing beside a shared expert, a chip's share of the experts)
under a scaled-down ``longctx`` mix, with the benchmark's own reference
``deepseek_v2``, generator, topology and the five per-layer metrics this
configuration brought, found by name beside a manifest of the test's own.
The result can never look like a pass."""

import json
import os

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell

NEW = ["program.latent_decode_step_mfu_share",
       "scope.attn_latent_decode_roofline_share",
       "scope.attn_latent_prefill_roofline_share",
       "scope.moe_shared_ffn_roofline_share", "attn.latent_keys_per_step"]
DEVICE = set(NEW[:4])
TINY = {
    "model_type": "deepseek_v2", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": False, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "vocab_size": 259, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "attention_bias": False,
    "hidden_act": "silu", "seq_aux": True,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "expert_shard": {"router_experts": 16, "first_expert": 4},
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of deepseek-v2-5l",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "deepseek_v2",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "longctx")
    # the mix's own generator, topology and distributions, at a CPU's size
    mix.update(arrivals={"clients": 4}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 60,
                              "min": 16, "max": 180},
               output_tokens={"dist": "uniform", "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-deepseek", TINY),
                            ("traffic", "longctx-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-deepseek.longctx-tiny"
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-deepseek", "source": "tests",
                     "file": "configs/tiny-deepseek.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-deepseek",
                       "traffic": "longctx-tiny", "chips": 1,
                       "why": "CPU rehearsal only"}],
        "end_to_end": keep("end_to_end", ["ttft_p50_ms", "tpot_p90_ms",
                                          "output_tok_s", "setup_s"]),
        "per_layer": keep("per_layer", NEW + [
            "moe.rows_per_expert_hit", "moe.held_assignment_share",
            "engine.batch_occupancy", "attn.live_page_share"]),
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
        # a CPU trace has no device to read: the four shares return nothing
        # and raise nothing; the counters' metrics read
        assert not DEVICE & set(got)
        val = lambda name: got[name]["value"]
        assert val("attn.latent_keys_per_step") > 16     # rows a step
        assert 5.0 < val("moe.held_assignment_share") < 80.0   # 4 of 16
        assert val("moe.rows_per_expert_hit") > 0
