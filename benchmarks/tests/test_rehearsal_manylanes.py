"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny configuration with STATE-SPACE layers
(a per-lane state pool beside a folded K/V pool for its attention layers)
under a scaled-down ``manylanes`` mix, with the benchmark's own reference
``granite_hybrid``, generator, topology and the three per-layer metrics this
configuration brought, found by name beside a manifest of the test's own.
The result can never look like a pass."""

import json
import os
import time

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell

NEW = ["scope.ssm_step_roofline_share", "scope.ssm_scan_roofline_share",
       "ssm.active_state_share"]
TINY = {
    "model_type": "granitemoehybrid", "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                    "attention", "mamba"],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 1024,
    "normalization_function": "rmsnorm", "num_attention_heads": 4,
    "num_experts_per_tok": 0, "num_hidden_layers": 7,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 128, "tie_word_embeddings": True,
    "vocab_size": 259,
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of granite-4.0-h-micro",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "granite_hybrid",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "manylanes")
    # the mix's own generator, topology and distributions, at a CPU's size
    mix.update(arrivals={"clients": 4}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 40,
                              "min": 8, "max": 180},
               output_tokens={"dist": "uniform", "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-granite", TINY),
                            ("traffic", "manylanes-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-granite.manylanes-tiny"
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-granite", "source": "tests",
                     "file": "configs/tiny-granite.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-granite",
                       "traffic": "manylanes-tiny", "chips": 1,
                       "why": "CPU rehearsal only"}],
        "end_to_end": keep("end_to_end", ["ttft_p50_ms", "tpot_p90_ms",
                                          "output_tok_s", "setup_s"]),
        "per_layer": keep("per_layer", NEW + ["engine.batch_occupancy"]),
    }
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(manifest, f)
    cat = Catalog(str(tmp_path / "BENCHMARK.json"),
                  roots=[str(tmp_path), BENCH])
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
        # a CPU trace has no device to read: the two roofline shares return
        # nothing and raise nothing; the counters' metric reads, and agrees
        # with the lanes in use (4 clients on 4 lanes, now and then one
        # between two requests)
        assert not {n for n in NEW if n.startswith("scope.")} & set(got)
        share = got["ssm.active_state_share"]["value"]
        assert 40.0 < share <= 100.0
