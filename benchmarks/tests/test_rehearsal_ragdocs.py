"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny configuration of the Cohere2-MoE
family's shape (a parallel block on one LayerNorm, window and full layers by
``layer_types`` with two caches, sigmoid-routed experts of which the chip
holds a share beside averaged shared experts) under a scaled-down
``ragdocs`` mix whose prompts are several windows long, with the benchmark's
own reference ``command_a_plus``, generator, topology and EVERY per-layer
metric that applies to the cell, listed or list-less, found by name beside a
manifest of the test's own: each reads a value or no value and none raises
on this file's key names. The result can never look like a pass."""

import json
import os
import time

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell
from tests.test_command_a_plus import TINY as MODEL

CELL = "command-a-plus-4l.ragdocs"
NEW = ["program.parblock_decode_step_mfu_share",
       "program.parblock_prefill_chunk_mfu_share",
       "scope.parblock_attn_window_roofline_share",
       "scope.parblock_attn_full_roofline_share",
       "scope.parblock_moe_ffn_roofline_share",
       "attn.window_key_share"]
DEVICE = set(NEW[:5])
TINY = {
    **MODEL, "sliding_window": 32,
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of command-a-plus-4l",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "command_a_plus",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "ragdocs")
    # the mix's own generator, topology and distributions, at a CPU's size:
    # prompts of one to six windows of 32 keys
    mix.update(arrivals={"rate_per_s": 3.0}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 80,
                              "min": 32, "max": 200},
               output_tokens={**mix["output_tokens"], "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-parblock", TINY),
                            ("traffic", "ragdocs-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-parblock.ragdocs-tiny"
    # every metric the real cell reports: listed for it, or list-less
    mine = [m["name"] for m in Catalog().metrics("per_layer", CELL)]
    assert set(NEW) <= set(mine)
    assert {"step.ffn_ms", "step.mixer_ms", "step.head_ms",
            "step.unscoped_ms", "attn.live_page_share",
            "scope.attn_busy_share", "sampler.greedy_dispatch_share",
            "moe.held_assignment_share", "moe.rows_per_expert_hit",
            "cache.window_resident_share", "program.prefill_chunk_ms",
            "client.ttft_p90_ms"} <= set(mine)
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-parblock", "source": "tests",
                     "file": "configs/tiny-parblock.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-parblock",
                       "traffic": "ragdocs-tiny", "chips": 1,
                       "why": "CPU rehearsal only"}],
        "end_to_end": keep("end_to_end", ["ttft_p50_ms", "tpot_p90_ms",
                                          "output_tok_s", "setup_s"]),
        "per_layer": keep("per_layer", mine),
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
        # a CPU trace has no device to read: the five shares return nothing
        # and raise nothing; the counters' metrics read
        assert not DEVICE & set(got)
        val = lambda name: got[name]["value"]
        # three window layers of 32 keys beside one full layer of 32-224:
        # under the 75 % of three layers that read everything
        assert 20.0 <= val("attn.window_key_share") < 75.0
        # 4 of the router's 8 are held
        assert 20.0 <= val("moe.held_assignment_share") <= 80.0
        assert val("moe.rows_per_expert_hit") > 0
        assert 0.0 < val("cache.window_resident_share") < 100.0
