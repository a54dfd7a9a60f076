"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny GATED SHORT-CONVOLUTION configuration
(conv layers whose cache is a two-row tail a lane beside GQA layers with
q / k norms, bias-selected sigmoid-routed experts behind two dense layers, a
tied head) under a scaled-down ``toolcalls`` mix, with the benchmark's own
reference ``lfm2_moe``, generator, topology and the four per-layer metrics
this configuration brought, found by name beside a manifest of the test's
own. The result can never look like a pass."""

import json
import os

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell
from benchmarks.tests.test_reference_lfm2 import TINY as MODEL

NEW = ["program.shortconv_decode_step_mfu_share",
       "scope.conv_step_roofline_share", "scope.conv_scan_roofline_share",
       "moe.expert_read_share"]
DEVICE = set(NEW[:3])
TINY = {
    **MODEL,
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of lfm2-24b-a2b-8l",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "lfm2_moe",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "toolcalls")
    # the mix's own generator, topology and distributions, at a CPU's size
    mix.update(arrivals={"rate_per_s": 3.0}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 60,
                              "min": 16, "max": 180},
               output_tokens={**mix["output_tokens"], "median": 12,
                              "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-lfm2", TINY),
                            ("traffic", "toolcalls-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-lfm2.toolcalls-tiny"
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-lfm2", "source": "tests",
                     "file": "configs/tiny-lfm2.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-lfm2",
                       "traffic": "toolcalls-tiny", "chips": 1,
                       "why": "CPU rehearsal only"}],
        "end_to_end": keep("end_to_end", ["ttft_p50_ms", "tpot_p90_ms",
                                          "output_tok_s", "setup_s"]),
        "per_layer": keep("per_layer", NEW + [
            "moe.rows_per_expert_hit", "scope.moe_ffn_roofline_share",
            "engine.batch_occupancy", "attn.live_page_share",
            "loadgen.lateness_p90_ms", "engine.lane_wait_ms"]),
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
        # a CPU trace has no device to read: the three shares return nothing
        # and raise nothing; the counters' metrics read
        assert not DEVICE & set(got)
        val = lambda name: got[name]["value"]
        # 4 decode rows x 2 a token hit 2 to 8 of 8 experts a routed layer
        assert 25.0 <= val("moe.expert_read_share") <= 100.0
        assert val("moe.rows_per_expert_hit") > 0
