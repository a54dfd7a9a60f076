"""The new cell's files, rehearsed on the CPU through the real harness
(``test_rehearsal.py``'s way): a tiny configuration of the LongCat-Flash
family's shape (two latent-attention sublayers and two dense feed-forwards a
layer, a routed branch across them, identity experts among the router's
outputs, a chip's share of the routed ones) under a scaled-down ``avturns``
mix, with the benchmark's own reference ``longcat_flash``, generator,
topology and EVERY per-layer metric that applies to the cell, listed or
list-less, found by name beside a manifest of the test's own: each reads a
value or no value and none raises on this file's key names. The result can
never look like a pass."""

import json
import os
import time

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.cell import run_cell
from benchmarks.tests.test_reference_longcat import TINY as MODEL

CELL = "longcat-flash-omni-4l.avturns"
NEW = ["program.scmoe_decode_step_mfu_share",
       "scope.scmoe_moe_ffn_roofline_share",
       "scope.scmoe_attn_latent_decode_roofline_share",
       "scope.scmoe_attn_latent_prefill_roofline_share",
       "moe.zero_assignment_share"]
DEVICE = set(NEW[:4])
TINY = {
    **MODEL,
    "benchmark": {
        "source": "tests: a tiny cut of the shapes of longcat-flash-omni-4l",
        "reduced": {}, "assumed": [], "stands_for": "nothing: a rehearsal",
        "reference": "longcat_flash",
        "reference_tolerance": {"rel_rms": 0.25, "why": "the default"},
        "engine": {"max_batch": 4, "max_context": 256, "prefill_chunk": 64,
                   "prefill_lanes": 1, "decode_steps": 4, "page_size": 16},
    },
}


def test_the_new_cells_files_rehearse_on_the_cpu(tmp_path):
    real = Catalog().manifest
    mix = Catalog().data("traffic", "avturns")
    # the mix's own generator, topology and distributions, at a CPU's size
    mix.update(arrivals={"rate_per_s": 3.0}, drain_s=60, trace_drain_s=90,
               trace_steps=16,
               prompt_tokens={**mix["prompt_tokens"], "median": 60,
                              "min": 16, "max": 180},
               output_tokens={**mix["output_tokens"], "min": 8, "max": 24})
    for sub, name, data in (("configs", "tiny-longcat", TINY),
                            ("traffic", "avturns-tiny", mix)):
        os.makedirs(tmp_path / sub, exist_ok=True)
        with open(tmp_path / sub / f"{name}.json", "w") as f:
            json.dump(data, f)
    cell = "tiny-longcat.avturns-tiny"
    # every metric the real cell reports: listed for it, or list-less
    mine = [m["name"] for m in Catalog().metrics("per_layer", CELL)]
    assert set(NEW) <= set(mine)
    assert {"step.ffn_ms", "step.mixer_ms", "attn.live_page_share",
            "scope.attn_busy_share", "sampler.greedy_dispatch_share",
            "moe.held_assignment_share"} <= set(mine)
    keep = lambda group, names: [
        {**{k: v for k, v in x.items() if k != "workloads"},
         **({"workloads": [cell]} if "workloads" in x else {})}
        for x in real[group] if x["name"] in names]
    manifest = {
        **{k: real[k] for k in ("command", "paths", "run_seconds")},
        "configs": [{"name": "tiny-longcat", "source": "tests",
                     "file": "configs/tiny-longcat.json", "reduced": [],
                     "why": "CPU rehearsal only"}],
        "workloads": [{"name": cell, "config": "tiny-longcat",
                       "traffic": "avturns-tiny", "chips": 1,
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
        # a CPU trace has no device to read: the four shares return nothing
        # and raise nothing; the counters' metrics read
        assert not DEVICE & set(got)
        val = lambda name: got[name]["value"]
        # 8 of the router's 24 outputs are identity experts, 8 are held
        assert 15.0 <= val("moe.zero_assignment_share") <= 55.0
        assert 10.0 <= val("moe.held_assignment_share") <= 60.0
        assert val("moe.rows_per_expert_hit") > 0
        # (attn.live_page_share counts the paged kernel's pages: nothing to
        # read on the CPU's dense path, and nothing raised)
