"""The two roofline shares of ``keye-vl2-30b-a3b-6l.longdoc`` on made-up
scrapes and a made-up trace summary: the work is the traced dispatches' own
(``dyn_profile_captured_work_total``), a cut trace scales it down and never
up, a shared operation counts by its stated part, a stale operation list is
an error, and a program without the counter reads as no value."""

import importlib
import json
import os

import pytest

from benchmarks.harness import routed
from benchmarks.harness.catalog import BenchError, Catalog

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
CONFIG = {k: v for k, v in Catalog().data(
    "configs", "keye-vl2-30b-a3b-6l").items() if k != "benchmark"}
INFO = ("dyn_engine_info", {"platform": "tpu", "device_kind": "TPU v5 lite"},
        1.0)
PEAKS = routed.peaks_for("TPU v5 lite")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def scrapes(work):
    """``work``: {(counter, kind): amount} counted during the capture."""
    after = [INFO] + [(routed.CAPTURED, {"counter": c, "kind": k}, float(v))
                      for (c, k), v in work.items()]
    return {"before": [INFO], "after": after}


def summary(ops, prefill_runs=2, decode_runs=1):
    return {"modules": {"jit_fn": {"runs": prefill_runs},
                        "jit_step": {"runs": decode_runs}},
            "ops": {k: {"events": 1, "total_s": v} for k, v in ops.items()}}


MOE_WORK = {("dispatches", "prefill"): 2, ("tokens", "prefill"): 512,
            ("dyn_moe_assignments_total", "prefill"): 512 * 8 * 6,
            ("dyn_moe_experts_hit_total", "prefill"): 2 * 128 * 6,
            ("dispatches", "decode"): 1, ("tokens", "decode"): 48,
            ("dyn_moe_assignments_total", "decode"): 48 * 8 * 6,
            ("dyn_moe_experts_hit_total", "decode"): 4 * 70 * 6}


def moe_expected(seconds, scale_decode=1.0):
    per = 3.0 * 2048 * 768
    hit = 2 * 128 * 6 + 4 * 70 * 6 * scale_decode
    pairs = 512 * 8 * 6 + 48 * 8 * 6 * scale_decode
    least = max(per * 2 * hit / PEAKS["hbm_bytes_per_s"],
                2 * per * pairs / PEAKS["bf16_flops"])
    return 100.0 * least / seconds


def test_moe_share_is_the_traced_dispatches_own_work_over_their_time():
    m = metric("kernel.moe_ffn_roofline_share")
    ops = {"fusion bf16[256,128,768]": 0.010, "fusion f32[256]": 0.020,
           "ragged-dot-none:tpu_custom_call bf16[96,768]": 0.004,
           "fusion f32[12]": 0.5,                # sorted decode: not ours
           "copy bf16[77,77]": 9.0}              # not listed
    got = m.reduce(scrapes(MOE_WORK), summary(ops), {"config": CONFIG})
    assert got == pytest.approx(moe_expected(0.010 + 0.96 * 0.020 + 0.004))


def test_a_cut_trace_scales_the_work_down_never_up():
    m = metric("kernel.moe_ffn_roofline_share")
    ops = {"fusion bf16[256,128,768]": 0.010,
           "ragged-dot-none:tpu_custom_call bf16[96,768]": 0.004}
    work = dict(MOE_WORK)
    work["dispatches", "decode"] = 2             # two counted, one in trace
    got = m.reduce(scrapes(work), summary(ops), {"config": CONFIG})
    assert got == pytest.approx(moe_expected(0.014, scale_decode=0.5))
    more = m.reduce(scrapes(MOE_WORK), summary(ops, decode_runs=5),
                    {"config": CONFIG})
    assert more == pytest.approx(moe_expected(0.014))


def test_a_stale_operation_list_is_an_error_not_a_value():
    m = metric("kernel.moe_ffn_roofline_share")
    ops = {"fusion bf16[256,128,999]": 0.010,    # gate/up renamed
           "ragged-dot-none:tpu_custom_call bf16[96,768]": 0.004}
    with pytest.raises(BenchError, match="stale"):
        m.reduce(scrapes(MOE_WORK), summary(ops), {"config": CONFIG})


def test_index_share_counts_only_dispatches_that_score():
    m = metric("kernel.index_select_roofline_share")
    work = {("dispatches", "prefill"): 3, ("tokens", "prefill"): 768,
            ("scoring_dispatches", "prefill"): 1,
            ("scoring_tokens", "prefill"): 256,
            ("scored_keys", "prefill"): 256 * 3000,
            ("dispatches", "decode"): 1, ("tokens", "decode"): 48,
            ("scoring_dispatches", "decode"): 1,
            ("scoring_tokens", "decode"): 48,
            ("scored_keys", "decode"): 48 * 5000}
    ops = {"fusion f32[256,4096]": 0.002, "fusion f32[12,8192]": 0.001,
           "fusion s32[12]": 0.001}
    got = m.reduce(scrapes(work), summary(ops, prefill_runs=3),
                   {"config": CONFIG})
    flops = 2.0 * (256 * 3000 + 48 * 5000) * 16 * 64 * 6
    bytes_ = (48 * 5000 + 3000) * 64 * 2 * 6
    least = max(bytes_ / PEAKS["hbm_bytes_per_s"],
                flops / PEAKS["bf16_flops"])
    assert got == pytest.approx(100.0 * least / 0.004)
    with pytest.raises(BenchError, match="stale"):
        m.reduce(scrapes(work), summary({"fusion s32[12]": 0.001},
                                        prefill_runs=3), {"config": CONFIG})


@pytest.mark.parametrize("name", ["kernel.moe_ffn_roofline_share",
                                  "kernel.index_select_roofline_share"])
def test_a_program_without_the_counter_reads_as_no_value(name):
    """The parent commit: the four counters may be there or not, the
    captured twin is not; a dense configuration has neither key."""
    m = metric(name)
    plain = {"before": [INFO], "after": [INFO, (
        "dyn_moe_assignments_total", {"kind": "prefill"}, 99.0)]}
    ops = {"fusion bf16[256,128,768]": 0.010}
    assert m.reduce(plain, summary(ops), {"config": CONFIG}) is None
    assert m.reduce(plain, summary(ops), {"config": {
        "hidden_size": 8, "num_hidden_layers": 1}}) is None


def test_the_lists_name_required_keys_they_hold():
    for name in ("kernel.moe_ffn_roofline_share",
                 "kernel.index_select_roofline_share"):
        with open(os.path.join(METRICS, name + ".ops.json")) as f:
            listed = json.load(f)
        for kind, need in listed["required"].items():
            assert need, (name, kind)
            for key in need:
                assert (key in listed["ops"]
                        or key in listed.get("prefixes", ())), (name, key)
        assert set(listed["shared"]) <= set(listed["ops"])
