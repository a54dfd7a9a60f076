"""The least work under the two roofline shares of
``keye-vl2-30b-a3b-6l.longdoc`` on made-up scrapes and a made-up trace
summary: the work is the traced dispatches' own
(``dyn_profile_captured_work_total``), a cut trace scales it down and never
up, only dispatches whose program scores count for the indexer, and a
program without the counter reads as no value. (What the shares divide it
by, the seconds under a scope: ``test_scopes.py``.)"""

import pytest

from benchmarks.harness import routed
from benchmarks.harness.catalog import Catalog

CONFIG = {k: v for k, v in Catalog().data(
    "configs", "keye-vl2-30b-a3b-6l").items() if k != "benchmark"}
INFO = ("dyn_engine_info", {"platform": "tpu", "device_kind": "TPU v5 lite"},
        1.0)
PEAKS = routed.peaks_for("TPU v5 lite")


def scrapes(work):
    """``work``: {(counter, kind): amount} counted during the capture."""
    after = [INFO] + [(routed.CAPTURED, {"counter": c, "kind": k}, float(v))
                      for (c, k), v in work.items()]
    return {"before": [INFO], "after": after}


def summary(prefill_runs=2, decode_runs=1):
    return {"modules": {"jit_fn": {"runs": prefill_runs},
                        "jit_step": {"runs": decode_runs}}}


MOE_WORK = {("dispatches", "prefill"): 2, ("tokens", "prefill"): 512,
            ("dyn_moe_assignments_total", "prefill"): 512 * 8 * 6,
            ("dyn_moe_experts_hit_total", "prefill"): 2 * 128 * 6,
            ("dispatches", "decode"): 1, ("tokens", "decode"): 48,
            ("dyn_moe_assignments_total", "decode"): 48 * 8 * 6,
            ("dyn_moe_experts_hit_total", "decode"): 4 * 70 * 6}


def moe_expected(scale_decode=1.0):
    """-> (bytes, operations, work by kind) of ``MOE_WORK`` by hand."""
    per = 3.0 * 2048 * 768
    hit = 2 * 128 * 6 + 4 * 70 * 6 * scale_decode
    work = {"prefill": 512 * 8 * 6.0, "decode": 48 * 8 * 6 * scale_decode}
    return per * 2 * hit, 2 * per * sum(work.values()), work


def test_moe_least_is_the_traced_dispatches_own_work():
    got = routed.moe_least(scrapes(MOE_WORK), summary(), CONFIG)
    assert got == pytest.approx(moe_expected())
    bytes_, flops, _ = got
    # memory-bound at these rows: the share over 34 ms is the bytes' time
    assert bytes_ / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["bf16_flops"]
    assert routed.roofline_share(bytes_, flops, 0.034, PEAKS) == \
        pytest.approx(100 * bytes_ / PEAKS["hbm_bytes_per_s"] / 0.034)
    assert routed.roofline_share(bytes_, flops, 0.0, PEAKS) is None
    assert routed.roofline_share(0.0, 0.0, 0.034, PEAKS) is None


def test_a_cut_trace_scales_the_work_down_never_up():
    work = dict(MOE_WORK)
    work["dispatches", "decode"] = 2             # two counted, one in trace
    got = routed.moe_least(scrapes(work), summary(), CONFIG)
    assert got == pytest.approx(moe_expected(scale_decode=0.5))
    more = routed.moe_least(scrapes(MOE_WORK), summary(decode_runs=5), CONFIG)
    assert more == pytest.approx(moe_expected())


def test_index_least_counts_only_dispatches_that_score():
    work = {("dispatches", "prefill"): 3, ("tokens", "prefill"): 768,
            ("scoring_dispatches", "prefill"): 1,
            ("scoring_tokens", "prefill"): 256,
            ("scored_keys", "prefill"): 256 * 3000,
            ("dispatches", "decode"): 1, ("tokens", "decode"): 48,
            ("scoring_dispatches", "decode"): 1,
            ("scoring_tokens", "decode"): 48,
            ("scored_keys", "decode"): 48 * 5000}
    s = scrapes(work)
    got = routed.index_select_least(s, summary(prefill_runs=3), CONFIG)
    flops = 2.0 * (256 * 3000 + 48 * 5000) * 16 * 64 * 6
    bytes_ = (48 * 5000 + 3000) * 64 * 2 * 6
    assert got == pytest.approx((bytes_, flops, {
        "prefill": 256 * 3000.0, "decode": 48 * 5000.0}))
    # decode alone, as the whole step's bound adds it in
    assert routed.index_select_least(
        s, summary(prefill_runs=3), CONFIG, ("decode",)) == pytest.approx(
        (48 * 5000 * 64 * 2 * 6, 2.0 * 48 * 5000 * 16 * 64 * 6,
         {"decode": 48 * 5000.0}))


@pytest.mark.parametrize("name", ["scope.moe_ffn_roofline_share",
                                  "scope.index_select_roofline_share"])
def test_a_program_without_the_counter_reads_as_no_value(name):
    """The parent commit: the four counters may be there or not, the
    captured twin is not; a dense configuration has neither key."""
    m = Catalog().module("layer_metrics", name)
    plain = {"before": [INFO], "after": [INFO, (
        "dyn_moe_assignments_total", {"kind": "prefill"}, 99.0)]}
    assert m.reduce(plain, summary(), {"config": CONFIG}) is None
    assert m.reduce(plain, summary(), {"config": {
        "hidden_size": 8, "num_hidden_layers": 1}}) is None
