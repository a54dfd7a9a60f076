"""``attn.latent_copied_block_share`` on scrapes written by hand: copied over
bucket key blocks of the window's chunk dispatches, and no value from a
program that has no such counter (the parent commit), from a window without
a chunk, or from one whose grid counted nothing."""

import pytest

from benchmarks.harness.catalog import Catalog

CELL = "deepseek-v2-5l.longctx"
NAME = "attn.latent_copied_block_share"
# the per-layer names of the manifest that PR 43 appended this metric to
AT_PR43 = """
    loadgen.lateness_p90_ms engine.decode_dispatch_ms
    engine.batch_occupancy program.decode_step_ms
    program.decode_step_mfu_share program.prefill_chunk_ms
    kernel.attn_busy_share device.idle_share frontend.pre_engine_ms
    frontend.post_engine_ms engine.queue_wait_ms engine.lane_wait_ms
    client.ttft_p90_ms engine.prefill_span_ms
    engine.prefill_tokens_per_dispatch engine.host_ms_per_dispatch
    engine.device_wait_share program.compiles_in_window attn.selected_share
    moe.rows_per_expert_hit kernel.index_select_roofline_share
    kernel.moe_ffn_roofline_share engine.prefill_behind_share
    engine.tokens_per_handoff cache.window_resident_share
    moe.held_assignment_share kernel.attn_window_roofline_share
    kernel.attn_full_roofline_share kernel.moe_share_ffn_roofline_share
    sampler.greedy_dispatch_share kernel.ssm_step_roofline_share
    kernel.ssm_scan_roofline_share ssm.active_state_share step.mixer_ms
    step.ffn_ms step.head_ms step.unscoped_ms scope.ssm_step_roofline_share
    scope.ssm_scan_roofline_share scope.moe_ffn_roofline_share
    scope.index_select_roofline_share scope.attn_full_roofline_share
    scope.attn_window_roofline_share scope.moe_share_ffn_roofline_share
    attn.live_page_share program.latent_decode_step_mfu_share
    scope.attn_latent_decode_roofline_share
    scope.attn_latent_prefill_roofline_share
    scope.moe_shared_ffn_roofline_share attn.latent_keys_per_step
""".split()


def series(**blocks):
    out = [("dyn_engine_info", {"platform": "tpu",
                                "device_kind": "TPU v5 lite"}, 1.0)]
    for state, v in blocks.items():
        out.append(("dyn_attn_latent_key_blocks_total",
                    {"kind": "prefill", "state": state}, float(v)))
    return out


def test_copied_over_bucket_and_nothing_without_the_counter():
    cat = Catalog()
    metric = cat.module("layer_metrics", NAME)
    assert metric.BLOCKS == "dyn_attn_latent_key_blocks_total"
    before = series(bucket=4096, copied=4000)
    after = series(bucket=4096 + 201 * 512, copied=4000 + 201 * 300)
    got = metric.reduce({"before": before, "after": after}, None, {})
    assert got == pytest.approx(100 * 300 / 512) and 0 < got <= 100
    # every block of the grid copied: the most it can read
    full = series(bucket=4096 + 64, copied=4000 + 64)
    assert metric.reduce({"before": before, "after": full}, None,
                         {}) == pytest.approx(100.0)
    # no chunk in the window; a grid that counted nothing; no counter at all
    assert metric.reduce({"before": after, "after": after}, None, {}) is None
    assert metric.reduce({"before": series(bucket=0, copied=0),
                          "after": series(bucket=0, copied=0)}, None,
                         {}) is None
    assert metric.reduce({"before": series(), "after": series()}, None,
                         {}) is None
    # a decode series of that name (there is none) would not be read
    other = after + [("dyn_attn_latent_key_blocks_total",
                      {"kind": "decode", "state": "copied"}, 1e9)]
    assert metric.reduce({"before": before, "after": other}, None,
                         {}) == pytest.approx(got)


def test_the_manifest_lists_it_for_the_latent_cell_alone():
    cat = Catalog()
    mine, = [m for m in cat.manifest["per_layer"] if m["name"] == NAME]
    assert mine == {"name": NAME, "unit": "%", "better": "lower",
                    "source": "program_counter", "layer": "kernels",
                    "moves": "ttft_p50_ms", "workloads": [CELL]}
    # appended, not inserted: what stands before it is what stood before it
    # when it came, in that order, less what has been taken out since
    names = [m["name"] for m in cat.manifest["per_layer"]]
    assert names[:names.index(NAME)] == [n for n in AT_PR43 if n in names]
    for cell in cat.manifest["workloads"]:
        names = [m["name"] for m in cat.metrics("per_layer", cell["name"])]
        assert (NAME in names) == (cell["name"] == CELL)
