"""``attn.latent_copied_block_share`` on scrapes written by hand: copied over
bucket key blocks of the window's chunk dispatches, and no value from a
program that has no such counter (the parent commit), from a window without
a chunk, or from one whose grid counted nothing."""

import pytest

from benchmarks.harness.catalog import Catalog

CELL = "deepseek-v2-5l.longctx"
NAME = "attn.latent_copied_block_share"


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
    assert cat.manifest["per_layer"][-1] is mine     # appended, not inserted
    for cell in cat.manifest["workloads"]:
        names = [m["name"] for m in cat.metrics("per_layer", cell["name"])]
        assert (NAME in names) == (cell["name"] == CELL)
