"""``attn.live_page_share`` on two scrapes written by hand: live over visited
pages of the window, both attention kinds together, and no value from a
program that has no such counters (the parent commit) or that ran no decode
dispatch in the window."""

import pytest

from benchmarks.harness.catalog import Catalog


def series(**pages):
    out = [("dyn_engine_info", {"platform": "tpu",
                                "device_kind": "TPU v5 lite"}, 1.0)]
    for name, by_kind in pages.items():
        for kind, v in by_kind.items():
            out.append((name, {"kind": kind}, float(v)))
    return out


def test_live_over_visited_and_nothing_without_counters():
    cat = Catalog()
    metric = cat.module("layer_metrics", "attn.live_page_share")
    reduce, LIVE, VISITED = metric.reduce, metric.LIVE, metric.VISITED
    assert (LIVE, VISITED) == ("dyn_attn_pages_live_total",
                               "dyn_attn_pages_visited_total")
    before = series(**{LIVE: {"full": 100, "window": 10},
                       VISITED: {"full": 800, "window": 80}})
    after = series(**{LIVE: {"full": 100 + 3 * 40, "window": 10 + 2 * 30},
                      VISITED: {"full": 800 + 8 * 40, "window": 80 + 8 * 30}})
    got = reduce({"before": before, "after": after}, None, {})
    assert got == pytest.approx(100 * (120 + 60) / (320 + 240))
    # no decode dispatch in the window; a program without the counters
    assert reduce({"before": after, "after": after}, None, {}) is None
    assert reduce({"before": series(), "after": series()}, None, {}) is None
    # every cell's decode runs the kernel: the manifest gives it no list
    for cell in cat.manifest["workloads"]:
        mine = [m for m in cat.metrics("per_layer", cell["name"])
                if m["name"] == "attn.live_page_share"]
        assert len(mine) == 1 and mine[0]["moves"] == "tpot_p90_ms"
        assert mine[0]["source"] == "program_counter"
