"""``moe.sorted_call_share`` on two scrapes written by hand: the decode
dispatches' sorted calls of a routed layer over all their calls, the prefill
series left out; a program without the counter (the parent commit) or a
window without a decode call reads as no value; listed for the lfm2 cell
alone."""

import pytest

from benchmarks.harness.catalog import Catalog

NAME = "moe.sorted_call_share"
CELL = "lfm2-24b-a2b-8l.toolcalls"
CALLS, SORTED = "dyn_moe_layer_calls_total", "dyn_moe_sorted_calls_total"


def series(counters=None):
    out = [("dyn_engine_info", {"platform": "tpu", "moe_dispatch":
                                "decode:by_hit,chunk:dense32-512"}, 1.0)]
    for (name, kind), v in (counters or {}).items():
        out.append((name, {"kind": kind}, float(v)))
    return out


@pytest.fixture(scope="module")
def cat():
    return Catalog()


def reduce(cat, before, after):
    return cat.module("layer_metrics", NAME).reduce(
        {"before": series(before), "after": series(after)}, None,
        {"config": cat.data("configs", "lfm2-24b-a2b-8l")})


def test_the_share_is_the_windows_own_decode_calls(cat):
    before = {(CALLS, "decode"): 240.0, (SORTED, "decode"): 240.0,
              (CALLS, "prefill"): 60.0}
    after = {(CALLS, "decode"): 240.0 + 4800, (SORTED, "decode"): 240.0 + 4560,
             (CALLS, "prefill"): 9000.0}
    assert reduce(cat, before, after) == pytest.approx(95.0)
    # every call dense: a value, not a missing one
    after[SORTED, "decode"] = 240.0
    assert reduce(cat, before, after) == 0.0


def test_a_program_without_the_counter_reads_as_no_value(cat):
    calls = {(CALLS, "decode"): 4800.0}
    assert reduce(cat, {}, calls) is None                 # the parent commit
    assert reduce(cat, {(SORTED, "decode"): 0.0},
                  {(SORTED, "decode"): 0.0}) is None      # no decode call


def test_the_manifest_lists_it_for_the_lfm2_cell_alone(cat):
    listed = {m["name"]: m for m in cat.manifest["per_layer"]}[NAME]
    assert listed == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p90_ms", "workloads": [CELL]}
    for w in cat.manifest["workloads"]:
        mine = {m["name"] for m in cat.metrics("per_layer", w["name"])}
        assert (NAME in mine) == (w["name"] == CELL)
