"""``engine.prefill_behind_share`` on hand-made scrapes: the share of the
window's prefill dispatches that went behind an unfetched one, and ``None``
from a program that has no such counter (the parent commit) or dispatched
no chunk."""

import pytest

from benchmarks.harness.catalog import Catalog

NAME = "engine.prefill_behind_share"
DISP, BEHIND = ("dyn_engine_dispatches_total",
                "dyn_engine_dispatches_behind_total")


def series(dispatches, behind=None):
    out = [(DISP, {"kind": k}, v) for k, v in dispatches.items()]
    if behind is not None:
        out += [(BEHIND, {"kind": k}, v) for k, v in behind.items()]
    return out


@pytest.fixture(scope="module")
def reduce():
    return Catalog().module("layer_metrics", NAME).reduce


@pytest.mark.parametrize("before, after, want", [
    # 60 chunks in the window, 54 of them behind something
    (series({"prefill": 10, "decode": 40}, {"prefill": 6, "decode": 30}),
     series({"prefill": 70, "decode": 140}, {"prefill": 60, "decode": 120}),
     90.0),
    # the counter is there (decode moved it) and no chunk went behind
    (series({"prefill": 10, "decode": 40}, {"decode": 30}),
     series({"prefill": 30, "decode": 90}, {"decode": 70}), 0.0),
    # a program from before the counter: no value, not 0
    (series({"prefill": 10, "decode": 40}),
     series({"prefill": 70, "decode": 140}), None),
    # no chunk in the window
    (series({"prefill": 10, "decode": 40}, {"prefill": 6, "decode": 30}),
     series({"prefill": 10, "decode": 90}, {"prefill": 6, "decode": 80}),
     None),
])
def test_share_of_the_windows_prefill_dispatches(reduce, before, after, want):
    got = reduce({"before": before, "after": after}, None, {})
    assert got == (want if want is None else pytest.approx(want))


def test_in_the_manifest_for_every_cell():
    entry = {m["name"]: m for m in Catalog().manifest["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "engine scheduler", "moves": "output_tok_s"}
