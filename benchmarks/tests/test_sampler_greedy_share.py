"""``sampler.greedy_dispatch_share`` on hand-made scrapes: the share of the
window's decode dispatches that served greedy lanes only, and ``None`` from
a program that has no such counter (the parent commit) or dispatched no
decode."""

import pytest

from benchmarks.harness.catalog import Catalog

NAME = "sampler.greedy_dispatch_share"
DISP, GREEDY = ("dyn_engine_dispatches_total",
                "dyn_engine_greedy_dispatches_total")


def series(dispatches, greedy=None):
    out = [(DISP, {"kind": k}, v) for k, v in dispatches.items()]
    if greedy is not None:
        out += [(GREEDY, {"kind": k}, v) for k, v in greedy.items()]
    return out


@pytest.fixture(scope="module")
def reduce():
    return Catalog().module("layer_metrics", NAME).reduce


@pytest.mark.parametrize("before, after, want", [
    # every cell of the benchmark today: all-greedy traffic
    (series({"prefill": 10, "decode": 40}, {"prefill": 10, "decode": 40}),
     series({"prefill": 70, "decode": 1840}, {"prefill": 70, "decode": 1840}),
     100.0),
    # 400 decode dispatches, 100 of them with a sampling lane; the chunks'
    # own count does not enter
    (series({"prefill": 10, "decode": 40}, {"prefill": 2, "decode": 30}),
     series({"prefill": 70, "decode": 440}, {"prefill": 2, "decode": 330}),
     75.0),
    # the counter is there (a chunk moved it) and no decode was greedy
    (series({"prefill": 10, "decode": 40}, {"prefill": 6}),
     series({"prefill": 30, "decode": 90}, {"prefill": 26}), 0.0),
    # a program from before the counter: no value, not 0
    (series({"prefill": 10, "decode": 40}),
     series({"prefill": 70, "decode": 140}), None),
    # no decode dispatch in the window
    (series({"prefill": 10, "decode": 40}, {"prefill": 10, "decode": 40}),
     series({"prefill": 30, "decode": 40}, {"prefill": 30, "decode": 40}),
     None),
])
def test_share_of_the_windows_decode_dispatches(reduce, before, after, want):
    got = reduce({"before": before, "after": after}, None, {})
    assert got == (want if want is None else pytest.approx(want))


def test_in_the_manifest_for_every_cell():
    entry = {m["name"]: m for m in Catalog().manifest["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "bucket programs", "moves": "tpot_p90_ms"}
