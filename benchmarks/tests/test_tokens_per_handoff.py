"""``engine.tokens_per_handoff`` on hand-made scrapes: the window's tokens
over its cross-thread calls, and ``None`` from a program that has no such
counter (the parent commit) or handed nothing over."""

import pytest

from benchmarks.harness.catalog import Catalog

NAME = "engine.tokens_per_handoff"
HANDOFFS, TOKENS = ("dyn_engine_handoffs_total",
                    "dyn_engine_handoff_tokens_total")


def series(handoffs=None, tokens=None):
    out = [("dyn_engine_dispatches_total", {"kind": "decode"}, 40.0)]
    if handoffs is not None:
        out += [(HANDOFFS, {}, handoffs), (TOKENS, {}, tokens)]
    return out


@pytest.fixture(scope="module")
def reduce():
    return Catalog().module("layer_metrics", NAME).reduce


@pytest.mark.parametrize("before, after, want", [
    # 1,600 iterations carried 30 lanes x 4 steps each
    (series(100, 9000), series(1700, 201000), 120.0),
    # a call a token, as a program that crossed per token would count
    (series(100, 100), series(1700, 1700), 1.0),
    # a program from before the counter: no value, not 0
    (series(), series(), None),
    # the counter is there and nothing was handed over in the window
    (series(100, 9000), series(100, 9000), None),
])
def test_tokens_over_cross_thread_calls(reduce, before, after, want):
    got = reduce({"before": before, "after": after}, None, {})
    assert got == (want if want is None else pytest.approx(want))


def test_in_the_manifest_for_every_cell():
    entry = {m["name"]: m for m in Catalog().manifest["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "tokens", "better": "higher",
                     "source": "program_counter",
                     "layer": "engine scheduler", "moves": "output_tok_s"}
