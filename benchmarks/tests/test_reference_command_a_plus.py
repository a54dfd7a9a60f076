"""The contract of ``references/command_a_plus.py`` (``build``,
``tail_logprobs``, ``VARIANTS``) at a tiny size on the CPU, and that each of
its broken variants differs from ``full``."""

import numpy as np
import pytest

from benchmarks.harness.catalog import Catalog
from benchmarks.harness.reference import PROBE_VARIANTS, score_samples
from tests.test_command_a_plus import TINY


@pytest.fixture(scope="module")
def module():
    return Catalog().module("references", "command_a_plus")


@pytest.fixture(scope="module")
def state(module):
    return module.build(TINY, 5)


@pytest.fixture(scope="module")
def samples():
    rng = np.random.default_rng(0)
    return [{"prompt": rng.integers(0, 259, n).tolist(),
             "served": rng.integers(0, 259, 6).tolist()} for n in (40, 90)]


def test_the_contract(module, state, samples):
    assert set(PROBE_VARIANTS) <= set(module.VARIANTS)
    assert module.VARIANTS[0] == "full"
    out = score_samples(module, state, samples)
    assert len(out) == 2
    for o in out:
        assert set(o) == {"logit_std", "served_logprob", "best_logprob",
                          "best_token"}
        assert all(len(v) == 6 for v in o.values())
        assert all(b >= s for b, s in zip(o["best_logprob"],
                                          o["served_logprob"]))
    # the blocked programs (a block of queries, an expert at a time) and the
    # tests' one-program trace agree
    toks = np.zeros(128, np.int32)
    seq = samples[0]["prompt"] + samples[0]["served"][:-1]
    toks[:len(seq)] = seq
    chosen, whole = module.trace(state, toks)
    assert chosen.shape == (4, 128, 2)
    tail = np.asarray(module.tail_logprobs(state, toks, 39, 6))
    # (near-tied routing is mixed in the one and not in the other)
    assert np.abs(tail - np.asarray(whole)[39:45]).max() < 0.05
    d = state["dims"]
    assert (d["R"], d["E"], d["first"], d["S"], d["W"]) == (8, 4, 2, 4, 8)
    assert d["window"] == (True, True, True, False)


@pytest.mark.parametrize("variant", [
    "dropped_layer", "int8", "sequential_block", "second_norm", "rms_norm",
    "rope_full_too", "no_rope", "rotate_half", "shared_summed",
    "softmax_routing", "gates_raw", "window_minus", "window_plus"])
def test_every_broken_variant_differs(module, state, samples, variant):
    assert variant in module.VARIANTS
    full = score_samples(module, state, samples[:1])[0]
    broken = score_samples(module, state, samples[:1], variant)[0]
    gap = np.abs(np.asarray(full["served_logprob"])
                 - np.asarray(broken["served_logprob"])).max()
    assert gap > 1e-3, (variant, gap)


def test_an_unknown_variant_raises(module, state):
    with pytest.raises(ValueError, match="no variant"):
        module.tail_logprobs(state, np.zeros(128, np.int32), 3, 2, "nope")
