"""The contract of ``references/lfm2_moe.py`` (``build``, ``tail_logprobs``,
``VARIANTS``) at a tiny size on the CPU, and that each of its broken and own
variants differs from ``full``."""

import numpy as np
import pytest

from benchmarks.harness.catalog import Catalog
from benchmarks.harness.reference import PROBE_VARIANTS, score_samples

TINY = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 160,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv"],
    "max_position_embeddings": 1024, "moe_intermediate_size": 32,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 259,
}


@pytest.fixture(scope="module")
def module():
    return Catalog().module("references", "lfm2_moe")


@pytest.fixture(scope="module")
def state(module):
    return module.build(TINY, 5)


@pytest.fixture(scope="module")
def samples():
    """The second prompt crosses position 256, where ``tail_dropped`` drops
    the tail, inside its scored positions."""
    rng = np.random.default_rng(0)
    return [{"prompt": rng.integers(0, 259, n).tolist(),
             "served": rng.integers(0, 259, 6).tolist()} for n in (40, 253)]


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
        # a tied head of rows of norm 1: logits of about unit spread
        assert all(0.3 < s < 3.0 for s in o["logit_std"])


def test_the_blocked_programs_and_the_one_program_trace_agree(module, state,
                                                              samples):
    """128 positions a block against the whole sequence as one block, and the
    chosen experts of the 6 routed layers come out [6, T, 2]."""
    toks = np.zeros(256, np.int32)
    seq = samples[1]["prompt"]
    toks[:len(seq)] = seq
    blocked = np.asarray(module.tail_logprobs(state, toks, 100, 8))
    one = np.zeros(250, np.int32)               # no multiple of 128
    one[:] = toks[:250]
    whole = np.asarray(module.tail_logprobs(state, one, 100, 8))
    # (near-tied routing is mixed alike in both)
    assert np.abs(blocked - whole).max() < 1e-4
    chosen = module.trace(state, toks)
    assert chosen.shape == (6, 256, 2)
    assert chosen.min() >= 0 and chosen.max() < 8


@pytest.mark.parametrize("variant", [
    "dropped_layer", "int8", "tail_dropped", "gate_c_off", "gate_b_off",
    "taps_reversed", "bias_off", "renorm_off", "qk_norm_off",
    "dense_as_routed"])
def test_every_broken_variant_differs(module, state, samples, variant):
    assert variant in module.VARIANTS
    full = score_samples(module, state, samples[1:])[0]
    broken = score_samples(module, state, samples[1:], variant)[0]
    gap = np.abs(np.asarray(full["served_logprob"])
                 - np.asarray(broken["served_logprob"])).max()
    assert gap > 1e-3, (variant, gap)


def test_every_variant_of_the_file_is_tried_here(module):
    import inspect
    here = inspect.getsource(test_every_broken_variant_differs)
    assert all(v in here or v == "full" for v in module.VARIANTS)


def test_an_unknown_variant_raises(module, state):
    with pytest.raises(ValueError, match="no variant"):
        module.tail_logprobs(state, np.zeros(128, np.int32), 3, 2, "nope")
