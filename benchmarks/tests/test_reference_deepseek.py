"""The contract of ``references/deepseek_v2.py`` (``build``,
``tail_logprobs``, ``VARIANTS``) at a tiny size on the CPU, and that each of
its broken variants differs from ``full``."""

import numpy as np
import pytest

from benchmarks.harness.catalog import Catalog
from benchmarks.harness.reference import PROBE_VARIANTS, score_samples

TINY = {
    "model_type": "deepseek_v2", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": False, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "vocab_size": 259, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "attention_bias": False,
    "hidden_act": "silu",
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "expert_shard": {"router_experts": 16, "first_expert": 0},
}


@pytest.fixture(scope="module")
def module():
    return Catalog().module("references", "deepseek_v2")


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
    # the blocked programs and the tests' one-program trace agree
    toks = np.zeros(128, np.int32)
    seq = samples[0]["prompt"] + samples[0]["served"][:-1]
    toks[:len(seq)] = seq
    _, whole = module.trace(state, toks)
    tail = np.asarray(module.tail_logprobs(state, toks, 39, 6))
    # (near-tied routing is mixed in the one and not in the other)
    assert np.abs(tail - np.asarray(whole)[39:45]).max() < 0.05


@pytest.mark.parametrize("variant", [
    "dropped_layer", "int8", "no_shared", "plain_top6", "renormalised",
    "scaling_1", "no_pe_term", "plain_rotary"])
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
