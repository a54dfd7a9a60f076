"""The contract of ``references/longcat_flash.py`` (``build``,
``tail_logprobs``, ``VARIANTS``) at a tiny size on the CPU, and that each of
its broken variants differs from ``full``."""

import numpy as np
import pytest

from benchmarks.harness.catalog import Catalog
from benchmarks.harness.reference import PROBE_VARIANTS, score_samples

TINY = {
    "model_type": "longcat_flash", "attention_bias": False,
    "vocab_size": 259, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 8, "max_position_embeddings": 1024,
    "rms_norm_eps": 1e-5, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "router_bias": False, "norm_topk_prob": False,
    "expert_shard": {"router_experts": 16, "first_expert": 0},
}


@pytest.fixture(scope="module")
def module():
    return Catalog().module("references", "longcat_flash")


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
    chosen, whole = module.trace(state, toks)
    assert chosen.shape == (2, 128, 4)          # one branch a PUBLISHED layer
    tail = np.asarray(module.tail_logprobs(state, toks, 39, 6))
    # (near-tied routing is mixed in the one and not in the other)
    assert np.abs(tail - np.asarray(whole)[39:45]).max() < 0.05


@pytest.mark.parametrize("variant", [
    "dropped_layer", "int8", "no_branch", "no_identity", "renormalised",
    "scaling_1", "bias_weighs", "branch_after_first", "no_q_scale",
    "no_kv_scale", "q_scale_nope_only", "kv_scale_on_key"])
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
