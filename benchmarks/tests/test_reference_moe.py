"""The rehearsal's reference of another architecture (``tests/rehearsal/
references/tiny_moe.py``: a router and experts) against the program's own
forward on the same seeded weights, and its two broken variants against the
comparison that decides ``correct``."""

import dataclasses
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from benchmarks.harness import correct, reference  # noqa: E402
from benchmarks.harness.catalog import BENCH, Catalog  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402

REHEARSAL = os.path.join(BENCH, "tests", "rehearsal")
T = 48


@pytest.fixture(scope="module")
def loaded():
    cat = Catalog(os.path.join(REHEARSAL, "BENCHMARK.json"), roots=[REHEARSAL])
    config = cat.data("configs", "tiny-moe")
    module = cat.module("references", config["benchmark"]["reference"])
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    return module, module.build(hf, 2147483659 % (2 ** 31 - 1))


def _program_logprobs(params, tokens):
    """``llama.forward`` over the whole sequence in one chunk against a fresh
    pool, in float32: the same numbers the reference rounds from."""
    cfg = dataclasses.replace(llama.preset("tiny-moe"), dtype=jnp.float32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n = len(tokens)
    pool = jnp.zeros((cfg.num_layers, cfg.num_kv_heads, n // 8 + 2, 8,
                      cfg.head_dim), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        logits, _, _ = llama.forward(
            p32, cfg, jnp.asarray(tokens, jnp.int32)[None], pos, pool, pool,
            pos + 8, pos + 8, pos, jnp.ones((1, n), bool))
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1))


def test_the_moe_reference_agrees_with_the_programs_forward(loaded):
    module, state = loaded
    tokens = np.random.default_rng(7).integers(0, 259, T).astype(np.int32)
    padded = np.zeros(128, np.int32)
    padded[:T] = tokens
    got = np.asarray(module.tail_logprobs(state, padded, 0, T, "full"))
    want = _program_logprobs(state["params"], tokens)
    assert got.shape == want.shape == (T, 259)
    # float32 on both sides from the same bf16-rounded weights; what differs
    # is the order of summation (the program sorts tokens by expert and runs
    # segment matmuls, the reference computes every expert and masks), and
    # no routing choice flips at that precision: the widest gap read 1.1e-4
    # on log-probabilities of magnitude 4 to 8
    assert np.abs(got - want).max() < 5e-4


def test_its_broken_variants_fail_the_comparison(loaded):
    module, state = loaded
    rng = np.random.default_rng(8)
    samples = [{"prompt": rng.integers(0, 259, n).tolist(), "served": []}
               for n in (5, 40, 90, 150)]
    for s in samples:                     # greedy under the model itself
        seq = list(s["prompt"])
        for _ in range(24):
            padded = np.zeros(256, np.int32)
            padded[: len(seq)] = seq
            lp = module.tail_logprobs(state, padded, len(seq) - 1, 1, "full")
            seq.append(int(np.argmax(np.asarray(lp)[0])))
        s["served"] = seq[len(s["prompt"]):]
    full = reference.score_samples(module, state, samples)
    served = [{"tokens": s["served"], "logprobs": r["served_logprob"]}
              for s, r in zip(samples, full)]
    assert correct.compare(served, full)["ok"] is True
    for variant in reference.PROBE_VARIANTS:
        broken = reference.score_samples(module, state, samples, variant)
        verdict = correct.compare(served, broken)
        assert verdict["ok"] is False, (variant, verdict)
