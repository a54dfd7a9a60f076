"""The float32 reference ``references/llama.py`` against a two-layer case
computed by hand: plain numpy, explicit loops over heads and positions,
written from the published equations and sharing no line with it."""

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from benchmarks.harness import reference  # noqa: E402
from benchmarks.harness.catalog import Catalog  # noqa: E402

llama_ref = Catalog().module("references", "llama")

DIMS = {"L": 2, "D": 8, "Hq": 4, "Hkv": 2, "Dh": 4, "F": 12, "V": 11,
        "theta": 10000.0, "eps": 1e-6}


def _params(rng, bias, tied):
    d = DIMS
    n = lambda *s: rng.normal(0, 0.5, s).astype(np.float32)
    layers = {"ln1": 1 + n(d["L"], d["D"]) * 0.1,
              "ln2": 1 + n(d["L"], d["D"]) * 0.1,
              "wq": n(d["L"], d["D"], d["Hq"], d["Dh"]),
              "wk": n(d["L"], d["D"], d["Hkv"], d["Dh"]),
              "wv": n(d["L"], d["D"], d["Hkv"], d["Dh"]),
              "wo": n(d["L"], d["Hq"], d["Dh"], d["D"]),
              "wg": n(d["L"], d["D"], d["F"]), "wu": n(d["L"], d["D"], d["F"]),
              "wd": n(d["L"], d["F"], d["D"])}
    if bias:
        layers.update(bq=n(d["L"], d["Hq"], d["Dh"]),
                      bk=n(d["L"], d["Hkv"], d["Dh"]),
                      bv=n(d["L"], d["Hkv"], d["Dh"]))
    p = {"embed": n(d["V"], d["D"]), "layers": layers,
         "final_norm": 1 + n(d["D"]) * 0.1}
    if not tied:
        p["lm_head"] = n(d["D"], d["V"])
    return p


def _by_hand(p, tokens):
    d = DIMS
    T, G, half = len(tokens), d["Hq"] // d["Hkv"], d["Dh"] // 2
    norm = lambda x, w: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                    + d["eps"]) * w

    def rope(vec, pos):                    # one head vector [Dh]
        out = np.empty_like(vec)
        for i in range(half):
            ang = pos * d["theta"] ** (-2.0 * i / d["Dh"])
            a, b = vec[i], vec[i + half]
            out[i] = a * math.cos(ang) - b * math.sin(ang)
            out[i + half] = b * math.cos(ang) + a * math.sin(ang)
        return out

    x = p["embed"][tokens].astype(np.float64)
    for l in range(d["L"]):
        w = {k: v[l].astype(np.float64) for k, v in p["layers"].items()}
        h = norm(x, w["ln1"])
        q = np.zeros((T, d["Hq"], d["Dh"]))
        k = np.zeros((T, d["Hkv"], d["Dh"]))
        v = np.zeros((T, d["Hkv"], d["Dh"]))
        for t in range(T):
            for hh in range(d["Hq"]):
                q[t, hh] = rope(h[t] @ w["wq"][:, hh] + (
                    w["bq"][hh] if "bq" in w else 0), t)
            for hh in range(d["Hkv"]):
                k[t, hh] = rope(h[t] @ w["wk"][:, hh] + (
                    w["bk"][hh] if "bk" in w else 0), t)
                v[t, hh] = h[t] @ w["wv"][:, hh] + (
                    w["bv"][hh] if "bv" in w else 0)
        att = np.zeros((T, d["D"]))
        for t in range(T):
            for hh in range(d["Hq"]):
                kv = hh // G
                s = np.array([q[t, hh] @ k[u, kv] / math.sqrt(d["Dh"])
                              for u in range(t + 1)])
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                ctx = sum(pr[u] * v[u, kv] for u in range(t + 1))
                att[t] += ctx @ w["wo"][hh]
        x = x + att
        h = norm(x, w["ln2"])
        gate = h @ w["wg"]
        x = x + ((gate / (1 + np.exp(-gate))) * (h @ w["wu"])) @ w["wd"]
    x = norm(x, p["final_norm"].astype(np.float64))
    head = p["lm_head"] if "lm_head" in p else p["embed"].T
    logits = x @ head.astype(np.float64)
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


@pytest.mark.parametrize("bias,tied", [(True, True), (False, False)])
def test_reference_matches_the_hand_computed_case(bias, tied):
    rng = np.random.default_rng(4)
    p = _params(rng, bias, tied)
    tokens = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    want = _by_hand(p, tokens)
    got = np.asarray(llama_ref.tail_logprobs(
        {"params": jax.tree.map(jax.numpy.asarray, p), "dims": DIMS}, tokens,
        first=2, n_tail=5, variant="full"))
    assert got.shape == (5, DIMS["V"])
    assert np.abs(got - want[2:7]).max() < 2e-5


def test_padding_after_the_scored_positions_is_inert():
    rng = np.random.default_rng(5)
    state = {"params": jax.tree.map(jax.numpy.asarray,
                                    _params(rng, True, True)), "dims": DIMS}
    a = np.array([3, 1, 4, 1, 5, 0, 0, 0], np.int32)
    b = np.array([3, 1, 4, 1, 5, 7, 7, 7], np.int32)
    f = lambda t: np.asarray(llama_ref.tail_logprobs(
        state, t, first=1, n_tail=4, variant="full"))
    assert np.array_equal(f(a), f(b))


def test_the_probes_break_the_model_and_the_comparison_sees_it():
    from benchmarks.harness import correct

    rng = np.random.default_rng(6)
    state = {"params": jax.tree.map(jax.numpy.asarray,
                                    _params(rng, True, True)), "dims": DIMS}
    samples = [{"prompt": [3, 1, 4, 1], "served": [5, 9, 2]}]
    full = reference.score_samples(llama_ref, state, samples)
    dropped = reference.score_samples(llama_ref, state, samples,
                                      "dropped_layer")
    served = [{"tokens": samples[0]["served"],
               "logprobs": full[0]["served_logprob"]}]
    assert correct.compare(served, full)["rel_max_diff"] == 0.0
    assert correct.compare(served, dropped)["ok"] is False
