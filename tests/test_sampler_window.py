"""The sampler's top-k window runs only in a dispatch where an active lane
samples (``sampling.sample`` / ``any_sampling``): same numbers out as the
straight-line sampler it replaced, the window inside one branch of a ``cond``
in the bucket programs, stale slot temperatures ignored, and
``dyn_engine_greedy_dispatches_total`` counting the branch the program took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.engine import EngineCore
from dynamo_tpu.engine.sampling import STATIC_K, any_sampling, sample
from dynamo_tpu.llm.protocols.common import SamplingOptions

from test_jax_engine import _bucket_program, drain, make_cfg, req


def _sample_before(logits, temperature, top_p, top_k, key):
    """``sample()`` as it stood before the window went under a ``cond``
    (commit 9b6741e), kept as the reference: every lane pays the window."""
    greedy_tok = jnp.argmax(logits, axis=-1)

    vals, idxs = jax.lax.top_k(logits, STATIC_K)  # [B,K]
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = vals / temp
    probs = jax.nn.softmax(scaled, axis=-1)
    karr = jnp.where(top_k[:, None] > 0, top_k[:, None], STATIC_K)
    kmask = jnp.arange(STATIC_K)[None, :] < karr
    cum = jnp.cumsum(probs, axis=-1)
    pmask = (cum - probs) < top_p[:, None]
    mask = kmask & pmask
    masked = jnp.where(mask, scaled, -jnp.inf)

    split = jax.vmap(lambda k: jax.random.split(k, 2))(key)
    new_keys, sub = split[:, 0], split[:, 1]
    draw = jax.vmap(jax.random.categorical)(sub, masked)
    sampled_tok = jnp.take_along_axis(idxs, draw[:, None], axis=-1)[:, 0]

    token = jnp.where(temperature <= 0.0, greedy_tok, sampled_tok)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logprob = jnp.take_along_axis(logp_all, token[:, None], axis=-1)[:, 0]
    return token.astype(jnp.int32), logprob, new_keys


B, V = 6, 1000
LANES = {
    "all_greedy": [0.0] * B,
    "all_sampling": [0.7, 1.0, 1.3, 0.2, 2.0, 0.9],
    "mixed": [0.0, 0.8, 0.0, 1.2, 0.0, 0.0],
}
WINDOWS = {
    "plain": ([1.0] * B, [0] * B),
    "top_k": ([1.0] * B, [5, 0, 1, 64, 40, 3]),
    "top_p": ([0.9, 0.5, 1.0, 0.95, 0.1, 0.7], [0] * B),
    "top_k_top_p": ([0.9, 0.5, 1.0, 0.95, 0.1, 0.7], [5, 0, 1, 64, 40, 3]),
}


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("lanes", LANES)
def test_sample_gives_what_the_straight_line_sampler_gave(lanes, window):
    """Tokens, log-probabilities and new keys bit-equal, whichever branch
    the dispatch takes; an ``active`` mask over every lane changes nothing."""
    logits = 4.0 * jax.random.normal(jax.random.key(11), (B, V), jnp.float32)
    keys = jax.random.split(jax.random.key(7), B)
    temp = np.asarray(LANES[lanes], np.float32)
    top_p = np.asarray(WINDOWS[window][0], np.float32)
    top_k = np.asarray(WINDOWS[window][1], np.int32)
    want = jax.jit(_sample_before)(logits, temp, top_p, top_k, keys)
    for active in (None, np.ones(B, bool)):
        got = jax.jit(sample)(logits, temp, top_p, top_k, keys, active)
        for g, w in zip(got[:2], want[:2]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(jax.random.key_data(got[2]),
                                      jax.random.key_data(want[2]))


def test_lanes_the_dispatch_does_not_serve_do_not_decide():
    """A stale temperature on an inactive lane leaves the window out: the
    lanes served read as they read with it, and every key still advances."""
    logits = 4.0 * jax.random.normal(jax.random.key(3), (B, V), jnp.float32)
    keys = jax.random.split(jax.random.key(5), B)
    temp = np.asarray([0.0, 0.9, 0.0, 0.0, 1.1, 0.0], np.float32)
    active = temp == 0.0
    top_p, top_k = np.ones(B, np.float32), np.zeros(B, np.int32)
    assert not any_sampling(temp, active) and any_sampling(temp)
    assert any_sampling(temp, ~active)
    want = jax.jit(_sample_before)(logits, temp, top_p, top_k, keys)
    got = jax.jit(sample)(logits, temp, top_p, top_k, keys, active)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(np.asarray(g)[active],
                                      np.asarray(w)[active])
    np.testing.assert_array_equal(jax.random.key_data(got[2]),
                                  jax.random.key_data(want[2]))
    # the lanes left out fall back to argmax, not to a draw never made
    np.testing.assert_array_equal(np.asarray(got[0])[~active],
                                  np.argmax(np.asarray(logits), -1)[~active])


# ----------------------------------------------------------------------
# the engine: one core whose window reports every run to the host
# ----------------------------------------------------------------------
class _Windows:
    """Runs of the window branch, counted where the branch runs (a
    ``jax.debug.callback`` inside it: only a branch taken calls back)."""

    def __init__(self):
        self.n = 0

    def runs(self):
        jax.effects_barrier()
        return self.n

    def bump(self):
        self.n += 1


@pytest.fixture(scope="module")
def windows():
    with pytest.MonkeyPatch.context() as mp:
        seen, draw = _Windows(), sampling._window_draw

        def counted(*a):
            jax.debug.callback(seen.bump)
            return draw(*a)

        mp.setattr(sampling, "_window_draw", counted)
        yield seen


@pytest.fixture(scope="module")
def core(windows):
    return EngineCore(make_cfg())


def _sampled(seed=77, **kw):
    return SamplingOptions(temperature=0.9, top_p=0.95, seed=seed, **kw)


def _tokens(core, reqs):
    for name, r in reqs.items():
        core.submit(name, r)
    got = drain(core, list(reqs))
    while core.has_work:        # the overshoot dispatch behind the finish
        core.step()
    return {name: [(g.token, g.logprob) for g in got[name]] for name in reqs}


def _dispatches(core):
    n, g = core.stage.engine_dispatches, core.stage.engine_greedy_dispatches
    return {k: (n.get(k), g.get(k)) for k in ("prefill", "decode")}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def _where_is(jaxpr, name, inside=()):
    """For every ``name`` equation: the chain of (cond equation, branch
    index) pairs it sits under, outermost first."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield inside
        if eqn.primitive.name == "cond":
            for i, br in enumerate(eqn.params["branches"]):
                yield from _where_is(br.jaxpr, name, inside + ((id(eqn), i),))
        else:
            for sub in _sub_jaxprs(eqn):
                yield from _where_is(sub, name, inside)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_top_k_sits_in_one_branch_of_a_cond(core, kind):
    """Nothing of the window is on the program's straight path: every
    ``top_k`` is under one and the same branch of one ``cond``, and the
    lowered program keeps the conditional."""
    fn, args = _bucket_program(core, kind)
    found = list(_where_is(jax.make_jaxpr(fn.jitted)(*args).jaxpr, "top_k"))
    assert found and all(found), found
    assert len(set(found)) == 1 and len(found[0]) == 1, found
    text = fn.jitted.lower(*args).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    assert "top_k" in text


def test_a_slot_freed_by_a_sampling_request_serves_greedy_ones(windows):
    """One lane: the greedy request takes the slot a ``temperature > 0``
    request left. Its dispatches count as greedy, none runs the window, and
    its tokens are a fresh engine's."""
    one = EngineCore(make_cfg(max_batch=1))
    prompt = list(range(60, 20, -1))
    fresh = _tokens(EngineCore(make_cfg(max_batch=1)),
                    {"g": req(prompt, max_tokens=9)})["g"]
    before, ran = _dispatches(one), windows.runs()
    _tokens(one, {"s": req([7, 8, 9, 10], max_tokens=9, sampling=_sampled())})
    mid = _dispatches(one)
    assert windows.runs() > ran
    assert one.sampling.temperature[0] > 0      # nothing cleared the slot
    for k in mid:       # the sampling request's dispatches: none greedy
        assert mid[k][0] > before[k][0] and mid[k][1] == before[k][1], k
    ran = windows.runs()
    got = _tokens(one, {"g": req(prompt, max_tokens=9)})["g"]
    after = _dispatches(one)
    assert got == fresh
    assert windows.runs() == ran
    for k in after:     # the greedy request's: all of them
        assert after[k][0] - mid[k][0] == after[k][1] - mid[k][1] > 0, k


def test_a_seeded_lane_beside_greedy_lanes_reads_as_alone(core):
    alone = {
        "s": req([40, 41, 42], max_tokens=10, sampling=_sampled(1234)),
        "g1": req([9, 10, 11, 12], max_tokens=10),
        "g2": req(list(range(100, 140)), max_tokens=7),
    }
    want = {}
    for name, r in alone.items():
        want.update(_tokens(core, {name: r}))
    together = _tokens(core, {name + "+": r for name, r in alone.items()})
    assert {n[:-1]: t for n, t in together.items()} == want
    assert len({t for t, _ in want["s"]}) > 1


def test_the_greedy_counter_is_the_branch_the_program_took(core, windows):
    """Over greedy, sampling and mixed traffic: a decode dispatch that does
    not count as greedy ran the window in each of its steps, a chunk once,
    and a dispatch that counts as greedy never."""
    N = core.cfg.decode_steps
    for reqs in (
        {"a": req([1, 2, 3], max_tokens=6),
         "b": req(list(range(200, 130, -1)), max_tokens=5)},
        {"c": req([4, 5, 6], max_tokens=6, sampling=_sampled(5))},
        {"d": req([7, 8, 9, 1], max_tokens=9),
         "e": req(list(range(50, 120)), max_tokens=6, sampling=_sampled(6)),
         "f": req([3, 1, 4, 1, 5], max_tokens=3)},
    ):
        before, ran = _dispatches(core), windows.runs()
        _tokens(core, reqs)
        d = {k: (n - before[k][0], g - before[k][1])
             for k, (n, g) in _dispatches(core).items()}
        assert windows.runs() - ran == (
            (d["decode"][0] - d["decode"][1]) * N
            + d["prefill"][0] - d["prefill"][1]), (reqs.keys(), d)
        sampling_here = any(not r.sampling.greedy for r in reqs.values())
        assert (d["decode"][1] < d["decode"][0]) == sampling_here
