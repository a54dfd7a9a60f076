"""LFM2-MoE-style language model (gated short-convolution layers whose only
cache is a two-row tail a lane, beside GQA layers with per-head q / k norms,
under bias-selected sigmoid-routed experts behind leading dense layers; a
tied head) against its ONE float32 reference,
``benchmarks/references/lfm2_moe.py``, at a tiny size, in float32.

(a) the whole-prompt forward, and chunked prefill (chunks of 1 and 2 tokens,
shorter than the tail, among the sizes) then multi-step decode through the
engine's own programs, dense path and kernels; (b) the conv operator alone
against a plain loop over positions, in chunks with a carried tail and
padding; (c) lanes that join, idle and leave keep their own tails; (d) every
control of the reference fails the tolerance; (e) the published config maps,
each key it cannot honour raises, ``num_dense_layers`` gives ``ffn_kinds``;
(f) the router against a plain loop; (g) experts hit and assignments counted
the same inside a scan and outside; (h) cache kinds, costs, counters, the
engine's labels; (i) what moves blocks refuses the model by name; (j) the
scopes of the lowered programs; (k) a decode step routes its busy rows
alone, and where the rule says dense it chooses sorted or dense on the device
from the experts they hit.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import lfm2_moe as ref
from dynamo_tpu.engine.cache import cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# served log-probability against the reference's, both float32, over 8
# layers (measured: 2e-6, of logits whose spread over the vocabulary is 0.4)
TOL = 3e-5
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


def published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-8l.json")) as f:
        cfg = json.load(f)
    cfg.pop("benchmark")
    return cfg


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def model(hf=TINY):
    return llama.LlamaConfig.from_hf_config(hf, dtype=jnp.float32)


def engine(state, impl, **kw):
    args = dict(page_size=16, max_batch=3, max_context=96, prefill_chunk=16,
                prefill_lanes=2, decode_steps=2)
    args.update(kw)
    c = EngineCore(JaxEngineConfig(model=model(), attn_impl=impl, **args))
    c.params = f32(state["params"])    # the reference's tensors, as float32
    return c


@pytest.fixture(autouse=True)
def one_routing(monkeypatch):
    """Both sides are float32 here: the reference scores its own routing
    alone (on the chip it mixes near-ties, against the bfloat16 path)."""
    monkeypatch.setitem(ref.HOW, "tie_eps", 0.0)


@pytest.fixture(scope="module")
def state():
    return ref.build(TINY, 3)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def core(request, state):
    return engine(state, request.param)


def request_of(prompt, n):
    return BackendInput(token_ids=list(prompt),
                        stop=StopConditions(max_tokens=n))


def run(core, wanted, steps=600, outs=None):
    """Step until every sequence of ``wanted`` has finished; -> their
    outputs by sequence (``outs``: what earlier steps already gave)."""
    outs = {s: list((outs or {}).get(s, ())) for s in wanted}
    for _ in range(steps):
        for so in core.step():
            if so.seq_id in outs:
                outs[so.seq_id].append(so)
        if all(o and o[-1].finish is not None for o in outs.values()):
            for o in outs.values():
                assert o[-1].error is None, o[-1].error
            return outs
    raise AssertionError("did not finish")


def generate(core, seq_id, prompt, n):
    core.submit(seq_id, request_of(prompt, n))
    return run(core, [seq_id])[seq_id]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 259, n).tolist()


def served_of(prompt, outs):
    toks = [o.token for o in outs]
    return (np.asarray(prompt + toks[:-1], np.int32), toks,
            np.asarray([o.token_logprob for o in outs]))


def against(state, served, variant="full"):
    """-> (the reference's log-softmax at the served positions, the largest
    difference of a served log-probability from the reference's)."""
    tokens, toks, logp = served
    T = -(-len(tokens) // 16) * 16
    padded = np.zeros(T, np.int32)
    padded[:len(tokens)] = tokens
    first = len(tokens) - len(toks)
    tail = np.asarray(ref.tail_logprobs(state, padded, first, len(toks),
                                        variant))
    return tail, np.abs(logp - tail[np.arange(len(toks)), toks]).max()


@pytest.fixture(scope="module")
def served(core):
    """Two requests admitted together: 41 and 23 prompt tokens in chunks of
    16, both lanes in one dispatch (ragged last chunks, and the long lane
    prefills on while the short one decodes), then 12 and 9 tokens decoded
    two a dispatch."""
    pa, pb = prompt_of(41), prompt_of(23, 1)
    core.submit("a", request_of(pa, 12))
    core.submit("b", request_of(pb, 9))
    outs = run(core, ["a", "b"])
    return served_of(pa, outs["a"]), served_of(pb, outs["b"])


# ---- (a) -----------------------------------------------------------------
def test_the_whole_prompt_forward_agrees_with_the_reference(state):
    """``llama.forward`` over a whole prompt with padding behind it, from a
    lane whose tail pool held something else: logits at every position, and
    the chosen experts of every routed layer are the reference's."""
    cfg, params = model(), f32(state["params"])
    n, S, page = 40, 48, 16
    toks = np.zeros(S, np.int32)
    toks[:n] = prompt_of(n, 2)
    glob, st = cache_kinds(cfg)
    ks, vs = glob.pool_shapes(5, page)
    cs, = st.state_shapes(3)
    stats = {"chosen": []}
    out = llama.forward(
        params, cfg, jnp.asarray(toks)[None], jnp.arange(S)[None],
        jnp.zeros(ks), jnp.zeros(vs), (page + jnp.arange(S))[None], None,
        jnp.arange(S)[None], (jnp.arange(S) < n)[None],
        read_pages=jnp.asarray([[1, 2, 3]]), stats=stats,
        ssm=(jnp.full(cs, 5.0), jnp.asarray([1]), jnp.asarray([True]),
             jnp.asarray([n])))
    logits, _, _, c_pool = out
    padded = np.zeros(128, np.int32)
    padded[:n] = toks[:n]
    want = np.asarray(ref.tail_logprobs(state, padded, 0, n))
    got = np.asarray(jax.nn.log_softmax(logits[0, :n], -1))
    assert np.abs(got - want).max() < TOL
    # the other lanes of the pool are what they were
    for lane in (0, 2):
        assert float(jnp.abs(c_pool[:, lane] - 5.0).max()) == 0.0
    chosen = np.stack([np.asarray(c)[0, :n] for c in stats["chosen"]])
    theirs = ref.trace(state, padded)[:, :n]
    assert chosen.shape == theirs.shape == (6, n, 2)
    assert (np.sort(chosen, -1) == np.sort(theirs, -1)).all()
    # experts hit, counted inside the scans and outside: the chosen ids'
    # own count, a layer at a time (the padding rows route too)
    every = np.stack([np.asarray(c)[0] for c in stats["chosen"]])
    assert int(stats["experts_hit"]) == sum(
        len(np.unique(layer)) for layer in every)


def test_engine_prefill_and_decode_agree_with_the_reference(core, state,
                                                             served):
    """Every served log-probability is the reference's for that token to
    ``TOL`` and every greedy token is the reference's best, for both lanes,
    through the tail pool and the folded K/V pool."""
    for one in served:
        tail, worst = against(state, one)
        assert one[1] == tail.argmax(-1).tolist()
        assert worst < TOL
    assert core.pool.free_pages == core.pool.num_pages - 1


@pytest.mark.parametrize("chunk", [1, 2, 3, 5, 32])
def test_chunks_shorter_and_longer_than_the_tail(state, chunk):
    """A prompt of 21 tokens prefilled ``chunk`` tokens a dispatch (1 and 2:
    a chunk that does not fill the two-row tail), then decoded."""
    one = engine(state, "xla", max_batch=2, prefill_lanes=1,
                 prefill_chunk=chunk)
    prompt = prompt_of(21, 30 + chunk)
    got = served_of(prompt, generate(one, "c", prompt, 5))
    tail, worst = against(state, got)
    assert got[1] == tail.argmax(-1).tolist()
    assert worst < TOL


# ---- (b) -----------------------------------------------------------------
def plain_conv(bcz, w, D):
    """The operator's recurrence, a loop over positions (float64)."""
    bcz, w = np.asarray(bcz, np.float64), np.asarray(w, np.float64)
    T, K = bcz.shape[0], w.shape[0]
    u = bcz[:, :D] * bcz[:, 2 * D:]
    y = np.zeros((T, D))
    for t in range(T):
        c = sum(w[k] * u[t - (K - 1) + k] for k in range(K)
                if t - (K - 1) + k >= 0)
        y[t] = bcz[t, D:2 * D] * c
    return y, u


@pytest.mark.parametrize("sizes", [(1, 1, 1, 2, 7), (5, 4, 3), (12,),
                                   (2, 10)])
def test_the_conv_operator_is_a_plain_loop_over_positions(sizes):
    """``conv_mix`` over a sequence cut into chunks (each padded to 12 rows
    behind its real tokens), the tail carried from chunk to chunk, then one
    decode step; an idle lane's tail stays bit for bit."""
    D, K, C = 16, 3, 12
    rng = np.random.default_rng(sum(sizes))
    T = sum(sizes) + 1
    bcz = jnp.asarray(rng.normal(size=(T, 3 * D)), jnp.float32)
    lp = {"conv_w": jnp.asarray(rng.uniform(-1, 1, (1, K, D)), jnp.float32)}
    want, u = plain_conv(bcz, lp["conv_w"][0], D)
    tail = jnp.zeros((1, K - 1, D), jnp.float32)
    got, at = [], 0
    for n in sizes:
        rows = jnp.zeros((1, C, 3 * D), jnp.float32).at[0, :n].set(
            bcz[at:at + n])
        y, tail = llama.conv_mix(rows, lp, 0, tail, jnp.asarray([n]), False)
        got.append(np.asarray(y[0, :n]))
        at += n
    # the tail is the last two rows of u, whatever the chunks were
    assert np.allclose(np.asarray(tail[0]), u[at - 2:at][-2:] if at >= 2
                       else np.vstack([np.zeros((1, D)), u[:1]]), atol=1e-6)
    two = jnp.concatenate([tail, jnp.full_like(tail, 9.0)], 0)
    y, new = llama.conv_mix(jnp.stack([bcz[at:at + 1]] * 2), lp, 0, two,
                            jnp.asarray([True, False]), True)
    got.append(np.asarray(y[0]))
    assert np.abs(np.concatenate(got) - want).max() < 1e-5
    assert np.allclose(np.asarray(new[0]), u[at - 1:at + 1], atol=1e-6)
    assert float(jnp.abs(new[1] - 9.0).max()) == 0.0


# ---- (c) -----------------------------------------------------------------
def test_a_reused_slot_serves_its_next_request_as_if_alone(core, state):
    """One slot, three requests one after another: each starts from a zero
    tail whatever the last one left (``dyn_ssm_state_resets_total`` counts
    each once)."""
    one = engine(state, core.attn_impl, max_batch=1, prefill_lanes=1)
    resets = one.stage.ssm_state_resets
    r0 = sum(resets._values.values())
    for k, n in enumerate((37, 18, 33)):
        prompt = prompt_of(n, 10 + k)
        got = served_of(prompt, generate(one, f"r{k}", prompt, 6))
        tail, worst = against(state, got)
        assert got[1] == tail.argmax(-1).tolist()
        assert worst < TOL
    assert sum(resets._values.values()) - r0 == 3


def test_lanes_that_join_idle_and_leave_keep_their_own_tails(core, state):
    """While one lane decodes alone, the other two lanes of the tail pool
    (an empty slot's garbage, planted here) are bit for bit what they were
    after every dispatch; a lane in the MIDDLE of its prefill keeps the tail
    its last chunk left while another lane's decode dispatches run in
    between; and a lane that leaves does not disturb the one that stays."""
    assert core.s_pool is None
    core.c_pool = core.c_pool.at[:, 1:].set(2.0)
    prompt = prompt_of(20, 21)
    core.submit("solo", request_of(prompt, 8))
    lane = None
    for _ in range(200):
        outs = core.step()
        if lane is None and "solo" in core.by_seq:
            lane = core.slots.index(core.by_seq["solo"])
            assert lane == 0
        assert float(jnp.abs(core.c_pool[:, 1:] - 2.0).max()) == 0.0
        if any(o.seq_id == "solo" and o.finish is not None for o in outs):
            break
    else:
        raise AssertionError("did not finish")
    # a long prompt admitted while another lane decodes; the short one
    # leaves first
    pa, pb = prompt_of(12, 22), prompt_of(61, 23)
    core.submit("dec", request_of(pa, 14))
    early = [so for _ in range(3) for so in core.step()]
    assert early and {so.seq_id for so in early} == {"dec"}
    core.submit("long", request_of(pb, 20))
    outs = run(core, ["dec", "long"], outs={"dec": early})
    for prompt, name in ((pa, "dec"), (pb, "long")):
        got = served_of(prompt, outs[name])
        tail, worst = against(state, got)
        assert got[1] == tail.argmax(-1).tolist()
        assert worst < TOL, name


# ---- (d) -----------------------------------------------------------------
@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v != "full"])
def test_every_control_fails_the_tolerance(state, served, variant):
    """Each control moves a served log-probability a thousand times further
    than the sound path lies from the reference. (``tail_dropped`` drops the
    tail at multiples of 256: past this prompt, so it is scored on a longer
    one below.)"""
    if variant == "tail_dropped":
        pytest.skip("scored on a prompt that crosses 256 below")
    _, worst = against(state, served[0], variant)
    assert worst > 1000 * TOL, (variant, worst)


def test_a_tail_lost_at_one_chunk_boundary_fails_the_tolerance(state):
    """The reference's ``tail_dropped`` control (u before position 256 taken
    as 0 for the tokens behind it) moves the log-probabilities just behind
    the boundary."""
    toks = np.zeros(384, np.int32)
    toks[:] = prompt_of(384, 40)
    full = np.asarray(ref.tail_logprobs(state, toks, 250, 20))
    lost = np.asarray(ref.tail_logprobs(state, toks, 250, 20,
                                        "tail_dropped"))
    assert np.abs(full[:6] - lost[:6]).max() == 0.0     # positions < 256
    assert np.abs(full[6:] - lost[6:]).max() > 1000 * TOL


# ---- (e) -----------------------------------------------------------------
def test_the_published_config_maps():
    """The benchmark's configuration (8 of the published 40 layers) and the
    catalog's uncut row: layer kinds, the tail, the fold, the router's law,
    the dense layers, the tied head; construction only."""
    hf = published()
    for L in (8, 40):
        lt = (hf["layer_types"] * 5)[:L]
        cfg = llama.LlamaConfig.from_hf_config(
            {**hf, "num_hidden_layers": L, "layer_types": lt})
        assert cfg.layer_kinds == tuple(0 if t == "full_attention" else 3
                                        for t in lt)
        assert cfg.ffn_kinds == (0, 0) + (1,) * (L - 2)
        assert (cfg.conv_cache, cfg.kv_fold, cfg.head_dim) == (3, 2, 64)
        assert (cfg.router, cfg.router_norm_eps, cfg.routed_scaling) == (
            "sigmoid_bias", 1e-6, 1.0)
        assert cfg.qk_norm and cfg.tie_embeddings and cfg.use_rope
        assert (cfg.rope_theta, cfg.rms_eps) == (1000000, 1e-5)
        assert (cfg.num_experts, cfg.experts_per_token,
                cfg.expert_width) == (64, 4, 1536)
        assert len(llama._segments(cfg)) == {8: 5, 40: 21}[L]
        shapes = jax.eval_shape(lambda: llama.init_params(
            cfg, jax.random.PRNGKey(0)))
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
        # (the norms' weights, q / k norms and expert_bias beside the
        # issue's count of the matrices and taps)
        want = {8: 4_025_257_984, 40: 23_843_661_440 - 40 * 2 * 2048
                - 2048 - 10 * 128 - 38 * 64}[L]
        extra = L * 2 * 2048 + 2048 + (L // 4) * 128 + (L - 2) * 64
        assert n == want + extra, (L, n - want - extra)
    st = cache_kinds(cfg)[1]
    assert st.label() == "state:30x(4096)" and st.lane_bytes(2) == 30 * 8192


@pytest.mark.parametrize("rope", [
    {"rope_parameters": {"rope_theta": 5e5, "rope_type": "default"}},
    {"rope_parameters": None, "rope_theta": 5e5},
])
def test_rope_theta_nested_or_flat(rope):
    hf = {k: v for k, v in {**TINY, **rope}.items() if v is not None}
    assert llama.LlamaConfig.from_hf_config(hf).rope_theta == 5e5


@pytest.mark.parametrize("dense, kinds", [
    (0, None), (1, (0,) + (1,) * 7), (3, (0, 0, 0, 1, 1, 1, 1, 1))])
def test_num_dense_layers_gives_ffn_kinds(dense, kinds):
    cfg = llama.LlamaConfig.from_hf_config({**TINY,
                                            "num_dense_layers": dense})
    assert cfg.ffn_kinds == kinds
    assert cfg.routed_layers == 8 - dense


@pytest.mark.parametrize("change, says", [
    ({"conv_bias": True}, "conv_bias true"),
    ({"layer_types": ["conv", "sliding_attention"] * 4},
     "'conv' or 'full_attention'"),
    ({"layer_types": ["conv", "mamba"] * 4}, "'mamba' or 'attention'"),
    ({"layer_types": ["conv"] * 5}, "each of the 8 layers"),
    ({"norm_topk_prob": False}, "norm_topk_prob false"),
    ({"use_expert_bias": False}, "use_expert_bias false"),
    ({"model_type": "llama"}, "without model_type 'lfm2_moe'"),
    ({"model_type": "qwen3_moe", "layer_types": None, "conv_L_cache": None,
      "conv_bias": None, "num_dense_layers": None}, "use_expert_bias"),
    ({"conv_L_cache": 1}, "keeps no tail"),
    ({"num_dense_layers": 8}, "no routed layer"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "plain rotary"),
    ({"rope_parameters": {"rope_theta": 1e6, "factor": 2}}, "plain rotary"),
    ({"rope_parameters": {"rope_theta": 1e6}, "rope_theta": 1e4},
     "given twice"),
    ({"rope_parameters": None}, "no rope_theta"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"expert_gate_dtype": "float32"}, "expert keys"),
])
def test_what_cannot_be_honoured_raises(change, says):
    hf = {k: v for k, v in {**TINY, **change}.items() if v is not None}
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config(hf)


# ---- (f) -----------------------------------------------------------------
def test_the_router_is_a_plain_loop_the_bias_chooses_and_never_weighs():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 9, 16)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=8) * 0.5, jnp.float32)
    vals, idx = moe.route_topk(x, wr, 3, "sigmoid_bias", bias,
                               scaling=2.5, norm_eps=1e-6)
    plain, _ = moe.route_topk(x, wr, 3, "sigmoid_bias", None)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x[0], np.float64)
                            @ np.asarray(wr, np.float64)))
    moved = 0
    for t in range(9):
        order = np.argsort(-(s[t] + np.asarray(bias)))[:3]
        assert sorted(order) == sorted(np.asarray(idx[0, t]).tolist())
        for e, g in zip(np.asarray(idx[0, t]), np.asarray(vals[0, t])):
            want = s[t, e] / (s[t, order].sum() + 1e-6) * 2.5
            assert abs(g - want) < 1e-6
        moved += sorted(order) != sorted(np.argsort(-s[t])[:3])
    assert moved >= 3            # the bias chose otherwise than the scores
    # the 1e-6: gates of tiny scores do not sum to 1
    tiny, _ = moe.route_topk(x * 0 - 1.0, jnp.full((16, 8), 2.0), 3,
                             "sigmoid_bias", bias, norm_eps=1e-6)
    total = float(tiny[0, 0].sum())
    s0 = 3.0 / (1.0 + np.exp(32.0))
    assert abs(total - s0 / (s0 + 1e-6)) < 1e-6 and total < 0.99
    assert abs(float(plain[0, 0].sum()) - 1.0) < 1e-6


# ---- (g) -----------------------------------------------------------------
@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_experts_hit_and_assignments_count_inside_a_scan_as_outside(
        core, kind):
    """Layers 3-5 are one scan, layers 2 and 6 bodies of their own: the
    counters of a request are what its tokens make them, whatever holds the
    routed layer (assignments: tokens x 2 a token x 6 routed layers; experts
    hit of a one-row dispatch: 2 a routed layer and step)."""
    st = core.stage
    series = (st.moe_assignments, st.moe_experts_hit, st.moe_layer_calls,
              st.engine_dispatches)
    read = lambda: {c.name: c._values.get((kind,), 0.0) for c in series}
    before = read()
    one = engine({"params": core.params}, core.attn_impl, max_batch=1,
                 prefill_lanes=1)
    generate(one, "cnt", prompt_of(21, 5), 5)
    moved = {k: v - before[k] for k, v in read().items()}
    d = int(moved["dyn_engine_dispatches_total"])
    steps = d * (2 if kind == "decode" else 1)
    # (a decode dispatch counts its two steps' tokens, asked for or not)
    tokens = steps if kind == "decode" else 21
    assert moved["dyn_moe_layer_calls_total"] == 6 * steps
    assert moved["dyn_moe_assignments_total"] == tokens * 2 * 6
    if kind == "decode":
        # one row a step: 2 experts a routed layer, exactly
        assert moved["dyn_moe_experts_hit_total"] == 2 * 6 * steps
    else:
        assert 2 * 6 * d <= moved["dyn_moe_experts_hit_total"] <= 8 * 6 * d


# ---- (h) -----------------------------------------------------------------
def test_counters_say_what_the_dispatches_did(core):
    st = core.stage
    series = (st.ssm_lane_steps, st.ssm_active_lane_steps, st.ssm_tokens,
              st.engine_dispatch_tokens, st.engine_dispatches)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    d = int(moved["dyn_engine_dispatches_total", "decode"])
    assert moved["dyn_ssm_tokens_total", "prefill"] == 37
    assert moved["dyn_ssm_tokens_total", "decode"] == n
    assert moved["dyn_ssm_lane_steps_total", "prefill"] == 3
    assert moved["dyn_ssm_active_lane_steps_total", "prefill"] == 3
    assert moved["dyn_ssm_lane_steps_total", "decode"] == d * 3 * 2
    assert moved["dyn_ssm_active_lane_steps_total", "decode"] == d * 2 == n
    assert sum(st.ssm_state_bytes._values.values()) > 0
    assert (core.cache_kinds[1].lane_bytes(4) * core.cfg.max_batch
            == core.c_pool.nbytes)


def test_costs_cache_kinds_and_labels(core):
    from dynamo_tpu.utils import roofline

    m = core.cfg.model
    assert [k.name for k in core.cache_kinds] == ["global", "state"]
    assert [k.label() for k in core.cache_kinds] == [
        "global:2x2x(16+16)", "state:6x(128)"]
    # a tail-only state kind: one pool, no float32 part
    st = core.cache_kinds[1]
    assert st.state == (None, (2 * 64,))
    assert st.state_shapes(3) == ((6, 3, 128),)
    assert st.lane_bytes(4) == 6 * 128 * 4 and st.lane_bytes(2) == 6 * 256
    assert st.token_bytes(4) == 0
    assert core.c_pool.shape == (6, 3, 128) and core.s_pool is None
    assert core._state_pools().keys() == {"c_pool"}
    assert llama.kv_block_bytes(m, 16) == 16 * 2 * 2 * 32 * 4
    costs = roofline.model_costs(m, weight_bytes=1.0)
    assert costs.window_groups == ((None, 2),)
    assert costs.state_bytes_per_lane == 6 * 128 * 4
    assert costs.state_flops_per_token == 8 * 6 * 64
    D, F, Fe = 64, 160, 32
    conv = D * 3 * D + D * D + 3 * D
    attn = D * 64 + 2 * D * 32 + 64 * D
    ffn = 2 * 3 * D * F + 6 * (2 * 3 * D * Fe + D * 8)
    assert costs.mat_flops_per_token == 2.0 * (6 * conv + 2 * attn + ffn)
    held = 2 * 3 * D * F + 6 * 8 * (3 * D * Fe + D)
    assert roofline.model_costs(m).weight_bytes == 4.0 * (
        6 * conv + 2 * attn + held + 259 * D)
    # 8 experts, 2 a token: few large experts, the old rule (sorted from 16
    # rows on); the published geometry (64 of them, 4 a token) below. A
    # decode step knows its busy rows: where the rule says dense it holds
    # both forms and chooses by the experts they hit
    assert core.moe_dispatch == "decode:by_hit,chunk:sorted"
    assert core._decode_cols == ("experts_hit", "sorted")
    assert moe.dispatch_form(32, 4, 64) == "dense"
    assert moe.dispatch_form(32, 4, 64, masked=True) == "by_hit"
    assert moe.dispatch_form(8, 4, 64, masked=True) == "sorted"
    assert moe.dispatch_form(8, 4, 64) == "sorted"
    assert moe.dispatch_form(1024, 4, 64) == "sorted"


# ---- (k) a decode step routes its busy rows alone --------------------------
def routed_layer(rows, E=16, K=3, D=32, F=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (rows, 1, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, E), jnp.float32) / np.sqrt(D) * 2
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
              for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) / np.sqrt(F)
    return x, wr, wg, wu, wd, K


def primitives(fn, *args, under="", **kw):
    """The names of every primitive ``fn`` traces to, sub-programs too
    (``under``: those traced under that scope alone)."""
    def names(jp, above=""):
        for eqn in jp.eqns:
            here = f"{above}/{eqn.source_info.name_stack}"
            if under in here:
                # (``ragged_dot_general``: by its stem)
                yield eqn.primitive.name.removesuffix("_general")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub, here)
    return set(names(jax.make_jaxpr(fn)(*args, **kw).jaxpr))


@pytest.mark.parametrize("form, share", [
    ("sorted", False), ("sorted", True), ("dense", False), ("dense", True),
    ("by_hit", False)])         # (a share never holds both forms)
def test_idle_rows_are_absent_and_busy_rows_get_what_they_got(
        monkeypatch, form, share):
    """``moe_ffn(active=)``: the busy rows' results are the unmasked call's
    under every form, and experts hit (and a share's held assignments) count
    the busy rows alone. A chip's SHARE dispatched dense takes no notice of
    the mask: its counts are every row's, as they were."""
    x, wr, wg, wu, wd, K = routed_layer(12)
    E = wr.shape[1]
    first = {"first": 4} if share else {}
    sl = slice(4, 12) if share else slice(None)
    active = jnp.asarray([1, 0, 1, 1, 0, 0, 0, 1, 0, 0, 1, 0], bool)
    busy = np.flatnonzero(np.asarray(active))
    monkeypatch.setattr(moe, "dispatch_form", lambda *a, **k: "dense")
    want, hit_all, chosen = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K,
                                        **first)
    alone, hit_busy, _ = moe.moe_ffn(x[busy], wr, wg[sl], wu[sl], wd[sl], K,
                                     **first)
    monkeypatch.setattr(moe, "dispatch_form", lambda *a, **k: form)
    stats = {}
    got, hit, ch = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K, **first,
                               active=active, stats=stats)
    np.testing.assert_array_equal(ch, chosen)        # the router's own
    np.testing.assert_allclose(got[busy], want[busy], atol=2e-5)
    np.testing.assert_allclose(got[busy], alone, atol=2e-5)
    heeds = not (share and form == "dense")
    assert jax.tree.map(int, hit) == jax.tree.map(
        int, hit_busy if heeds else hit_all)
    if heeds and not share:
        seen = {int(e) for e in np.asarray(chosen)[busy].reshape(-1)}
        assert int(hit) == len(seen) < int(hit_all) <= E
        # an idle row's result is nothing at all
        assert float(jnp.abs(got[~np.asarray(active)]).max()) == 0.0
    assert int(stats["sorted"]) == (form != "dense")   # 7 of 16 hit: under


@pytest.mark.parametrize("busy, took", [(1, 1), (4, 1), (8, 0), (12, 0)])
def test_the_device_takes_sorted_under_the_crossing_and_dense_over_it(
        busy, took):
    """16 experts, 3 a token, 12 rows: the rule says dense (36 assignments),
    so a decode call holds both forms and goes sorted while its busy rows
    hit fewer than ``sorted_under(16)`` = 12 experts; the branch it took is
    what ``stats["sorted"]`` says, and both branches agree."""
    x, wr, wg, wu, wd, K = routed_layer(12, seed=1)
    assert moe.dispatch_form(12, K, 16, masked=True) == "by_hit"
    assert moe.dispatch_form(12, K, 16) == "dense"
    assert moe.sorted_under(16) == 12 and moe.sorted_under(1) == 1
    active = jnp.arange(12) < busy

    @jax.jit
    def call(x, active):
        stats = {}
        y, hit, _ = moe.moe_ffn(x, wr, wg, wu, wd, K, active=active,
                                stats=stats)
        return y, hit, stats["sorted"]

    y, hit, sorted_ = call(x, active)
    assert int(sorted_) == took == (int(hit) < 12), int(hit)
    assert {"ragged_dot", "cond"} <= primitives(call, x, active)
    for forced in ("sorted", "dense"):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "dispatch_form", lambda *a, **k: forced)
            want, hit_f, _ = moe.moe_ffn(x, wr, wg, wu, wd, K, active=active)
        assert int(hit_f) == int(hit)
        np.testing.assert_allclose(y[:busy], want[:busy], atol=2e-5)
    # without the mask the call is what it was: dense, no branch
    assert not {"ragged_dot", "cond"} & primitives(
        lambda x: moe.moe_ffn(x, wr, wg, wu, wd, K)[0], x)


def check_busy_rows_alone(step, cfg, active, form):
    """``step(stats, active) -> logits [B, 1, V]``: one ``forward_decode``
    call of a routed model over fixed operands. With the mask the busy rows'
    logits are the unmasked call's, ``experts_hit`` / ``held`` are what the
    BUSY rows' chosen experts make them (every row's for a share dispatched
    dense, which takes no notice), and ``sorted`` counts the routed layers
    of a sorted form."""
    every = {"chosen": []}
    want = np.asarray(step(every, None))
    stats = {}
    got = np.asarray(step(stats, active))
    busy = np.asarray(active)
    np.testing.assert_allclose(got[busy], want[busy], atol=2e-5)
    E, R = cfg.num_experts, cfg.router_experts or cfg.num_experts
    first = cfg.expert_first if cfg.router_experts else 0
    counted = busy if moe.heeds_active(form, E / R) else np.ones_like(busy)
    hit = held = 0
    for chosen in every["chosen"]:
        mine = np.asarray(chosen)[counted].reshape(-1) - first
        mine = mine[(mine >= 0) & (mine < E)]
        hit, held = hit + len(set(mine.tolist())), held + len(mine)
    assert int(stats["experts_hit"]) == hit
    if cfg.router_experts:
        assert int(stats["held"]) == held
    assert "sorted" not in every                    # a call without a mask
    assert int(stats["sorted"]) == cfg.routed_layers * (form == "sorted")


def parent_routing(monkeypatch):
    """The decode programs as they were: every row routed, busy or not, and
    dense where the rule says dense."""
    was = moe.dispatch_form
    monkeypatch.setattr(moe, "heeds_active", lambda form, share: False)
    monkeypatch.setattr(
        moe, "dispatch_form",
        lambda *a, masked=False, **k: was(*a, **k))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_busy_lanes_decode_the_same_beside_idle_and_deferred_lanes(
        state, impl, monkeypatch):
    """Five lanes, four requests of different lengths (so 4, 3, 2, 1 lanes
    decode while one slot stays empty), one of them DEFERRED for a few
    dispatches (pool pressure: its row stays in the program, inactive): the
    tokens and log-probabilities are those of an engine whose decode program
    routes every row and dispatches dense (the parent's), both branches of
    the device's choice were taken, and the counters count the busy rows."""
    from dynamo_tpu.engine.cache import OutOfPages

    def serve(core):
        ensure, n = core._ensure_pages, {"decodes": 0}

        def pressed(seq_id, total):
            # "b" finds no pages for its 3rd to 6th decode dispatch
            if seq_id == "b" and core.by_seq["b"].generated >= 1:
                n["decodes"] += 1
                if 3 <= n["decodes"] <= 6:
                    raise OutOfPages("pressed")
            return ensure(seq_id, total)

        core._ensure_pages = pressed
        prompts = {"a": prompt_of(19, 1), "b": prompt_of(9, 2),
                   "c": prompt_of(30, 3), "d": prompt_of(12, 4)}
        for (name, pr), n_out in zip(prompts.items(), (24, 10, 5, 16)):
            core.submit(name, request_of(pr, n_out))
        outs = run(core, list(prompts))
        return {k: ([o.token for o in v], [o.token_logprob for o in v])
                for k, v in outs.items()}

    kw = dict(max_batch=5, prefill_lanes=4, decode_steps=2)
    new = engine(state, impl, **kw)
    assert new.moe_dispatch.startswith("decode:by_hit")
    st = new.stage                               # (one registry a process)
    series = (st.moe_layer_calls, st.moe_sorted_calls, st.moe_experts_hit,
              st.moe_assignments)
    read = lambda: [c._values.get(("decode",), 0.0) for c in series]
    before = read()
    got = serve(new)
    calls, took, hit, assigned = np.subtract(read(), before)
    assert 0 < took < calls                      # both branches ran
    # a busy row hits 2 experts a routed layer, an idle row none: at most
    # the busy rows' assignments (the host's count), fewer where two rows
    # share an expert
    assert 2 * calls <= hit < assigned
    with monkeypatch.context() as mp:
        parent_routing(mp)
        old = engine(state, impl, **kw)
        assert old.moe_dispatch.startswith("decode:dense")
        before = read()
        want = serve(old)
    calls_old, took_old, hit_old, assigned_old = np.subtract(read(), before)
    assert (calls_old, assigned_old, took_old) == (calls, assigned, 0)
    assert hit_old > hit                         # the idle rows' experts too
    for name in want:
        assert got[name][0] == want[name][0], name
        assert np.abs(np.subtract(got[name][1], want[name][1])).max() < TOL


# ---- (i) -----------------------------------------------------------------
@pytest.mark.parametrize("kw, says", [
    ({"host_cache_blocks": 4}, "host / disk KV tiers"),
    ({"spec": "ngram"}, "speculative"),
    ({"tp": 2}, "one chip"),
    ({"ep": 2}, "one chip"),
    ({"pp": 2}, "K/V blocks alone"),
])
def test_what_moves_blocks_refuses_the_model_by_name(kw, says):
    with pytest.raises(ValueError, match=says):
        EngineCore(JaxEngineConfig(**{
            "model": model(), "page_size": 16, "max_batch": 2,
            "max_context": 64, "prefill_chunk": 16, "attn_impl": "xla",
            **kw}))


def test_block_moving_calls_refuse_and_no_block_is_hashed(core):
    for call in (lambda: core.extract_kv("x"),
                 lambda: core.stage_prefetch([1, 2, 3]),
                 lambda: core.prefill_extract("x", None),
                 lambda: core.begin_stream_inject("x", {})):
        with pytest.raises(ValueError,
                           match="gated short-convolution layers"):
            call()
    with pytest.raises(ValueError, match="convolution tail a lane"):
        core._refuse_block_moves("anything that moves blocks")
    from dynamo_tpu.llm.kvpage.programs import PagedPrograms
    assert PagedPrograms.validate(core.cfg) is not None
    # the same prompt twice: nothing is matched, sealed or published
    generate(core, "p1", prompt_of(33, 9), 2)
    hit0 = core.prefix_hit_tokens
    generate(core, "p2", prompt_of(33, 9), 2)
    assert core.prefix_hit_tokens == hit0 == 0


# ---- (j) -----------------------------------------------------------------
def test_every_operation_of_the_bucket_programs_names_a_scope(monkeypatch):
    import re

    from benchmarks.harness import scopes
    from dynamo_tpu.utils import jaxenv
    from tests.test_step_scopes import (NOT_LEAVES, compiled_programs,
                                        loops_own)

    was = {k: getattr(jax.config, k) for k in jaxenv.PROGRAM_LOCATIONS}
    for k, v in jaxenv.PROGRAM_LOCATIONS.items():
        jax.config.update(k, v)
    try:
        texts = compiled_programs(
            llama.LlamaConfig.from_hf_config(TINY), monkeypatch)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
    every = {"embed", "attn_in", "kv_write", "attn", "attn_out", "ssm_in",
             "ssm_out", "ffn", "moe_ffn", "head", "sample"}
    for kind, text in texts.items():
        seen, unscoped = set(), []
        for line in text.splitlines():
            m = re.search(r'op_name="([^"]*)"', line)
            if (not m or NOT_LEAVES.search(line)
                    or not m.group(1).startswith("jit(")):
                continue
            where = scopes.scope_of(m.group(1))
            if where is None:
                if not loops_own(m.group(1)):
                    unscoped.append(line.strip()[:200])
            else:
                assert where in llama.SCOPES, line
                seen.add(where.removeprefix("dynamo."))
        assert not unscoped, (kind, unscoped[:8])
        assert seen == every | {"ssm_step" if kind == "decode"
                                else "ssm_scan"}, (kind, seen)
