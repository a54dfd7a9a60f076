"""Granite-4.0-H-style hybrid language model (Mamba-2 layers whose per-lane
recurrent state lives beside a paged K/V cache for a few no-rotary attention
layers; four multipliers; a tied head) against its ONE float32 reference,
``benchmarks/references/granite_hybrid.py``, at a tiny size, in float32.

(a) the whole-prompt forward, and chunked prefill (a ragged last chunk, two
lanes of different lengths in one dispatch) then multi-step decode through
the engine's own programs, dense path and kernels; (b) the chunk form is the
token form, from a non-zero state, and padding leaves state and convolution
tail untouched; (c) a slot reused serves its next request as if alone, and a
lane a decode dispatch does not serve keeps its state bit for bit; (d) every
control of the reference fails the tolerance; (e) the folded K/V pool reads
and writes what the plain one does; (f) the published config maps, and what
cannot be honoured raises; (g) what moves or re-enters blocks refuses the
model by name; (h) counters, costs and cache kinds.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import granite_hybrid as ref
from dynamo_tpu.engine.cache import cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama
from dynamo_tpu.ops import state as state_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# served log-probability against the reference's, both float32: the chunk
# form sums a chunk's contributions in another order than the reference's
# token-by-token recurrence, over 7 layers (measured: 1e-6, of logits whose
# spread over the vocabulary is 0.09)
TOL = 2e-5
TINY = {
    "model_type": "granitemoehybrid", "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba",
                    "attention", "mamba"],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 1024,
    "normalization_function": "rmsnorm", "num_attention_heads": 4,
    "num_experts_per_tok": 0, "num_hidden_layers": 7,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 128, "tie_word_embeddings": True,
    "vocab_size": 259,
}


def published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
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
    16, both lanes in one dispatch (41 = 16 + 16 + 9, 23 = 16 + 7: ragged
    last chunks, and the long lane prefills on while the short one
    decodes), then 12 and 9 tokens decoded two a dispatch."""
    pa, pb = prompt_of(41), prompt_of(23, 1)
    core.submit("a", request_of(pa, 12))
    core.submit("b", request_of(pb, 9))
    outs = run(core, ["a", "b"])
    return served_of(pa, outs["a"]), served_of(pb, outs["b"])


# ---- (a) -----------------------------------------------------------------
def test_the_whole_prompt_forward_agrees_with_the_reference(state):
    """``llama.forward`` over a whole prompt with padding behind it, from a
    lane whose pools held something else: logits at every position."""
    cfg, params = model(), f32(state["params"])
    n, S, page = 40, 48, 16
    toks = np.zeros(S, np.int32)
    toks[:n] = prompt_of(n, 2)
    glob, st = cache_kinds(cfg)
    ks, vs = glob.pool_shapes(5, page)
    ss, cs = st.state_shapes(3)
    out = llama.forward(
        params, cfg, jnp.asarray(toks)[None], jnp.arange(S)[None],
        jnp.zeros(ks), jnp.zeros(vs), (page + jnp.arange(S))[None], None,
        jnp.arange(S)[None], (jnp.arange(S) < n)[None],
        read_pages=jnp.asarray([[1, 2, 3]]),
        ssm=(jnp.full(ss, 7.0), jnp.full(cs, 5.0), jnp.asarray([1]),
             jnp.asarray([True]), jnp.asarray([n])))
    logits, _, _, s_pool, c_pool = out
    padded = np.zeros(128, np.int32)
    padded[:n] = toks[:n]
    want = np.asarray(ref.tail_logprobs(state, padded, 0, n))
    got = np.asarray(jax.nn.log_softmax(logits[0, :n], -1))
    assert np.abs(got - want).max() < TOL
    # the other lanes of both pools are what they were
    for lane in (0, 2):
        assert float(jnp.abs(s_pool[:, lane] - 7.0).max()) == 0.0
        assert float(jnp.abs(c_pool[:, lane] - 5.0).max()) == 0.0


def test_engine_prefill_and_decode_agree_with_the_reference(core, state,
                                                             served):
    """Every served log-probability is the reference's for that token to
    ``TOL`` and every greedy token is the reference's best, for both lanes,
    through the state pool and the folded K/V pool."""
    for one in served:
        tail, worst = against(state, one)
        assert one[1] == tail.argmax(-1).tolist()
        assert worst < TOL
    assert core.pool.free_pages == core.pool.num_pages - 1


# ---- (b) -----------------------------------------------------------------
def mixer_inputs(cfg, B, T, seed):
    rng = np.random.default_rng(seed)
    Cd, H = cfg.ssm_conv_dim, cfg.ssm_heads
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return (f(B, T, Cd), f(B, T, H),
            f(B, H, cfg.ssm_head_dim, cfg.ssm_state),
            f(B, cfg.ssm_conv - 1, Cd))


def test_the_chunk_form_is_the_token_form_and_padding_is_inert(state):
    """From a NON-ZERO state and convolution tail: 13 real tokens of a
    16-token chunk through ``ssm_chunk`` give, to float32 rounding, the y,
    the state and the tail that 13 ``ssm_step`` calls give; the 3 padded
    positions change nothing; a chunk with no real token, and a step of a
    lane that is not active, leave state and tail BIT FOR BIT."""
    cfg = model()
    mp = f32(state["params"])["stacks"]["mamba"]
    c, d, s0, t0 = mixer_inputs(cfg, 2, 16, 5)
    n = jnp.asarray([13, 0])
    y, s1, t1 = llama.ssm_chunk(c, d, mp, 1, cfg, s0, t0, n)
    s, t = s0[:1], t0[:1]
    on = jnp.asarray([True])
    for i in range(13):
        yi, s, t = llama.ssm_step(c[:1, i], d[:1, i], mp, 1, cfg, s, t, on)
        assert float(jnp.abs(yi - y[:1, i]).max()) < 1e-4
    assert float(jnp.abs(s - s1[:1]).max()) < 1e-4
    assert float(jnp.abs(t - t1[:1]).max()) == 0.0
    # row 1 had no real token
    assert float(jnp.abs(s1[1] - s0[1]).max()) == 0.0
    assert float(jnp.abs(t1[1] - t0[1]).max()) == 0.0
    _, s2, t2 = llama.ssm_step(c[:, 0], d[:, 0], mp, 1, cfg, s0, t0,
                               jnp.asarray([True, False]))
    assert float(jnp.abs(s2[1] - s0[1]).max()) == 0.0
    assert float(jnp.abs(t2[1] - t0[1]).max()) == 0.0
    assert float(jnp.abs(s2[0] - s0[0]).max()) > 0.0


# ---- (b') the state kernel against the token form -------------------------
def kernel_of(active):
    """``forward_decode``'s ``state_kernel`` for a dispatch that serves
    ``active``, through the Pallas interpreter."""
    served = state_ops.served_lanes(jnp.asarray(active))
    return lambda pool, l, *operands: state_ops.state_step(
        pool, l, *served, *operands, interpret=True)


def step_inputs(hf, B, layers, seed):
    """-> (cfg, one layer stack's recurrence parameters, c, d, state pool
    [layers,B,H,P,N], tail) with random contents."""
    cfg = model(hf)
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    H, Cd, K = cfg.ssm_heads, cfg.ssm_conv_dim, cfg.ssm_conv
    lp = {"conv_w": 0.5 * f(layers, K, Cd), "conv_b": 0.5 * f(layers, Cd),
          "dt_bias": f(layers, H), "D": f(layers, H),
          "A_log": jnp.log(1.0 + 15.0 * jnp.abs(f(layers, H)) / 3.0)}
    return (cfg, lp, f(B, Cd), f(B, H),
            f(layers, B, H, cfg.ssm_head_dim, cfg.ssm_state),
            f(B, K - 1, Cd))


WIDE = dict(TINY, mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
            mamba_expand=64)      # the benchmark cell's H / P / N
MASKS = {"none": [False] * 5, "one": [False, False, False, True, False],
         "some": [True, False, True, True, False], "all": [True] * 5,
         "ends": [True, False, False, False, True]}


@pytest.mark.parametrize("hf, B", [(TINY, 5), (WIDE, 3)],
                         ids=["tiny", "cell-widths"])
def test_the_state_kernel_is_the_token_form(hf, B):
    """``ssm_step`` through the kernel against ``ssm_step`` itself, random
    operands, every lane served, layer 1 of 2: the updated state to 1e-6 of
    the state's scale (the same two products and one sum an element, so
    they differ by whether the compiler contracts them: an ulp or two; a
    lane updated twice or not at all is off by the scale itself), y to 1e-5
    of y's scale (the kernel reads it out as a matrix product at HIGHEST: N
    products summed in another order, on the chip from six bfloat16 passes:
    a few sqrt(N) ulps); the other layer of the pool bit for bit."""
    cfg, lp, c, d, pool, tail = step_inputs(hf, B, 2, 7)
    on = jnp.asarray([True] * B)
    y0, s0, t0 = llama.ssm_step(c, d, lp, 1, cfg, pool[1], tail, on)
    y1, p1, t1 = jax.jit(lambda *a: llama.ssm_step(
        *a[:2], lp, 1, cfg, *a[2:], on, kernel_of(on)))(c, d, pool, tail)
    assert p1.shape == pool.shape and p1.dtype == jnp.float32
    assert float(jnp.abs(p1[1] - s0).max()) < 1e-6 * float(jnp.abs(s0).max())
    assert float(jnp.abs(y1 - y0).max()) < 1e-5 * float(jnp.abs(y0).max())
    assert float(jnp.abs(p1[0] - pool[0]).max()) == 0.0
    assert float(jnp.abs(t1 - t0).max()) == 0.0


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_a_lane_the_kernel_does_not_serve_is_not_touched(mask):
    """None, one, some (not contiguous) and all of five lanes served: a
    lane that is not keeps state AND tail bit for bit and returns y == 0;
    a served lane's state, y and tail are the token form's."""
    cfg, lp, c, d, pool, tail = step_inputs(TINY, 5, 3, 11)
    on = np.asarray(MASKS[mask])
    y0, s0, t0 = llama.ssm_step(c, d, lp, 2, cfg, pool[2], tail,
                                jnp.asarray(on))
    y1, p1, t1 = llama.ssm_step(c, d, lp, 2, cfg, pool, tail,
                                jnp.asarray(on), kernel_of(on))
    off = ~on
    assert np.array_equal(np.asarray(p1[2])[off], np.asarray(pool[2])[off])
    assert np.array_equal(np.asarray(t1)[off], np.asarray(tail)[off])
    assert not np.asarray(y1)[off].any()
    assert np.array_equal(np.asarray(p1[:2]), np.asarray(pool[:2]))
    if on.any():
        assert float(jnp.abs(p1[2] - s0)[on].max()) < 1e-6 * float(
            jnp.abs(s0).max())
        assert float(jnp.abs(y1 - y0)[on].max()) < 1e-5 * float(
            jnp.abs(y0).max())
        assert np.array_equal(np.asarray(t1)[on], np.asarray(t0)[on])


@pytest.mark.parametrize("mask", ["one", "some", "ends"])
def test_a_padded_lane_list_updates_no_lane_twice(mask):
    """The kernel's lane list repeats the last served lane in every slot
    behind the served ones: those slots do nothing. Two steps through the
    kernel are two steps through ``ssm_step`` (a lane stepped once more a
    call would be off by a whole decay)."""
    on = np.asarray(MASKS[mask])
    lanes, count = state_ops.served_lanes(jnp.asarray(on))
    last = int(np.flatnonzero(on)[-1])
    assert int(count) == on.sum() < len(on)
    assert np.asarray(lanes).tolist() == (
        np.flatnonzero(on).tolist() + [last] * int((~on).sum()))
    cfg, lp, c, d, pool, tail = step_inputs(TINY, 5, 2, 13)
    s0, t0, t1, act = pool[0], tail, tail, jnp.asarray(on)
    for k in range(2):
        ck, dk = jnp.roll(c, k, 0), jnp.roll(d, k, 0)
        _, s0, t0 = llama.ssm_step(ck, dk, lp, 0, cfg, s0, t0, act)
        _, pool, t1 = llama.ssm_step(ck, dk, lp, 0, cfg, pool, t1, act,
                                     kernel_of(on))
    assert float(jnp.abs(pool[0] - s0).max()) < 2e-6 * float(
        jnp.abs(s0).max())
    assert float(jnp.abs(t1 - t0).max()) == 0.0


def kernels_under(jaxpr, found=None):
    """The name stacks of every ``pallas_call`` of ``jaxpr``, at any depth
    (a scan's body, an inlined jit)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.source_info.name_stack))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    kernels_under(inner, found)
    return found


def test_the_decode_program_takes_the_state_kernel_where_it_says(core):
    """The engine's own decode program under ``pallas`` holds the state
    kernel's call under ``dynamo.ssm_step`` (so the dispatches of (a) and
    (c) ran its body), the dense path's holds none: a silent fall back to
    the ``jax.numpy`` form fails here."""
    B, S, s = core.cfg.max_batch, core.s_buckets[0], core.sampling
    pt = np.zeros((B, S // core.page_size), np.int32)
    flags = np.zeros(B, bool)
    traced = core._decode_fn(S).jitted.trace(
        core.params, np.zeros(B, np.int32), core.k_pool, core.v_pool, pt,
        np.ones(B, np.int32), s.temperature, s.top_p, s.top_k, s.key,
        core.gen_counts, flags, flags, s.freq_pen, s.pres_pen, **core._idx())
    calls = kernels_under(traced.jaxpr)
    state_calls = [c for c in calls if "dynamo.ssm_step" in c]
    taken = llama.state_kernel_taken(core.mesh, core.decode_attn_impl)
    assert taken == (core.attn_impl == "pallas")
    # one call a scan body: the runs of state-space layers between the
    # attention layers (2 + 2 + 1 of the 7 layers)
    assert len(state_calls) == (3 if taken else 0), calls


# ---- (c) -----------------------------------------------------------------
def test_a_reused_slot_serves_its_next_request_as_if_alone(core, state):
    """One slot, three requests one after another: each starts from a zero
    state whatever the last one left (``dyn_ssm_state_resets_total``
    counts each once)."""
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


def test_a_lane_the_dispatch_does_not_serve_keeps_its_state(core, state):
    """While one lane decodes alone, the other two lanes of both pools (an
    empty slot's garbage, planted here) are bit for bit what they were
    after every dispatch; and a lane in the MIDDLE of its prefill keeps the
    state its last chunk left while another lane's decode dispatches run
    in between (its served log-probabilities are still the reference's)."""
    s_before = core.s_pool.at[:, 1:].set(3.0)
    c_before = core.c_pool.at[:, 1:].set(2.0)
    core.s_pool, core.c_pool = s_before, c_before
    prompt = prompt_of(20, 21)
    core.submit("solo", request_of(prompt, 8))
    lane = None
    for _ in range(200):
        outs = core.step()
        if lane is None and "solo" in core.by_seq:
            lane = core.slots.index(core.by_seq["solo"])
            assert lane == 0
        assert float(jnp.abs(core.s_pool[:, 1:] - 3.0).max()) == 0.0
        assert float(jnp.abs(core.c_pool[:, 1:] - 2.0).max()) == 0.0
        if any(o.seq_id == "solo" and o.finish is not None for o in outs):
            break
    else:
        raise AssertionError("did not finish")
    # a long prompt admitted while another lane decodes
    pa, pb = prompt_of(12, 22), prompt_of(61, 23)
    core.submit("dec", request_of(pa, 14))
    early = [so for _ in range(3) for so in core.step()]
    assert early and {so.seq_id for so in early} == {"dec"}
    core.submit("long", request_of(pb, 4))
    outs = run(core, ["dec", "long"], outs={"dec": early})
    for prompt, name in ((pa, "dec"), (pb, "long")):
        got = served_of(prompt, outs[name])
        tail, worst = against(state, got)
        assert worst < TOL, name


# ---- (d) -----------------------------------------------------------------
@pytest.mark.parametrize("variant", [v for v in ref.VARIANTS if v != "full"])
def test_every_control_fails_the_tolerance(state, served, variant,
                                           monkeypatch):
    """The served path against the reference with ONE departure: the last
    layer dropped, int8 weights, the carry dropped at the chunk boundaries
    (every 16 positions here: the engine's chunk), rotary switched on in
    the attention layers, scores x 1 / sqrt(width). Each is told apart at
    the tolerance (a) passes, ten times over and more (the nearest: int8,
    32 times). ``state_bf16`` (the state rounded to bfloat16 once a token)
    is no fault of the program and is only READ: at this size, a state of
    16 x 16 a head, it moves a log-probability by 6e-6, a third of the
    tolerance."""
    monkeypatch.setattr(ref, "CARRY_EVERY", 16)
    state = {k: v for k, v in state.items() if k != "programs"}
    _, worst = against(state, served[0], variant)
    if variant == "state_bf16":
        assert 0 < worst < 10 * TOL, worst
    else:
        assert worst > 10 * TOL, (variant, worst)


def test_a_carry_lost_at_one_chunk_boundary_fails_the_tolerance(state):
    """The program itself with its state pool zeroed between the second and
    the third chunk of a prompt: the served log-probabilities leave the
    reference's by far more than the tolerance."""
    one = engine(state, "xla", max_batch=1, prefill_lanes=1)
    prompt = prompt_of(41, 31)
    one.submit("x", request_of(prompt, 4))
    outs, chunks = [], 0
    for _ in range(200):
        before = sum(one.stage.ssm_tokens._values.values())
        outs += [so for so in one.step() if so.seq_id == "x"]
        if sum(one.stage.ssm_tokens._values.values()) - before >= 16:
            chunks += 1
            if chunks == 2:
                one.s_pool = jnp.zeros_like(one.s_pool)
                one.c_pool = jnp.zeros_like(one.c_pool)
        if outs and outs[-1].finish is not None:
            break
    _, worst = against(state, served_of(prompt, outs))
    assert worst > 10 * TOL


# ---- (e) -----------------------------------------------------------------
def test_the_folded_pool_reads_and_writes_what_the_plain_one_does():
    rng = np.random.default_rng(0)
    L, H, N, page, Dh, f = 2, 2, 5, 16, 16, 8
    plain = jnp.asarray(rng.normal(size=(L, H, N, page, Dh)), jnp.float32)
    folded = plain.reshape(L, H, N, page // f, f * Dh)
    n = 24   # neighbours in a row, rows written twice, two pages
    w_page = jnp.asarray(rng.integers(1, 3, n))
    w_off = jnp.asarray(rng.integers(0, page, n))
    # (slots written twice get the same row, as a program's padding does)
    rows = jnp.asarray(rng.normal(size=(n, H, Dh)), jnp.float32)
    _, first = np.unique(np.asarray(w_page * page + w_off),
                         return_inverse=True)
    rows = rows[jnp.asarray(
        [int(np.flatnonzero(first == g)[0]) for g in first])]
    a = llama.kv_write(plain, 1, w_page, w_off, rows)
    b = llama.kv_write(folded, 1, w_page, w_off, rows)
    assert float(jnp.abs(a.reshape(b.shape) - b).max()) == 0.0
    pages = jnp.asarray([[1, 2, 0], [4, 0, 0]])
    assert float(jnp.abs(llama.kv_pages(a, 1, pages)
                         - llama.kv_pages(b, 1, pages, f)).max()) == 0.0
    r_page, r_off = jnp.asarray([[1, 2], [2, 4]]), jnp.asarray([[3, 9],
                                                               [0, 15]])
    assert float(jnp.abs(llama.kv_rows(a, 1, r_page, r_off)
                         - llama.kv_rows(b, 1, r_page, r_off, f)).max()
                 ) == 0.0
    from dynamo_tpu.ops.attention import paged_attention
    q = jnp.asarray(rng.normal(size=(2, 4, Dh)), jnp.float32)
    lengths = jnp.asarray([37, 9])
    want = paged_attention(q, a, a, pages, lengths, 1, interpret=True)
    got = paged_attention(q, b, b, pages, lengths, 1, interpret=True, fold=f)
    assert float(jnp.abs(want - got).max()) == 0.0


# ---- (f) -----------------------------------------------------------------
def test_the_published_config_maps():
    m = llama.LlamaConfig.from_hf_config(published())
    assert (m.num_layers, m.hidden_size, m.vocab_size) == (40, 2048, 100352)
    assert m.state_layers == tuple(l for l in range(40)
                                   if l not in (5, 15, 25, 35))
    assert m.kind_layers(False) == (5, 15, 25, 35)
    assert llama._segments(m) == [(0, 5), (5, 1), (6, 9), (15, 1), (16, 9),
                                  (25, 1), (26, 9), (35, 1), (36, 4)]
    assert (m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_conv) == (
        64, 64, 128, 4)
    assert (m.ssm_inner, m.ssm_conv_dim) == (4096, 4352)
    assert (m.num_heads, m.num_kv_heads, m.head_dim) == (32, 8, 64)
    assert m.intermediate_size == 8192 and not m.num_experts
    assert not m.use_rope and m.tie_embeddings and m.kv_fold == 2
    assert (m.attn_scale, m.embed_multiplier, m.residual_multiplier,
            m.logits_scaling) == (0.015625, 12.0, 0.22, 8.0)
    glob, st = cache_kinds(m)
    assert (glob.name, glob.layers, glob.token_bytes(2)) == ("global", 4,
                                                              8192)
    assert glob.pool_shapes(9, 64) == ((4, 8, 9, 32, 128),) * 2
    assert (st.name, st.lane_bytes(2)) == (
        "state", 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2))
    shapes = jax.eval_shape(lambda: llama.init_params(
        m, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 3.191e9) < 2e6


def test_a_damped_model_adds_and_scores_in_float32():
    """Served in bfloat16, a model with ``residual_multiplier`` keeps its
    residual stream in float32 and one with ``logits_scaling`` takes its
    logits from the float32 accumulator (``LlamaConfig.stream_dtype``,
    ``_lm_head``): the matrices still see bfloat16 activations, and every
    other model's programs are what they were."""
    m = llama.LlamaConfig.from_hf_config(TINY)
    assert m.dtype == jnp.bfloat16 and m.stream_dtype == jnp.float32
    assert llama.preset("tiny-byte").stream_dtype == jnp.bfloat16
    params = llama.init_params(m, jax.random.PRNGKey(0))
    x = llama._embed(params, m, jnp.zeros((1, 3), jnp.int32))
    assert x.dtype == jnp.float32
    h = llama._normed(x, params["final_norm"], m)
    assert h.dtype == jnp.bfloat16
    branch = jnp.ones_like(h)
    assert llama._residual(x, branch, m).dtype == jnp.float32
    text = jax.jit(lambda x: llama._lm_head(x, params, m)).lower(x).as_text()
    dots = [l for l in text.splitlines() if "dot_general" in l]
    assert dots and all("-> tensor<1x3x259xf32>" in l for l in dots)


def test_absent_multipliers_mean_one():
    m = llama.preset("tiny-byte")
    assert (m.attn_multiplier, m.embed_multiplier, m.residual_multiplier,
            m.logits_scaling) == (None,) * 4
    assert m.use_rope and m.kv_fold == 1 and not m.has_state


@pytest.mark.parametrize("change, says", [
    ({"layer_types": ["mamba", "sliding_attention"] + ["mamba"] * 5},
     "'mamba' or 'attention'"),
    ({"position_embedding_type": "alibi"}, "position_embedding_type"),
    ({"num_local_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": True}, "beside a shared feed-forward"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"mamba_d_head": 8}, "mamba_expand x hidden_size"),
    ({"normalization_function": "layernorm"}, "normalization_function"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_dt_rank": 4}, "does not implement"),
])
def test_what_cannot_be_honoured_raises(change, says):
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config({**TINY, **change})


# ---- (g) -----------------------------------------------------------------
@pytest.mark.parametrize("kw, says", [
    ({"host_cache_blocks": 4}, "host / disk KV tiers"),
    ({"spec": "ngram"}, "speculative"),
    ({"tp": 2}, "one chip"),
    ({"ep": 2}, "one chip"),
    ({"pp": 2}, "K/V blocks alone"),
    ({"sp": 2, "attn_impl": "ring"}, "sp > 1 / ring"),
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
                 lambda: core.inject_prefilled("x", None, None, None, 0, 0.0),
                 lambda: core.begin_stream_inject("x", {})):
        with pytest.raises(ValueError, match="state-space layers"):
            call()
    m = core.cfg.model
    with pytest.raises(ValueError, match="K/V blocks alone"):
        llama.forward_pp(core.params, m, jnp.zeros((1, 1, 1), jnp.int32),
                         *[None] * 7, mesh=None)
    with pytest.raises(ValueError, match="K/V blocks alone"):
        llama.forward_decode(core.params, m, jnp.zeros(3, jnp.int32),
                             core.k_pool, core.v_pool,
                             jnp.zeros((3, 6), jnp.int32),
                             jnp.ones(3, jnp.int32))
    from dynamo_tpu.llm.kvpage.programs import PagedPrograms
    assert "state-space layers" in PagedPrograms.validate(core.cfg)
    # the same prompt twice: nothing is matched, sealed or published
    generate(core, "p1", prompt_of(33, 9), 2)
    hit0 = core.prefix_hit_tokens
    generate(core, "p2", prompt_of(33, 9), 2)
    assert core.prefix_hit_tokens == hit0 == 0


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
    # three chunks of one row each; a decode dispatch advances 3 lanes x 2
    # steps of which this request's lane is one
    assert moved["dyn_ssm_lane_steps_total", "prefill"] == 3
    assert moved["dyn_ssm_active_lane_steps_total", "prefill"] == 3
    assert moved["dyn_ssm_lane_steps_total", "decode"] == d * 3 * 2
    assert moved["dyn_ssm_active_lane_steps_total", "decode"] == d * 2 == n
    # (the gauge is the process's: the engine built last set it)
    assert sum(st.ssm_state_bytes._values.values()) > 0
    assert (core.cache_kinds[1].lane_bytes(4) * core.cfg.max_batch
            == core.s_pool.nbytes + core.c_pool.nbytes)


def test_costs_and_cache_kinds(core):
    from dynamo_tpu.utils import roofline

    m = core.cfg.model
    assert [k.name for k in core.cache_kinds] == ["global", "state"]
    assert [k.label() for k in core.cache_kinds] == [
        "global:2x2x(16+16)", "state:5x(8x16x16f32+480)"]
    # a block of the K/V cache: 2 attention layers x 2 heads x 2 x 16 x 4 B
    assert llama.kv_block_bytes(m, 16) == 16 * 2 * 2 * 32 * 4
    lane = 5 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    assert core.cache_kinds[1].lane_bytes(4) == lane
    costs = roofline.model_costs(m, weight_bytes=1.0)
    assert costs.window_groups == ((None, 2),)
    assert costs.state_bytes_per_lane == lane
    _, by, _ = roofline.decode_cost(costs, [50, 20], 2)
    kv = 2 * 2 * 32 * 4
    assert by == 2 * 1.0 + (50 + 51 + 20 + 21) * kv + 4 * kv + 2 * 2 * lane
