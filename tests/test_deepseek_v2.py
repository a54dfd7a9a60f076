"""DeepSeek-V2-style language model (latent attention: low-rank q, ONE
compressed row a token for all heads with one shared rotary key, YaRN
rotary; a leading dense layer; group-limited softmax routing without
renormalisation, scaled, beside a shared expert; this chip's share of the
experts) against its ONE float32 reference,
``benchmarks/references/deepseek_v2.py``, which expands K and V per head as
published, at a tiny size in float32.

(a) chunked prefill then decode through the latent cache: the engine's own
programs, dense path and kernels, and ``llama.forward`` at three chunk sizes;
(b) every broken variant of the reference fails the same tolerance; (c) the
published config maps and each key that cannot be honoured raises; (d)
group-limited routing against a plain loop; (e) YaRN frequencies and the
softmax scale against a transcription; (f) the shares of the experts, the
shared expert counted once, add up to the uncut layer; (g) the latent
kernels in the interpreter against the dense path; (h) the cache kind's
bytes and pool shapes; (i) a sealed latent block re-entered by prefix match
gives the same logits; (j) what moves blocks refuses the latent kind by
name; (k) counters and costs; (l) the float32 stream and logits.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import deepseek_v2 as ref
from dynamo_tpu.engine.cache import cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # served log-probability against the reference's, float32
TINY = {
    "model_type": "deepseek_v2", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": False, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "topk_method": "group_limited_greedy",
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "vocab_size": 259, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "attention_bias": False,
    "hidden_act": "silu", "seq_aux": True,
    # contexts of 41-53 lie beyond the original 16: YaRN's frequencies bind
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    # this chip: experts 4-7 of the router's 16 (group 1 of 4)
    "expert_shard": {"router_experts": 16, "first_expert": 4},
}


def published():
    """The catalog row's ``config`` as the benchmark's file holds it (the
    three keys the file reduces put back, the share taken off)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v2-5l.json")) as f:
        cfg = json.load(f)
    cfg.pop("benchmark")
    cfg.pop("expert_shard")
    cfg.update(num_hidden_layers=60, n_routed_experts=160, vocab_size=102400)
    return cfg


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def engine(hf, state, impl, **kw):
    args = dict(page_size=8, max_batch=2, max_context=64, prefill_chunk=16,
                decode_steps=2)
    args.update(kw)
    c = EngineCore(JaxEngineConfig(
        model=llama.LlamaConfig.from_hf_config(hf, dtype=jnp.float32),
        attn_impl=impl, **args))
    c.params = f32(state["params"])    # the reference's tensors, as float32
    return c


@pytest.fixture(scope="module")
def state():
    return ref.build(TINY, 3)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def core(request, state):
    return engine(TINY, state, request.param)


def generate(core, seq_id, prompt, n):
    core.submit(seq_id, BackendInput(token_ids=list(prompt),
                                     stop=StopConditions(max_tokens=n)))
    outs = []
    for _ in range(600):
        outs += [so for so in core.step() if so.seq_id == seq_id]
        if outs and outs[-1].finish is not None:
            assert outs[-1].error is None, outs[-1].error
            return outs
    raise AssertionError("did not finish")


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 259, n).tolist()


@pytest.fixture(scope="module")
def served(core):
    """41 prompt tokens in chunks of 16 (three dispatches, the last one
    partial), 12 tokens decoded two a dispatch: (tokens of the whole
    sequence, served tokens, their served log-probabilities)."""
    prompt = prompt_of(41)
    outs = generate(core, "a", prompt, 12)
    toks = [o.token for o in outs]
    return (np.asarray(prompt + toks[:-1], np.int32), toks,
            np.asarray([o.token_logprob for o in outs]))


def against(state, served, variant="full"):
    tokens, toks, logp = served
    _, ref_logp = ref.trace(state, tokens, variant)
    tail = np.asarray(ref_logp[len(tokens) - len(toks):])
    return tail, np.abs(logp - tail[np.arange(len(toks)), toks]).max()


# ---- (a) -----------------------------------------------------------------
def test_engine_prefill_and_decode_agree_with_the_reference(core, state,
                                                             served):
    """Every served log-probability is the reference's for that token to
    ``TOL`` and every greedy token is the reference's best: the absorbed
    form, chunks and decode steps, through the latent cache, against K and V
    expanded per head."""
    tail, worst = against(state, served)
    assert served[1] == tail.argmax(-1).tolist()
    assert worst < TOL
    assert core.pool.free_pages == core.pool.num_pages - 1


def through_the_cache(cfg, params, toks, C=16, impl="xla", flash="xla"):
    """The first 32 of ``toks`` through ``llama.forward`` in chunks of ``C``,
    the rest through ``llama.forward_decode``: log-softmax at every
    position."""
    kind, = cache_kinds(cfg)
    page, n_pages, S = 8, 16, 64
    k_pool, v_pool = (jnp.zeros(s, jnp.float32)
                      for s in kind.pool_shapes(n_pages, page))
    pages = np.arange(1, 9, dtype=np.int32)
    got = []
    for start in range(0, 32, C):
        pos = np.arange(start, start + C, dtype=np.int32)[None]
        rpos = np.arange(S, dtype=np.int32)[None]
        logits, k_pool, v_pool = llama.forward(
            params, cfg, jnp.asarray(toks[start:start + C][None]),
            jnp.asarray(pos), k_pool, v_pool,
            jnp.asarray(pages[pos // page] * page + pos % page), None,
            jnp.asarray(rpos), jnp.asarray(rpos < start + C),
            read_pages=jnp.asarray(pages[None]), attn_impl=flash)
        got.append(np.asarray(jax.nn.log_softmax(logits[0], -1)))
    for t in range(32, len(toks)):
        logits, k_pool, v_pool = llama.forward_decode(
            params, cfg, jnp.asarray(toks[t:t + 1]), k_pool, v_pool,
            jnp.asarray(pages[None]), jnp.asarray([t + 1], jnp.int32),
            attn_impl=impl)
        got.append(np.asarray(jax.nn.log_softmax(logits[0], -1)))
    return np.concatenate(got)


@pytest.fixture(scope="module")
def whole(state):
    toks = np.asarray(prompt_of(40, 11), np.int32)
    _, want = ref.trace(state, toks)
    return toks, np.asarray(want)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunks_then_decode_are_the_published_attention(state, whole, chunk):
    """However the prompt is cut into chunks (each attends to the cached
    rows of those before it), every position's whole distribution is the
    reference's full forward."""
    toks, want = whole
    cfg = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    got = through_the_cache(cfg, f32(state["params"]), toks, chunk)
    assert np.abs(got - want).max() < TOL


# ---- (g) -----------------------------------------------------------------
def test_the_latent_kernels_are_the_dense_path(state, whole):
    """The flash kernel's absorbed form (every (token, head) a query row
    against the one shared row a key) and the paged kernel (it writes the
    step's two rows itself) in the interpreter, against the reference, as
    the dense path is."""
    toks, want = whole
    cfg = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    kind, = cache_kinds(cfg)
    assert llama.kernel_writes(None, "pallas", kind.k_store, kind.fold)
    got = through_the_cache(cfg, f32(state["params"]), toks,
                            impl="pallas", flash="flash")
    assert np.abs(got - want).max() < TOL


# ---- (b) -----------------------------------------------------------------
BROKEN = [v for v in ref.VARIANTS if v != "full"]


@pytest.mark.parametrize("variant", BROKEN)
def test_every_broken_variant_fails_the_tolerance(state, served, variant):
    """The served path against the reference with ONE departure (no shared
    expert, top-K without groups, renormalised gates, a scaling factor of
    1, the rotary term of the scores off, rotary without YaRN, the probe's
    dropped layer and int8 weights): each is told apart at the tolerance
    (a) passes, twenty times over."""
    _, worst = against(state, served, variant)
    assert worst > 20 * TOL, (variant, worst)


# ---- (c) -----------------------------------------------------------------
def test_the_published_config_maps():
    """DeepSeek-V2's ``config.json``, uncut, passes ``from_hf_config``
    (construction only), and says what the issue says of it."""
    m = llama.LlamaConfig.from_hf_config(published())
    assert (m.num_layers, m.num_heads, m.num_kv_heads) == (60, 128, 1)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_dim, m.qk_rope_dim,
            m.head_dim, m.v_dim, m.rotary_dim) == (1536, 512, 128, 64, 192,
                                                   128, 64)
    assert abs(m.attn_scale - 0.114721) < 1e-6
    assert m.ffn_kinds == (0,) + (1,) * 59 and m.layer_kinds == (0,) * 60
    assert (m.num_experts, m.experts_per_token, m.expert_width,
            m.shared_experts) == (160, 6, 1536, 2)
    assert (m.router, m.router_groups, m.routed_scaling) == (
        "softmax_group", (8, 3), 16.0)
    assert m.router_experts is None and m.has_latent and m.per_kind
    kind, = cache_kinds(m)
    assert kind.token_bytes(2) == 60 * 1152
    # the benchmark's file: 40 of the 160 held, five layers
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v2-5l.json")) as f:
        cut = json.load(f)
    cut.pop("benchmark")
    c = llama.LlamaConfig.from_hf_config(cut)
    assert (c.num_layers, c.num_experts, c.router_experts, c.expert_first,
            c.vocab_size) == (5, 40, 160, 0, 25600)
    assert llama.kv_block_bytes(c, 64) == 64 * 5 * 1152


@pytest.mark.parametrize("change, says", [
    ({"q_lora_rank": None}, "full-rank q"),
    ({"kv_lora_rank": None}, "without kv_lora_rank"),
    ({"qk_nope_head_dim": None}, "qk_nope_head_dim"),
    ({"qk_rope_head_dim": None}, "qk_rope_head_dim"),
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim 7"),
    ({"v_head_dim": "absent"}, "without v_head_dim"),
    ({"num_key_value_heads": 2}, "num_key_value_heads 2"),
    ({"attention_bias": True}, "attention_bias"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"sliding_window": 8}, "sliding_window"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "rope_scaling"),
    ({"rope_scaling": {**TINY["rope_scaling"], "extra": 1}}, "extra"),
    ({"rope_scaling": {**TINY["rope_scaling"], "mscale": 1.0}},
     "scales the rotary tables"),
    ({"rope_scaling": {**TINY["rope_scaling"], "mscale_all_dim": 0}},
     "scales the rotary tables"),
    ({"topk_method": "noaux_tc"}, "topk_method 'noaux_tc'"),
    ({"topk_method": "greedy"}, "norm_topk_prob False"),
    ({"topk_method": "grouped_something"}, "grouped_something"),
    ({"scoring_func": "sigmoid"}, "scoring_func 'sigmoid'"),
    ({"norm_topk_prob": True}, "norm_topk_prob True"),
    ({"n_group": 3}, "n_group that divides"),
    ({"n_group": None}, "n_group that divides"),
    ({"topk_group": 5}, "topk_group"),
    ({"topk_group": 1, "num_experts_per_tok": 6}, "fewer experts"),
    ({"n_shared_experts": 2, "moe_intermediate_size": None},
     "n_shared_experts 2"),
    ({"moe_layer_freq": 0}, "no routed layer"),
    ({"first_k_dense_replace": 3}, "no routed layer"),
    ({"moe_layer_freq": None}, "without moe_layer_freq"),
    ({"moe_layer_freq": [0, 1]}, "moe_layer_freq must list"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"n_routed_experts_per_group": 4}, "n_routed_experts_per_group"),
    ({"expert_shard": {"router_experts": 16, "first_expert": 14}},
     "are not among"),
])
def test_what_cannot_be_honoured_raises(change, says):
    cfg = {**TINY, **change}
    for k, v in change.items():
        if v == "absent":
            del cfg[k]
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config(cfg)


def test_yarn_without_latent_attention_raises():
    dense = {k: v for k, v in TINY.items()
             if k not in llama._LATENT_KEYS and "expert" not in k
             and k not in ("n_group", "topk_group", "topk_method",
                           "scoring_func", "norm_topk_prob",
                           "routed_scaling_factor", "moe_layer_freq",
                           "first_k_dense_replace", "moe_intermediate_size",
                           "v_head_dim")}
    with pytest.raises(ValueError, match="yarn"):
        llama.LlamaConfig.from_hf_config(dense)
    dense.pop("rope_scaling")
    assert not llama.LlamaConfig.from_hf_config(dense).has_latent


# ---- (d) -----------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_is_the_plain_loop(seed):
    """softmax over all; a group scores as its best expert; the best groups
    stay; the top-K inside them; gates = 16 x score, not renormalised."""
    D, R, G, Gk, K, T = 32, 24, 6, 2, 5, 9
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (1, T, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, R), jnp.float32) / np.sqrt(D) * 2
    vals, idx = moe.route_topk(x, wr, K, "softmax_group", groups=(G, Gk),
                               scaling=16.0)
    z = np.asarray(x[0] @ wr, np.float64)
    s = np.exp(z - z.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    for t in range(T):
        best = [s[t, g * 4:(g + 1) * 4].max() for g in range(G)]
        stay = sorted(range(G), key=lambda g: -best[g])[:Gk]
        among = [e for g in stay for e in range(g * 4, (g + 1) * 4)]
        want = sorted(among, key=lambda e: -s[t, e])[:K]
        assert list(np.asarray(idx[0, t])) == want
        np.testing.assert_allclose(vals[0, t], 16.0 * s[t, want], rtol=1e-5)
    # the reference's router says the same
    dims = {"K": K, "groups": G, "topk_group": Gk, "scaling": 16.0}
    gates, ridx, _ = ref.route(x[0], wr, dims, ref.HOW, 0.0)
    np.testing.assert_array_equal(ridx, idx[0])
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), np.asarray(idx[0]), -1),
        vals[0], rtol=1e-5)


# ---- (e) -----------------------------------------------------------------
def test_yarn_frequencies_and_scale_are_the_published_ones():
    """``_yarn_find_correction_range`` / ``_yarn_linear_ramp_mask`` /
    ``yarn_get_mscale`` of the source's modeling file, transcribed, at the
    published numbers."""
    m = llama.LlamaConfig.from_hf_config(published())
    dim, base, factor, orig = 64, 10000.0, 40.0, 4096

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(correction_dim(32)), 0)
    high = min(math.ceil(correction_dim(1)), dim - 1)
    assert (low, high) == (10, 23)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    want = inter * (1 - mask) + extra * mask
    np.testing.assert_allclose(llama._rope_inv_freq(m), want, rtol=1e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(ref.hf_dims(published())), want, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(m.attn_scale - 192 ** -0.5 * mscale * mscale) < 1e-12
    assert round(m.attn_scale, 6) == 0.114721
    assert llama.yarn_mscale(1.0, 0.707) == 1.0


# ---- (f) -----------------------------------------------------------------
@pytest.mark.parametrize("rows", [2, 16])       # sorted / dense dispatch
def test_shares_of_the_experts_add_up_to_the_whole_layer(rows):
    """16 experts in 4 shares of 4 (a group each): every share routes over
    all 16 in their 4 groups, computes its own experts' part and the WHOLE
    shared expert; the four parts, the shared expert counted once, add up
    to the uncut layer, which is the reference's."""
    D, F, E, K, G, Gk = 32, 16, 16, 3, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(rows), 8)
    x = jax.random.normal(ks[0], (1, rows, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, E), jnp.float32) / np.sqrt(D) * 2
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
              for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) / np.sqrt(F)
    shared = (jax.random.normal(ks[5], (D, 2 * F), jnp.float32) / np.sqrt(D),
              jax.random.normal(ks[6], (D, 2 * F), jnp.float32) / np.sqrt(D),
              jax.random.normal(ks[7], (2 * F, D), jnp.float32)
              / np.sqrt(2 * F))
    law = dict(router="softmax_group", groups=(G, Gk), scaling=16.0)
    whole, _, chosen = moe.moe_ffn(x, wr, wg, wu, wd, K, shared=shared,
                                   **law)
    every, _, _ = moe.moe_ffn(x, wr, wg[:0], wu[:0], wd[:0], K, first=0,
                              shared=shared, **law)      # no expert held
    parts, n_held = 0.0, 0
    for first in range(0, E, 4):
        sl = slice(first, first + 4)
        y, (hit, held), ch = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K,
                                         first=first, shared=shared, **law)
        np.testing.assert_array_equal(ch, chosen)
        assert 0 <= int(hit) <= 4
        parts, n_held = parts + (y - every), n_held + int(held)
    assert n_held == rows * K
    np.testing.assert_allclose(parts + every, whole, atol=2e-5)
    dims = {"K": K, "groups": G, "topk_group": Gk, "scaling": 16.0}
    gates, _, _ = ref.route(x[0], wr, dims, ref.HOW, 0.0)
    act = (jax.nn.silu(jnp.einsum("td,edf->tef", x[0], wg))
           * jnp.einsum("td,edf->tef", x[0], wu))
    want = jnp.einsum("tef,efd,te->td", act, wd, gates) + (
        jax.nn.silu(x[0] @ shared[0]) * (x[0] @ shared[1])) @ shared[2]
    np.testing.assert_allclose(whole[0], want, atol=2e-5)


def test_the_dispatch_rule_at_the_benchmarks_geometry():
    """40 held of 160, 6 a token: sorted while a call's expected
    assignments to held experts are fewer than the experts held (a decode
    step of 16 lanes: 24), dense from there (a chunk of 256 rows: 384)."""
    assert moe.sorted_wins(16, 6, 40, 0.25)
    assert not moe.sorted_wins(32, 6, 40, 0.25)
    assert not moe.sorted_wins(256, 6, 40, 0.25)


# ---- (h) -----------------------------------------------------------------
def test_the_cache_kind_says_the_row(core):
    m = llama.LlamaConfig.from_hf_config(published())
    kind, = cache_kinds(m)
    assert (kind.name, kind.latent, kind.kv_heads, kind.k_dim, kind.v_dim,
            kind.k_store, kind.fold, kind.window) == (
        "global", True, 1, 64, 512, 128, 1, None)
    # 576 numbers a token a layer; 640 as stored (the key's lane tile)
    assert kind.token_bytes(2) // kind.layers == 1152
    assert kind.token_bytes(2, stored=True) // kind.layers == 1280
    # the published K and V of 128 heads would be 81,920 B
    assert 2 * 128 * (192 + 128) == 81920
    assert kind.pool_shapes(100, 64) == ((60, 1, 100, 64, 128),
                                         (60, 1, 100, 64, 512))
    assert kind.label() == "global:60x(latent 512+rope 64)"
    tiny, = core.cache_kinds
    assert core.k_pool.shape == (3, 1, core.pool.num_pages, 8, 128)
    assert core.v_pool.shape == (3, 1, core.pool.num_pages, 8, 32)
    assert tiny.label() == "global:3x(latent 32+rope 8)"


# ---- (i) -----------------------------------------------------------------
def test_a_sealed_latent_block_re_entered_gives_the_same_logits(core):
    """The same prompt twice: the second adopts the first's sealed pages
    (per token, hashed like any K/V page) and serves the same tokens and
    log-probabilities."""
    prompt = prompt_of(37, 21)
    first = generate(core, "p1", prompt, 6)
    hit0 = core.prefix_hit_tokens
    again = generate(core, "p2", prompt, 6)
    assert core.prefix_hit_tokens - hit0 == 32          # four pages of 8
    assert [o.token for o in again] == [o.token for o in first]
    np.testing.assert_allclose([o.token_logprob for o in again],
                               [o.token_logprob for o in first], atol=1e-5)


# ---- (j) -----------------------------------------------------------------
@pytest.mark.parametrize("kw, says", [
    ({"host_cache_blocks": 4}, "latent cache kind"),
    ({"spec": "ngram"}, "latent cache kind"),
    ({"tp": 2}, "latent attention runs on one chip"),
    ({"ep": 2}, "latent attention runs on one chip"),
    ({"pp": 2}, "latent"),
])
def test_what_moves_blocks_refuses_the_model_by_name(kw, says):
    model = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    with pytest.raises(ValueError, match=says):
        EngineCore(JaxEngineConfig(model=model, page_size=8, max_batch=2,
                                   max_context=64, prefill_chunk=16,
                                   attn_impl="xla", **kw))


def test_block_moving_calls_refuse_the_latent_kind(core):
    with pytest.raises(ValueError, match="latent cache kind"):
        core._refuse_block_moves("a test")
    for call in (lambda: core.extract_kv("x"),
                 lambda: core.stage_prefetch([1, 2, 3]),
                 lambda: core.prefill_extract("x", None),
                 lambda: core.inject_prefilled("x", None, None, None, 0, 0.0),
                 lambda: core.begin_stream_inject("x", {})):
        with pytest.raises(ValueError, match="latent cache kind"):
            call()
    with pytest.raises(ValueError, match="one compressed row"):
        llama.forward_pp(core.params, core.cfg.model,
                         jnp.zeros((1, 1, 1), jnp.int32), *[None] * 7,
                         mesh=None)
    from dynamo_tpu.llm.kvpage.programs import PagedPrograms
    assert "latent attention" in PagedPrograms.validate(core.cfg)


# ---- (k) -----------------------------------------------------------------
def test_counters_say_what_the_dispatches_did(core):
    st = core.stage
    series = (st.moe_assignments, st.moe_routed_assignments,
              st.moe_experts_hit, st.engine_dispatch_tokens,
              st.attn_latent_keys, st.attn_latent_pairs)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    # K = 3 experts a token in each of the 2 ROUTED layers (not the 3)
    assert moved["dyn_moe_routed_assignments_total", "prefill"] == 37 * 3 * 2
    assert moved["dyn_moe_routed_assignments_total", "decode"] == n * 3 * 2
    for kind in ("prefill", "decode"):
        held = moved["dyn_moe_assignments_total", kind]
        routed = moved["dyn_moe_routed_assignments_total", kind]
        assert 0 < held < routed        # a group of four of the sixteen
    # chunks of 16, 16 and 5: each reads its lane's rows once; a query at
    # position p sees p + 1 keys
    assert moved["dyn_attn_latent_keys_total", "prefill"] == 16 + 32 + 37
    assert moved["dyn_attn_latent_pairs_total", "prefill"] == 37 * 38 // 2
    # decode queries at positions 37 .. 37 + n - 1 each read their visible
    # rows (the first served token came from the last chunk)
    want = sum(p + 1 for p in range(37, 37 + n))
    assert moved["dyn_attn_latent_keys_total", "decode"] == want
    assert moved["dyn_attn_latent_pairs_total", "decode"] == want


@pytest.mark.parametrize("p0,n,S,want", [
    # 128 heads: 8 tokens a query block, 4 of them a 32-token chunk; key
    # blocks of 512, and a second lane of the program that serves nothing
    # (one copy: it holds block 0 from its first step to its last)
    (0, 32, 2048, (4 * 4 * 2, 1 + 1)),        # block 0 alone, copied once
    # positions 1000..1031: the first three query blocks end before 1024
    # and see blocks 0-1, the last sees 0-2; copied again for each
    (1000, 32, 2048, (4 * 4 * 2, 3 * 2 + 3 + 1)),
    (2016, 32, 2048, (4 * 4 * 2, 4 * 4 + 1)),  # the bucket's end: all four
    # 20 tokens: three query blocks hold one, the fourth is padding (block 0)
    (2016, 20, 2048, (4 * 4 * 2, 3 * 4 + 1 + 1)),
])
def test_key_blocks_of_the_latent_flash_call_on_the_host(core, p0, n, S,
                                                         want):
    """What a chunk dispatch counts, at the lane's start, mid-bucket and at
    the bucket's end: the grid of ONE latent flash call and the key blocks
    it copies of it, by the call's own table on the arrays the dispatch
    hands its program (``_prefill_enqueue``: unused queries and the slots
    past the chunk's end at position 0, those slots invalid)."""
    q_pos = np.zeros((2, 32), np.int32)
    k_pos = np.zeros((2, S), np.int32)
    k_valid = np.zeros((2, S), bool)
    q_pos[0, :n] = np.arange(p0, p0 + n)
    k_pos[0, :p0 + n] = np.arange(p0 + n)
    k_valid[0, :p0 + n] = True
    assert core._latent_key_blocks(q_pos, k_pos, k_valid, 128) == want


def test_chunk_dispatches_count_their_key_blocks(core):
    """Through the counter, as fetched chunk dispatches count it, and only
    where chunks run the flash kernel (the tiny model's 4 heads and contexts
    under 128: every chunk one query block against one key block)."""
    series = core.stage.attn_latent_key_blocks
    was = dict(series._values)
    generate(core, "blk", prompt_of(37, 7), 2)
    moved = {k: v - was.get(k, 0.0) for k, v in series._values.items()}
    if core.attn_impl == "pallas":
        # chunks of 16, 16 and 5
        assert moved == {("prefill", "bucket"): 3.0, ("prefill", "copied"): 3.0}
    else:
        assert not series._values


def test_costs_price_the_latent_row_and_the_shared_expert(core):
    from dynamo_tpu.utils import roofline

    m = llama.LlamaConfig.from_hf_config(published())
    c = roofline.model_costs(m)
    assert c.kv_bytes_per_tok_layer == 1152.0
    assert c.attn_flops_coef == 2.0 * 128 * (512 + 64 + 512)
    # the five matrices and wo (the issue's 149,227,520 less the two inner
    # norms' 2,048 weights)
    attn = 149_225_472
    dense = attn + 3 * 5120 * 12288
    routed = attn + 160 * 23_592_960 + 47_185_920 + 819_200
    assert c.weight_bytes == 2.0 * (dense + 59 * routed
                                    + 2 * 102400 * 5120)
    # a token: the attention's matrices, the router, the shared expert and
    # its 6 assignments (the whole model holds every expert)
    per_tok = (attn * 60 + 3 * 5120 * 12288
               + 59 * (819_200 + 47_185_920 + 6 * 23_592_960))
    assert c.mat_flops_per_token == 2.0 * per_tok
    assert llama.kv_block_bytes(core.cfg.model, 8) == 8 * 3 * (32 + 8) * 4


# ---- (l) -----------------------------------------------------------------
def test_a_group_routed_model_adds_and_scores_in_float32():
    """Served in bfloat16, a model with group-limited routing keeps its
    residual stream in float32 and takes its logits from the float32
    accumulator (``LlamaConfig.stream_dtype``: what the stream loses reaches
    the router and flips near-ties); the matrices still see bfloat16
    activations."""
    m = llama.LlamaConfig.from_hf_config(TINY)
    assert m.dtype == jnp.bfloat16 and m.stream_dtype == jnp.float32
    params = llama.init_params(m, jax.random.PRNGKey(0))
    x = llama._embed(params, m, jnp.zeros((1, 3), jnp.int32))
    assert x.dtype == jnp.float32
    h = llama._normed(x, params["final_norm"], m)
    assert h.dtype == jnp.bfloat16
    assert llama._residual(x, jnp.ones_like(h), m).dtype == jnp.float32
    text = jax.jit(lambda x: llama._lm_head(x, params, m)).lower(x).as_text()
    dots = [l for l in text.splitlines() if "dot_general" in l]
    assert dots and all("-> tensor<1x3x259xf32>" in l for l in dots)


# ---- a decode step routes its busy rows alone ------------------------------
@pytest.mark.parametrize("rows, form", [(16, "sorted"), (2, "dense")])
def test_a_decode_step_routes_its_busy_rows_alone(state, rows, form):
    """4 held of 16, 3 a token: a 16-row decode step dispatches its share
    SORTED (as the benchmark's 16-lane program does, 40 held of 160) and
    then reads and counts the busy rows' held assignments alone, which is
    what the host's routed count beside it counts; a 2-row step is dense and
    takes no notice of the mask (ROADMAP ``held-experts-hit``)."""
    from tests.test_lfm2_moe import check_busy_rows_alone, primitives

    cfg = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    params = f32(state["params"])
    assert moe.dispatch_form(rows, 3, 4, 0.25, masked=True) == form
    assert moe.dispatch_form(16, 6, 40, 0.25, masked=True) == "sorted"
    kind, = cache_kinds(cfg)
    P, page = 2, 8
    k_pool, v_pool = (jnp.zeros(s, jnp.float32)
                      for s in kind.pool_shapes(rows * P + 1, page))
    tables = jnp.arange(1, rows * P + 1, dtype=jnp.int32).reshape(rows, P)
    tokens = jnp.asarray(prompt_of(rows, 3), jnp.int32)

    def step(stats, active):
        return llama.forward_decode(
            params, cfg, tokens, k_pool, v_pool, tables,
            jnp.ones(rows, jnp.int32), stats=stats, active=active)[0]

    active = jnp.arange(rows) % 3 != 1
    check_busy_rows_alone(step, cfg, active, form)
    assert "cond" not in primitives(lambda: step({}, active))
    assert ("ragged_dot" in primitives(lambda: step({}, active))) == (
        form == "sorted")


def test_a_sorted_share_counts_its_busy_lanes_held_assignments(state):
    """A 16-lane engine serving ONE request: the decode program's ``held``
    is the busy lane's own (no idle row's), so the host takes it as it is,
    and ``dyn_moe_assignments_total`` lies inside the routed count whatever
    the idle lanes' stale tokens would have been routed to."""
    core = engine(TINY, state, "xla", max_batch=16)
    assert core.moe_dispatch.startswith("decode:sorted")
    assert core._decode_routes_busy and core._decode_cols == (
        "experts_hit", "held")
    st = core.stage
    series = (st.moe_assignments, st.moe_routed_assignments,
              st.moe_experts_hit, st.moe_layer_calls, st.moe_sorted_calls)
    read = lambda: [c._values.get(("decode",), 0.0) for c in series]
    before = read()
    generate(core, "one", prompt_of(21, 5), 9)
    held, routed, hit, calls, took = np.subtract(read(), before)
    assert held == int(held) and 0 <= held <= routed     # a count, unscaled
    assert hit <= held                       # one row: an expert an assignment
    assert took == calls > 0                 # every call sorted
