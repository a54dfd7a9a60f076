"""LongCat-Flash-style language model (a layer of TWO latent-attention
sublayers with a dense feed-forward each and a routed branch that leaves
after the first and lands after the second; a softmax router over routed AND
identity experts, a selection bias that chooses and never weighs, gates x 6
and not renormalised; the two constant scales of its latent attention; this
chip's share of the routed experts) against its ONE float32 reference,
``benchmarks/references/longcat_flash.py``, which expands K and V per head
as published, at a tiny size in float32.

(a) chunked prefill then decode through the latent cache: the engine's own
programs, dense path and kernels, a prompt that crosses a chunk boundary, and
``llama.forward`` at three chunk sizes; (b) every broken variant of the
reference (the branch dropped, the identity part dropped, gates renormalised,
either latent scale moved or dropped, the branch landing after the FIRST
sublayer, ...) fails the same tolerance; (c) the published config maps and
every key of the family that cannot be honoured raises; (d) the router law
case by case; (e) the shares of the routed experts, the identity part counted
once, add up to the uncut layer; (f) the cache keeps two rows a published
layer; (g) counters and costs; (h) the dispatch rule at the benchmark's
geometry; (i) what moves blocks refuses the model.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import longcat_flash as ref
from dynamo_tpu.engine.cache import cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.models import llama, moe
# the engine fixture, the token loop and the chunks-then-decode walk through
# the latent cache are the sibling latent model's, letter for letter
from tests.test_deepseek_v2 import (engine, f32, generate, prompt_of,
                                    through_the_cache)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # served log-probability against the reference's, float32
TINY = {
    "attention_bias": False, "vocab_size": 259, "hidden_size": 64,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "max_position_embeddings": 1024, "rms_norm_eps": 1e-5,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 8,
    "zero_expert_type": "identity", "moe_topk": 4,
    # this chip: routed experts 4-7 of the deployment's 16; the router is
    # 16 + 8 identity experts = 24 wide
    "expert_shard": {"router_experts": 16, "first_expert": 4},
}
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "longcat-flash-omni-4l.json")


def published():
    """The catalog row's ``config`` as the benchmark's file holds it (the
    three keys the file reduces put back, the share taken off)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    for k in ("benchmark", "expert_shard"):
        cfg.pop(k)
    cfg.update(num_layers=28, n_routed_experts=512, vocab_size=131072)
    return cfg


@pytest.fixture(scope="module")
def state():
    return ref.build(TINY, 3)


@pytest.fixture(scope="module", params=["xla", "pallas"])
def core(request, state):
    return engine(TINY, state, request.param)


@pytest.fixture(scope="module")
def served(core):
    """41 prompt tokens in chunks of 16 (three dispatches, the last one
    partial: the prompt crosses two chunk boundaries), 12 tokens decoded two
    a dispatch: (tokens of the whole sequence, served tokens, their served
    log-probabilities)."""
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
    ``TOL`` and every greedy token is the reference's best: two sublayers a
    layer through the latent cache (two rows a token a layer), the branch
    carried from the first to the second, against the published form."""
    tail, worst = against(state, served)
    assert served[1] == tail.argmax(-1).tolist()
    assert worst < TOL
    assert core.pool.free_pages == core.pool.num_pages - 1


@pytest.fixture(scope="module")
def whole(state):
    toks = np.asarray(prompt_of(40, 11), np.int32)
    _, want = ref.trace(state, toks)
    return toks, np.asarray(want)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunks_then_decode_are_the_published_layer(state, whole, chunk):
    """However the prompt is cut into chunks, every position's whole
    distribution is the reference's full forward."""
    toks, want = whole
    cfg = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    got = through_the_cache(cfg, f32(state["params"]), toks, chunk)
    assert np.abs(got - want).max() < TOL


def test_the_latent_kernels_are_the_dense_path(state, whole):
    """The flash kernel's absorbed form and the paged kernel (it writes the
    step's two rows itself) in the interpreter, over the scaled compressed
    rows, against the reference, as the dense path is."""
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
    """The served path against the reference with ONE departure (the routed
    branch dropped, the identity part dropped, renormalised gates, a scaling
    factor of 1, gates that carry the bias, the branch landing after the
    FIRST sublayer, a latent scale dropped, the q scale on the nope half
    alone, the kv scale on the rotary key too, the probe's dropped layer and
    int8 weights): each is told apart at the tolerance (a) passes, twenty
    times over."""
    _, worst = against(state, served, variant)
    assert worst > 20 * TOL, (variant, worst)


# ---- (c) -----------------------------------------------------------------
def test_the_published_config_maps():
    """The catalog row's config, uncut, passes ``from_hf_config``
    (construction only), and says what the issue says of it."""
    m = llama.LlamaConfig.from_hf_config(published())
    # two program layers a published layer: mixers, cache rows, dense FFNs
    assert (m.num_layers, m.num_heads, m.num_kv_heads) == (56, 64, 1)
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_dim, m.qk_rope_dim,
            m.head_dim, m.v_dim, m.rotary_dim) == (1536, 512, 128, 64, 192,
                                                   128, 64)
    assert abs(m.attn_scale - 192 ** -0.5) < 1e-9
    assert (m.latent_q_scale, m.latent_kv_scale) == (2.0, math.sqrt(12.0))
    assert m.shortcut_moe and m.ffn_kinds == (0,) * 56
    assert [m.layer_branch(l) for l in range(4)] == [True, False] * 2
    assert m.routed_layers == 28
    assert (m.num_experts, m.zero_experts, m.router_width,
            m.experts_per_token, m.expert_width, m.intermediate_size,
            m.shared_experts) == (512, 256, 768, 12, 2048, 12288, 0)
    assert (m.router, m.routed_scaling, m.router_groups) == (
        "softmax_bias", 6.0, None)
    assert m.router_experts is None and m.has_latent and m.per_kind
    assert m.rope_theta == 1e7 and m.rope_scaling is None
    assert m.stream_dtype == jnp.float32 and not m.tie_embeddings
    kind, = cache_kinds(m)
    assert kind.token_bytes(2) == 56 * 1152
    # the benchmark's file: 16 of the 512 held, four published layers
    with open(CONFIG) as f:
        cut = json.load(f)
    cut.pop("benchmark")
    c = llama.LlamaConfig.from_hf_config(cut)
    assert (c.num_layers, c.num_experts, c.router_experts, c.expert_first,
            c.router_width, c.vocab_size) == (8, 16, 512, 0, 768, 16384)
    assert llama.kv_block_bytes(c, 64) == 64 * 8 * 1152


@pytest.mark.parametrize("change, says", [
    ({"zero_expert_type": "copy"}, "zero_expert_type 'copy'"),
    ({"zero_expert_type": "absent"}, "zero_expert_type None"),
    ({"n_routed_experts": "absent"}, "zero_expert_num without n_routed"),
    ({"attention_method": "MHA"}, "attention_method 'MHA'"),
    ({"kv_lora_rank": "absent"}, "MLA without kv_lora_rank"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 10}}, "rope_scaling"),
    ({"num_layers": "absent"}, "num_layers"),
    ({"num_hidden_layers": 4}, "num_hidden_layers"),
    ({"ffn_hidden_size": "absent"}, "ffn_hidden_size"),
    ({"expert_ffn_hidden_size": "absent"}, "expert_ffn_hidden_size"),
    ({"moe_topk": "absent"}, "moe_topk"),
    ({"model_type": "deepseek_v2"}, "under model_type 'deepseek_v2'"),
    ({"router_bias": True}, "router_bias true"),
    ({"norm_topk_prob": True}, "norm_topk_prob true"),
    ({"attention_bias": True}, "attention_bias true"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"index_topk": 2048}, "index_topk"),
    ({"mtp_num_layers": 3}, "mtp_num_layers"),
    ({"ngram_vocab_size_ratio": 78}, "ngram_vocab_size_ratio"),
    ({"moe_switch_token_num": 1024}, "moe_switch_token_num"),
    ({"q_lora_rank": None}, "full-rank q"),
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


@pytest.mark.parametrize("key, field", [
    ("mla_scale_q_lora", "latent_q_scale"),
    ("mla_scale_kv_lora", "latent_kv_scale")])
def test_a_latent_scale_that_is_false_is_honoured(state, whole, key, field):
    """``mla_scale_*`` false is mapped to NO scale (not ignored): the same
    weights then serve other logits, and the config says so."""
    m = llama.LlamaConfig.from_hf_config({**TINY, key: False},
                                         dtype=jnp.float32)
    assert getattr(m, field) is None
    on = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    assert getattr(on, field) == math.sqrt(64 / (16 if "q_" in key else 32))
    toks, want = whole
    got = through_the_cache(m, f32(state["params"]), toks)
    assert np.abs(got - want).max() > 20 * TOL


def test_the_family_keys_mean_nothing_elsewhere():
    """A latent-attention config of another family that carries one of this
    family's keys is refused, not served without it."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v2-5l.json")) as f:
        other = json.load(f)
    other.pop("benchmark")
    with pytest.raises(ValueError, match="mla_scale_q_lora"):
        llama.LlamaConfig.from_hf_config({**other, "mla_scale_q_lora": True})


# ---- (d) -----------------------------------------------------------------
def _router(seed, rows=6, D=32, R=12, Z=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (1, rows, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, R + Z), jnp.float32) / np.sqrt(D) * 2
    bias = 0.02 * jax.random.normal(ks[2], (R + Z,), jnp.float32)
    return x, wr, bias


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_router_law_is_the_plain_loop(seed):
    """softmax over ALL outputs in float32; the K largest of score + bias
    are chosen; the gates are the chosen SCORES x 6: the bias chooses and
    never weighs, nothing is renormalised."""
    x, wr, bias = _router(seed)
    K = 4
    vals, idx = moe.route_topk(x, wr, K, "softmax_bias", bias, scaling=6.0)
    z = np.asarray(x[0] @ wr, np.float64)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    flipped = 0
    for t in range(z.shape[0]):
        want = np.argsort(-(p[t] + np.asarray(bias)))[:K]
        assert set(want) == set(np.asarray(idx[0, t]).tolist())
        np.testing.assert_allclose(np.sort(np.asarray(vals[0, t])),
                                   np.sort(6.0 * p[t, want]), rtol=1e-5)
        flipped += set(want) != set(np.argsort(-p[t])[:K])
    assert flipped                      # the bias does choose
    assert float(jnp.sum(vals, -1).max()) < 6.0     # and nothing sums to 1
    # the reference's router is the same law
    dims = {"K": K, "scaling": 6.0}
    gates, chosen, _ = ref.route(x[0], wr, bias, dims, ref.HOW, 0.0)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(idx[0], -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), np.asarray(idx[0]), -1),
        vals[0], rtol=1e-5)


def _experts(seed, E, D=32, F=16):
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
              for k in ks[:2])
    return wg, wu, jax.random.normal(ks[2], (E, F, D), jnp.float32) / np.sqrt(F)


@pytest.mark.parametrize("case", ["identity", "scaled", "not_renormalised",
                                  "idle_lane"])
def test_identity_experts_case_by_case(case):
    """ids >= the routed experts are identity experts: never dispatched,
    their gates' sum x the input is added; x 6; not renormalised; an idle
    lane's identity part is masked under ``active``."""
    R, Z, K = 12, 6, 4
    x, wr, bias = _router(7, rows=1)
    x = jnp.tile(x, (3, 1, 1)) * jnp.arange(1, 4)[:, None, None]  # 3 lanes
    wg, wu, wd = _experts(7, R)
    law = dict(router="softmax_bias", bias=bias, scaling=6.0, zero=Z)
    out, (hit, held), idx = moe.moe_ffn(x, wr, wg, wu, wd, K, **law)
    vals, _ = moe.route_topk(x, wr, K, "softmax_bias", bias, scaling=6.0)
    ident = np.asarray(idx) >= R
    assert ident.any() and not ident.all()
    zgate = np.where(ident, np.asarray(vals), 0.0).sum(-1, keepdims=True)
    routed, _, _ = moe.moe_ffn(x, wr, wg, wu, wd, K, first=0,
                               **{**law, "zero": 0})
    if case == "identity":
        # the identity part is exactly gate x input, on top of the routed
        # experts' sum, and no identity assignment counts as held
        np.testing.assert_allclose(out - routed, zgate * np.asarray(x),
                                   atol=1e-5)
        assert int(held) == int((~ident).sum())
        stats = {}
        moe.moe_ffn(x, wr, wg, wu, wd, K, stats=stats, **law)
        assert int(stats["zero"]) == int(ident.sum())
    elif case == "scaled":
        one, _, _ = moe.moe_ffn(x, wr, wg, wu, wd, K, **{**law,
                                                         "scaling": 1.0})
        np.testing.assert_allclose(out, 6.0 * np.asarray(one), atol=1e-5)
    elif case == "not_renormalised":
        p = jax.nn.softmax(x @ wr, -1)
        chosen = np.take_along_axis(np.asarray(p), np.asarray(idx), -1)
        np.testing.assert_allclose(vals, 6.0 * chosen, rtol=1e-5)
        assert not np.allclose(np.asarray(vals).sum(-1), 6.0, atol=0.5)
    else:
        active = jnp.asarray([True, False, True])
        stats = {}
        got, _, _ = moe.moe_ffn(x, wr, wg, wu, wd, K, active=active,
                                stats=stats, **law)
        np.testing.assert_allclose(got[0], out[0], atol=1e-5)
        np.testing.assert_allclose(got[2], out[2], atol=1e-5)
        # the idle lane: no identity part (its routed part is what the
        # dispatch form makes it: a share dispatched dense routes every row)
        assert not moe.heeds_active(moe.dispatch_form(3, K, R, R / (R + Z)),
                                    R / (R + Z))
        np.testing.assert_allclose(got[1], routed[1], atol=1e-5)
        assert np.abs(np.asarray(out[1] - routed[1])).max() > 0.1
        assert int(stats["zero"]) == int(ident[[0, 2]].sum())


# ---- (e) -----------------------------------------------------------------
@pytest.mark.parametrize("rows", [2, 40])       # dense / sorted dispatch
def test_shares_of_the_experts_add_up_to_the_whole_layer(rows):
    """16 routed experts in 4 shares of 4 beside 8 identity experts: every
    share routes over all 24 outputs, computes its own experts' part and the
    WHOLE identity part; the four routed parts, the identity part counted
    once, add up to the uncut layer, which is the reference's ``MoE(h)``."""
    D, R, Z, K = 32, 16, 8, 4
    x, wr, bias = _router(rows, rows=rows, D=D, R=R, Z=Z)
    wg, wu, wd = _experts(rows, R, D)
    law = dict(router="softmax_bias", bias=bias, scaling=6.0, zero=Z)
    whole, _, chosen = moe.moe_ffn(x, wr, wg, wu, wd, K, **law)
    every, _, _ = moe.moe_ffn(x, wr, wg[:0], wu[:0], wd[:0], K, first=0,
                              **law)            # no expert held: identity
    forms = set()
    parts, n_held = 0.0, 0
    for first in range(0, R, 4):
        sl = slice(first, first + 4)
        forms.add(moe.dispatch_form(rows, K, 4, 4 / (R + Z)))
        y, (hit, held), ch = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K,
                                         first=first, **law)
        np.testing.assert_array_equal(ch, chosen)
        assert 0 <= int(hit) <= 4
        parts, n_held = parts + (y - every), n_held + int(held)
    assert forms == {"dense" if rows == 2 else "sorted"}
    n_zero = int((np.asarray(chosen) >= R).sum())
    assert n_zero and n_held + n_zero == rows * K
    np.testing.assert_allclose(parts + every, whole, atol=2e-5)
    dims = {"K": K, "scaling": 6.0, "first": 0, "E": R, "R": R}
    br = {"wr": wr, "rbias": bias, "wg": wg, "wu": wu, "wd": wd}
    want, _, _ = ref.moe(x[0], br, dims, ref.HOW, 0.0)
    np.testing.assert_allclose(whole[0], want, atol=2e-5)


# ---- (h) -----------------------------------------------------------------
def test_the_dispatch_rule_at_the_benchmarks_geometry():
    """16 held of the router's 768 outputs, 12 a token: a row gives the
    held experts 0.25 assignments, so a call is sorted while it has fewer
    than 64 rows (a decode step of up to 32 lanes, a 32-row chunk) and dense
    from there (chunks of 64 to 512 rows)."""
    share = 16 / 768
    assert moe.sorted_wins(32, 12, 16, share)
    assert not moe.sorted_wins(64, 12, 16, share)
    assert not moe.sorted_wins(512, 12, 16, share)
    assert moe.dispatch_form(32, 12, 16, share, masked=True) == "sorted"
    assert moe.heeds_active("sorted", share)


# ---- (f) -----------------------------------------------------------------
def test_the_cache_keeps_two_rows_a_published_layer(core):
    m = llama.LlamaConfig.from_hf_config(published())
    kind, = cache_kinds(m)
    assert (kind.name, kind.latent, kind.kv_heads, kind.k_dim, kind.v_dim,
            kind.k_store, kind.fold, kind.window) == (
        "global", True, 1, 64, 512, 128, 1, None)
    # 28 published layers keep 56 rows a token: 576 numbers each, 640 as
    # stored (the key's lane tile)
    assert kind.layers == 56
    assert kind.token_bytes(2) // kind.layers == 1152
    assert kind.token_bytes(2, stored=True) // kind.layers == 1280
    assert kind.label() == "global:56x(latent 512+rope 64)"
    tiny, = core.cache_kinds
    assert core.k_pool.shape == (4, 1, core.pool.num_pages, 8, 128)
    assert core.v_pool.shape == (4, 1, core.pool.num_pages, 8, 32)
    assert tiny.label() == "global:4x(latent 32+rope 8)"


# ---- (i) -----------------------------------------------------------------
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


# ---- (g) -----------------------------------------------------------------
def test_counters_say_what_the_dispatches_did(core):
    st = core.stage
    series = (st.moe_assignments, st.moe_routed_assignments,
              st.moe_zero_assignments, st.moe_experts_hit,
              st.moe_layer_calls, st.engine_dispatch_tokens,
              st.attn_latent_keys, st.attn_latent_pairs)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    # K = 4 choices a token in each of the 2 PUBLISHED layers (not the 4
    # sublayers): all of them, identity experts' included
    assert moved["dyn_moe_routed_assignments_total", "prefill"] == 37 * 4 * 2
    assert moved["dyn_moe_routed_assignments_total", "decode"] == n * 4 * 2
    for kind in ("prefill", "decode"):
        held = moved["dyn_moe_assignments_total", kind]
        zero = moved["dyn_moe_zero_assignments_total", kind]
        routed = moved["dyn_moe_routed_assignments_total", kind]
        # 4 of the 24 outputs are held here, 8 are identity experts
        assert 0 < held < routed and 0 < zero < routed
        assert held + zero <= routed
        assert 0.15 < zero / routed < 0.55
    # one routed call a PUBLISHED layer and step
    assert moved["dyn_moe_layer_calls_total", "decode"] == 2 * n
    # the latent counters are ONE sublayer's worth: chunks of 16, 16 and 5
    # each read their lane's rows once; a query at position p sees p + 1
    assert moved["dyn_attn_latent_keys_total", "prefill"] == 16 + 32 + 37
    assert moved["dyn_attn_latent_pairs_total", "prefill"] == 37 * 38 // 2
    want = sum(p + 1 for p in range(37, 37 + n))
    assert moved["dyn_attn_latent_keys_total", "decode"] == want
    assert moved["dyn_attn_latent_pairs_total", "decode"] == want


def test_the_zero_counter_is_the_references_share(state, served):
    """What the program counts as identity assignments is what the
    reference's router chose: ids >= the deployment's routed experts."""
    tokens, _, _ = served
    chosen, _ = ref.trace(state, tokens)
    cfg = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    stats = {"chosen": []}
    kind, = cache_kinds(cfg)
    T = 56
    toks = np.zeros(T, np.int32)
    toks[:len(tokens)] = tokens
    pos = np.arange(T, dtype=np.int32)[None]
    pages = np.arange(1, 8, dtype=np.int32)
    k_pool, v_pool = (jnp.zeros(s, jnp.float32)
                      for s in kind.pool_shapes(8, 8))
    llama.forward(f32(state["params"]), cfg, jnp.asarray(toks[None]),
                  jnp.asarray(pos), k_pool, v_pool,
                  jnp.asarray(pages[pos // 8] * 8 + pos % 8), None,
                  jnp.asarray(pos), jnp.asarray(pos < T),
                  read_pages=jnp.asarray(pages[None]), stats=stats)
    n = len(tokens)
    assert len(stats["chosen"]) == 2            # one a PUBLISHED layer
    for l, ch in enumerate(stats["chosen"]):
        np.testing.assert_array_equal(np.sort(ch[0, :n], -1),
                                      np.sort(chosen[l], -1))
    assert int(stats["zero"]) == int(
        sum((np.asarray(ch) >= 16).sum() for ch in stats["chosen"]))


def test_costs_price_two_sublayers_and_the_branch(core):
    """``model_costs`` of the published model: two latent projections and
    two dense feed-forwards a published layer, the router over 768 outputs
    and a token's share of its 12 choices once."""
    from dynamo_tpu.utils.roofline import model_costs
    m = llama.LlamaConfig.from_hf_config(published())
    c = model_costs(m)
    D, Fe, F = 6144, 2048, 12288
    proj = (D * 1536 + 1536 * 64 * 192 + D * 576 + 512 * 64 * 256
            + 64 * 128 * D)
    assert proj == 90570752                     # 90.6 M a sublayer
    layer = 2 * proj + 2 * 3 * D * F + D * 768
    assert layer == 638844928                   # 638.8 M outside the experts
    active = 12 * (512 / 768 * 3 * D * Fe + 256 / 768 * D)
    assert c.mat_flops_per_token == pytest.approx(2.0 * 28 * (layer + active))
    weights = 28 * (layer + 512 * 3 * D * Fe) + 2 * 131072 * D
    assert c.weight_bytes == pytest.approx(2.0 * weights)
    assert 559e9 < weights < 562e9              # the published 560 B
    assert c.num_layers == 56
