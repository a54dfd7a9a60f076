"""Command A+-style language model (``model_type cohere2_moe``: a PARALLEL
block on one mean-centred LayerNorm, interleaved rotary in the window layers
and none in the full ones, two caches, sigmoid-routed experts chosen among
all beside shared experts that are AVERAGED, of which this chip holds a
share) against its ONE float32 reference,
``benchmarks/references/command_a_plus.py``, at a tiny size where the window
binds (8 keys of contexts of 41-53), in float32.

(a) chunked prefill then decode through both page pools and the engine's
own programs, dense path and kernels, on the stored tree and the published
one; (b) every broken variant of the reference fails the same tolerance;
(c) the shares of the experts add up to the whole layer, the shared experts
counted once; (d) the router law case by case; (e) a window page given back
DURING prefill is never read again; (f) the family's keys map or raise, one
by one; (g) the two rotary pairings agree under the column permutation; (h)
the counters.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import command_a_plus as ref
from dynamo_tpu.engine.cache import WindowPages, cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # served log-probability against the reference's, float32
TINY = {
    "model_type": "cohere2_moe", "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "norm_topk_prob": True,
    "expert_selection_fn": "sigmoid",
    "shared_expert_combination_strategy": "average",
    "first_k_dense_replace": 0, "prefix_dense_intermediate_size": 128,
    "prefix_dense_sliding_window_pattern": 1,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "layer_switch": 4, "order_of_interleaved_layers": "local_attn_first",
    "sliding_window": 8, "rope_theta": 50000,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "layer_norm_eps": 1e-5, "rms_norm_eps": None, "logit_scale": 1,
    "vocab_size": 259, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "attention_bias": False, "hidden_act": "silu",
    "max_position_embeddings": 1024, "tf_legacy_loss": False,
    # this chip: experts 2-5 of the router's 8
    "expert_shard": {"router_experts": 8, "first_expert": 2},
}


def published():
    """The catalog row's ``config`` as the benchmark's file holds it (the
    three keys the file reduces put back, the share taken off)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "command-a-plus-4l.json")) as f:
        cfg = json.load(f)
    for k in ("benchmark", "expert_shard"):
        cfg.pop(k)
    assert len(cfg["layer_types"]) == 32       # kept whole, as published
    cfg.update(num_hidden_layers=32, num_experts=128, vocab_size=262144)
    return cfg


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def engine(hf, state, impl, stored=True, **kw):
    args = dict(page_size=8, max_batch=2, max_context=64, prefill_chunk=16,
                decode_steps=2)
    args.update(kw)
    model = llama.LlamaConfig.from_hf_config(hf, dtype=jnp.float32)
    c = EngineCore(JaxEngineConfig(model=model, attn_impl=impl, **args))
    # the reference's tensors, as float32: as an engine stores them, or as
    # published (the programs then rotate interleaved themselves)
    c.params = f32(state["params"])
    if stored:
        c.params = llama.stored_params(c.params, cfg=model)
    return c


@pytest.fixture(scope="module")
def state():
    return ref.build(TINY, 3)


@pytest.fixture(scope="module", params=["xla", "pallas", "xla-published"])
def core(request, state):
    impl, _, form = request.param.partition("-")
    return engine(TINY, state, impl, stored=not form)


def generate(core, seq_id, prompt, n):
    core.submit(seq_id, BackendInput(token_ids=list(prompt),
                                     stop=StopConditions(max_tokens=n)))
    outs = []
    for _ in range(900):
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
    partial; five windows long), 12 tokens decoded two a dispatch: (tokens
    of the whole sequence, served tokens, their served log-probabilities)."""
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
    ``TOL`` and every greedy token is the reference's best, through both
    page pools; the window pool gave pages back on the way, the first of
    them while the prompt was still being prefilled."""
    tail, worst = against(state, served)
    assert served[1] == tail.argmax(-1).tolist()
    assert worst < TOL
    assert core.win.released_total >= 4
    released = core.stage.kv_window_pages_released._values
    assert released[("prefill",)] >= 2 and released[("decode",)] >= 1
    assert core.win.pages_in_use == 0          # all back at the end
    assert core.pool.free_pages == core.pool.num_pages - 1


def test_the_layer_has_one_norm_and_the_stored_tree_says_its_pairing(core):
    stacks = core.params[llama.STACKS]
    assert "ln2" not in stacks["routed"] and "dense" not in stacks
    assert set(stacks["routed"]) == {"wr", "wg", "wu", "wd", "ws_g", "ws_u",
                                     "ws_d"}
    stored = isinstance(stacks["window"]["wk"], tuple)
    assert (llama.ROPE_HALVES in stacks["window"]) == stored
    assert llama.ROPE_HALVES not in stacks["full"]     # no rotary there
    m = core.cfg.model
    assert m.stream_dtype == jnp.float32 and m.layer_norm and m.nope_full
    once = llama.stored_params(core.params, cfg=m)
    assert llama.ROPE_HALVES in once[llama.STACKS]["window"]
    again = llama.stored_params(once, cfg=m)     # stored already: as it is
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(once)))


# ---- (b) -----------------------------------------------------------------
BROKEN = [v for v in ref.VARIANTS if v != "full"]


@pytest.mark.parametrize("variant", BROKEN)
def test_every_broken_variant_fails_the_tolerance(state, served, variant):
    """The served path against the reference with ONE departure (a
    sequential block: the feed-forward reads LN(x + a), or norms its input a
    second time; RMSNorm for LayerNorm; rotary in the full layers too, or in
    none; rotate-half pairing on the published columns; the shared experts
    summed; softmax scores; gates not renormalised; a window a key shorter
    or longer; the probe's dropped layer and int8 weights): each is told
    apart at the tolerance (a) passes, twenty times over and more."""
    _, worst = against(state, served, variant)
    assert worst > 20 * TOL, (variant, worst)


# ---- (c) -----------------------------------------------------------------
@pytest.mark.parametrize("rows", [2, 16])       # dense / sorted dispatch
def test_shares_add_up_with_the_shared_experts_counted_once(rows):
    """8 experts in 8 shares of 1: each share routes over all 8, computes
    its own expert's part with gates normalised over all chosen; the eight
    parts plus the AVERAGED shared experts, counted ONCE, are the uncut
    reference's whole feed-forward branch (router, eight experts, four
    shared experts each on its own, summed and divided by four)."""
    D, F, E, K, S = 32, 16, 8, 3, 4
    ks = jax.random.split(jax.random.PRNGKey(rows), 8)
    x = jax.random.normal(ks[0], (1, rows, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, E), jnp.float32) / np.sqrt(D)
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
              for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) / np.sqrt(F)
    sg, su = (jax.random.normal(k, (D, S * F), jnp.float32) / np.sqrt(D)
              for k in ks[5:7])
    sd = jax.random.normal(ks[7], (S * F, D), jnp.float32) / np.sqrt(F)
    parts, n_held = 0.0, 0
    for first in range(E):
        sl = slice(first, first + 1)
        y, (hit, held), ch = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K,
                                         first=first, router="sigmoid")
        parts, n_held = parts + y, n_held + int(held)
    assert n_held == rows * K
    mean = moe.shared_ffn(x, sg, su, sd, scale=1.0 / S)
    gates, idx, _ = ref.route(x[0], wr, K, "sigmoid", 0.0)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ch[0], -1))
    whole = sum(gates[:, e, None] * ref.swiglu(x[0], wg[e], wu[e], wd[e])
                for e in range(E))
    whole = whole + sum(
        ref.swiglu(x[0], sg[:, j * F:(j + 1) * F], su[:, j * F:(j + 1) * F],
                   sd[j * F:(j + 1) * F]) for j in range(S)) / S
    np.testing.assert_allclose((parts + mean)[0], whole, atol=2e-5)
    # ... and one share with the shared experts counted eight times is not
    assert np.abs((parts + 8 * mean)[0] - whole).max() > 0.1


# ---- (d) -----------------------------------------------------------------
def _router(rows=6, E=8, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (2, rows // 2, D), jnp.float32),
            jax.random.normal(ks[1], (D, E), jnp.float32))


@pytest.mark.parametrize("case", ["sigmoid-not-softmax", "among-all",
                                  "renormalised", "idle-lane-masked"])
def test_the_router_law_case_by_case(case):
    x, wr = _router()
    K = 3
    vals, idx = moe.route_topk(x, wr, K, router="sigmoid")
    scores = jax.nn.sigmoid(jnp.einsum("btd,de->bte", x, wr))
    if case == "sigmoid-not-softmax":
        # the gates are the chosen SIGMOID scores over their sum, which the
        # softmax law's are not
        want = jnp.take_along_axis(scores, idx, -1)
        np.testing.assert_allclose(vals, want / want.sum(-1, keepdims=True),
                                   rtol=1e-6)
        soft, _ = moe.route_topk(x, wr, K, router="softmax")
        assert np.abs(np.asarray(soft) - np.asarray(vals)).max() > 1e-3
        # ... and the law WITH a selection bias is the same arithmetic
        same, same_idx = moe.route_topk(x, wr, K, router="sigmoid_bias")
        np.testing.assert_array_equal(same, vals)
        np.testing.assert_array_equal(same_idx, idx)
    elif case == "among-all":
        # the k best of ALL experts, no group limit
        np.testing.assert_array_equal(
            np.sort(idx, -1), np.sort(np.argsort(-scores, -1)[..., :K], -1))
    elif case == "renormalised":
        np.testing.assert_allclose(vals.sum(-1), 1.0, rtol=1e-6)
    else:
        # a decode step's idle row: no assignment of its is dispatched or
        # counted; the busy rows' results are what they were
        E, F = wr.shape[1], 8
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        wg, wu = (jax.random.normal(k, (E, 16, F), jnp.float32) / 4
                  for k in ks[:2])
        wd = jax.random.normal(ks[2], (E, F, 16), jnp.float32) / 3
        xd = x.reshape(6, 1, -1)
        active = jnp.asarray([True, False, True, True, False, True])
        full, hit, _ = moe.moe_ffn(xd, wr, wg, wu, wd, K, router="sigmoid")
        part, hit_busy, _ = moe.moe_ffn(xd, wr, wg, wu, wd, K,
                                        router="sigmoid", active=active)
        np.testing.assert_allclose(part[active], full[active], atol=1e-6)
        np.testing.assert_array_equal(part[~active], 0.0)
        busy = np.unique(np.asarray(moe.route_topk(
            xd, wr, K, router="sigmoid")[1])[np.asarray(active)])
        assert int(hit_busy) == len(busy) <= int(hit)


# ---- (e) -----------------------------------------------------------------
def test_a_window_page_given_back_during_prefill_is_never_read_again(state):
    """Window 16 on pages of 8: a prompt of 70 tokens in chunks of 16, then
    decode. Every page the allocator gives back is overwritten with 1e4 at
    once (K and V, every window layer), the first while the prompt is still
    being prefilled; had any later dispatch read it unmasked, its
    log-probabilities would leave the reference's."""
    hf = {**TINY, "sliding_window": 16}
    st = {"params": state["params"], "dims": ref.hf_dims(hf)}
    c = engine(hf, st, "xla", max_context=128)
    spoiled = []
    give_back = c.win.release_behind

    def spoil(seq_id, position):
        first, pages = c.win.seqs.get(seq_id, (0, []))
        n = give_back(seq_id, position)
        if n:
            gone = jnp.asarray(pages[:n])
            c.wk_pool = c.wk_pool.at[:, :, gone].set(1e4)
            c.wv_pool = c.wv_pool.at[:, :, gone].set(1e4)
            spoiled.append((position, first, n))
        return n

    c.win.release_behind = spoil
    prompt = prompt_of(70, 7)
    outs = generate(c, "w", prompt, 20)
    toks = [o.token for o in outs]
    tokens = np.asarray(prompt + toks[:-1], np.int32)
    _, ref_logp = ref.trace(st, tokens)
    tail = np.asarray(ref_logp[len(prompt) - 1:])
    got = np.asarray([o.token_logprob for o in outs])
    np.testing.assert_allclose(got, tail[np.arange(20), toks], atol=TOL)
    assert toks == tail.argmax(-1).tolist()
    # pages went back chunk by chunk while the prompt was prefilled (a
    # fetched chunk's first query stood at 32, 48, 64) and in decode
    assert [p for p, _, _ in spoiled][:3] == [32, 48, 64]
    assert all(p - 15 >= (f + n) * 8 for p, f, n in spoiled)
    # ... and the lane never held more than a window and three chunks
    assert c.win.num_pages == 2 * WindowPages.lane_pages(16, 16, 8) + 1


@pytest.mark.parametrize("window, page, chunk", [(128, 64, 256),
                                                 (4096, 64, 512),
                                                 (4096, 64, 1024)])
@pytest.mark.parametrize("lag", [0, 1])
def test_window_pages_over_a_prompt_several_windows_long(window, page, chunk,
                                                         lag):
    """A prompt of five windows and a bit, prefilled in chunks as the engine
    does it: pages leased before a chunk is enqueued, pages behind the
    window of a chunk's FIRST query given back when that chunk is fetched,
    which is before the next chunk is enqueued (``lag`` 0) or one chunk
    later (the engine: a chunk is fetched at the end of the iteration after
    its own). At every chunk ``read_window`` names, in order, the pages that
    hold positions ``start - (window - 1) .. start + count - 1``, all of
    them valid and nothing else, never more than ``lane_pages`` of them; the
    lane HOLDS a window and two chunks' pages at most (lag 0) or a window
    and three chunks' (the engine; ``_can_admit``'s bound), whatever the
    prompt's length; pages go back while the prompt is prefilled."""
    total = 5 * window + 3 * page + 17
    n_read = WindowPages.chunk_read_pages(window, chunk, page)
    lane = WindowPages.lane_pages(window, chunk, page)
    bound = -(-(window - 1 + (2 + lag) * chunk) // page) + 1
    w = WindowPages(bound + 1, page, window)
    w.create("s")
    where = {}                      # position -> physical page, as leased
    starts = list(range(0, total, chunk))
    for i, start in enumerate(starts):
        count = min(chunk, total - start)
        w.ensure("s", start + count)
        assert w.pages_in_use <= bound
        first, pages = w.seqs["s"]
        for j, p in enumerate(pages):
            where.setdefault(first + j, p)
        ids, pos, valid = w.read_window("s", start, count, n_read)
        lo = max(0, start - (window - 1))
        seen = pos[valid]
        assert seen.min() <= lo and seen.max() == start + count - 1
        assert (np.diff(seen) == 1).all()
        assert seen.min() >= lo - page + 1          # no page wholly behind
        assert len(seen) <= lane * page
        np.testing.assert_array_equal(
            ids[: len(seen) // page + (len(seen) % page > 0)],
            [where[p] for p in range(seen.min() // page,
                                     seen.max() // page + 1)])
        np.testing.assert_array_equal(
            w.write_slots("s", start, count),
            [where[t // page] * page + t % page
             for t in range(start, start + count)])
        if i >= lag:
            w.release_behind("s", starts[i - lag])
    assert w.released_total >= (total - window - (1 + lag) * chunk) // page - 2
    w.release("s")
    assert w.pages_in_use == 0


# ---- (f) -----------------------------------------------------------------
def test_the_published_config_maps():
    m = llama.LlamaConfig.from_hf_config(published())
    assert (m.num_layers, m.hidden_size, m.num_heads, m.num_kv_heads,
            m.head_dim, m.v_dim) == (32, 4096, 128, 8, 128, 128)
    assert m.layer_kinds == (1, 1, 1, 0) * 8 and m.window_kv_heads == 8
    assert (m.sliding_window, m.rope_theta, m.rope_local_theta,
            m.rms_eps) == (4096, 50000, None, 1e-5)
    assert (m.parallel_block, m.layer_norm, m.nope_full, m.rope_interleaved,
            m.shared_average, m.tie_embeddings) == (True,) * 6
    assert (m.num_experts, m.experts_per_token, m.expert_width, m.router,
            m.router_experts, m.shared_experts) == (128, 8, 4096, "sigmoid",
                                                    None, 4)
    assert m.routed_layers == 32 and m.ffn_kinds is None
    assert m.logits_scaling is None and not m.qk_norm and m.use_rope
    g, w = cache_kinds(m)
    # 8 x (128 + 128) x 2 B a token a layer: 8 full layers, 24 window ones
    assert [g.token_bytes(2), w.token_bytes(2)] == [8 * 4096, 24 * 4096]
    assert (g.window, w.window, w.kv_heads) == (None, 4096, 8)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "command-a-plus-4l.json")) as f:
        cut = json.load(f)
    bench = cut.pop("benchmark")
    c = llama.LlamaConfig.from_hf_config(cut)
    assert (c.num_layers, c.num_experts, c.router_experts, c.expert_first,
            c.vocab_size) == (4, 16, 128, 0, 32768)
    assert c.layer_kinds == (1, 1, 1, 0) and c.routed_layers == 4
    assert sorted(bench["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 4733292544                     # 9.47 GB: the issue's count
    # the logit scale is honoured where it is not 1
    s = llama.LlamaConfig.from_hf_config({**published(), "logit_scale": 0.25})
    assert s.logits_scaling == 4.0


@pytest.mark.parametrize("change, says", [
    ({"shared_expert_combination_strategy": "sum"},
     "shared_expert_combination_strategy"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"first_k_dense_replace": 2}, "first_k_dense_replace"),
    ({"use_parallel_block": False}, "use_parallel_block"),
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"layer_switch": 2}, "layer_switch"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 8.0}},
     "rope_scaling"),
    ({"rope_parameters": {"rope_theta": 10000, "rope_type": "default"}},
     "rope_theta"),
    ({"use_gated_activation": False}, "use_gated_activation"),
    ({"use_embedding_sharing": False}, "use_embedding_sharing"),
    ({"rms_norm_eps": 1e-6}, "layer_norm_eps"),
    ({"num_shared_experts": 0}, "num_shared_experts"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"order_of_interleaved_layers": "global_attn_first"},
     "order_of_interleaved_layers"),
    ({"sliding_window": None}, "layer_types"),
    ({"expert_capacity_factor": 1.5}, "expert keys this engine does not"),
    ({"expert_shard": {"router_experts": 128, "first_expert": 120}},
     "not among"),
    ({"model_type": "cohere2"}, "without model_type 'cohere2_moe'"),
])
def test_the_familys_keys_map_or_raise(change, says):
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config({**published(), **change})


def test_layer_types_of_both_kinds_keep_two_caches_for_any_model():
    """``layer_types`` that names window and full layers gives a model that
    is not Gemma per-kind stacks and the window pool (it used to be served
    with every layer's whole context kept); a Gemma keeps its mask."""
    base = {"vocab_size": 259, "hidden_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 128, "sliding_window": 8,
            "layer_types": ["sliding_attention", "full_attention"] * 2}
    m = llama.LlamaConfig.from_hf_config(base)
    assert m.layer_kinds == (1, 0, 1, 0) and m.sliding_window == 8
    assert m.has_window and m.window_kv_heads == 2 and not m.parallel_block
    assert [k.name for k in cache_kinds(m)] == ["global", "window"]
    with pytest.raises(ValueError, match="ONE sliding_window"):
        llama.LlamaConfig.from_hf_config({**base, "sliding_window": None})
    g = llama.LlamaConfig.from_hf_config(
        {**base, "architectures": ["Gemma2ForCausalLM"], "head_dim": 16})
    assert g.layer_kinds is None and g.sliding_window == 8


# ---- (g) -----------------------------------------------------------------
def test_the_two_pairings_agree_under_the_column_permutation():
    """Interleaved rotary on q = h Wq, k = h Wk gives the scores rotate-half
    gives on the de-interleaved columns, and rotate-half on the published
    columns does not."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (1, 5, 32), jnp.float32)
    wq = jax.random.normal(ks[1], (32, 4, 16), jnp.float32)
    wk = jax.random.normal(ks[2], (32, 2, 16), jnp.float32)
    m = llama.LlamaConfig(head_dim=16, rope_theta=50000.0)
    rope = llama.rope_tables(m, jnp.arange(5)[None] * 7)

    def scores(wq, wk, interleaved):
        q = llama.apply_rope(jnp.einsum("btd,dhk->bthk", h, wq), *rope,
                             interleaved)
        k = llama.apply_rope(jnp.einsum("btd,dhk->bthk", h, wk), *rope,
                             interleaved)
        return jnp.einsum("bthk,bshk->bhts", q.reshape(1, 5, 2, 2, 16)[
            :, :, :, 0], k)

    want = scores(wq, wk, True)
    got = scores(llama.deinterleaved(wq), llama.deinterleaved(wk), False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.abs(scores(wq, wk, False) - want).max() > 0.5
    # ... and the reference's own rotary is the interleaved one
    np.testing.assert_allclose(
        ref.rotary(jnp.einsum("td,dhk->thk", h[0], wk), jnp.arange(5) * 7,
                   50000.0),
        llama.apply_rope(jnp.einsum("btd,dhk->bthk", h, wk), *rope, True)[0],
        rtol=2e-5, atol=2e-5)


# ---- (h) -----------------------------------------------------------------
def test_counters_say_what_the_dispatches_did(core):
    st = core.stage
    series = (st.moe_assignments, st.moe_routed_assignments,
              st.moe_shared_rows, st.moe_experts_hit,
              st.engine_dispatch_tokens, st.kv_resident_token_steps)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    # K = 2 experts a token in each of the 4 layers; every real token's row
    # through the shared experts of each
    assert moved["dyn_moe_routed_assignments_total", "prefill"] == 37 * 2 * 4
    assert moved["dyn_moe_routed_assignments_total", "decode"] == n * 2 * 4
    assert moved["dyn_moe_shared_rows_total", "prefill"] == 37 * 4
    assert moved["dyn_moe_shared_rows_total", "decode"] == n * 4
    for kind in ("prefill", "decode"):
        held = moved["dyn_moe_assignments_total", kind]
        routed = moved["dyn_moe_routed_assignments_total", kind]
        # this chip holds 4 of the router's 8: about half, never all or none
        assert 0.2 * routed < held < 0.8 * routed
        assert moved["dyn_moe_experts_hit_total", kind] > 0
    # a window of 8 on pages of 8: a lane holds 2-3 pages of its 38-42 tokens
    share = (moved["dyn_kv_resident_token_steps_total", "window"]
             / moved["dyn_kv_resident_token_steps_total", "global"])
    assert 0.2 < share < 0.65


def test_costs_and_block_bytes_are_by_kind(core):
    from dynamo_tpu.utils import roofline

    m = core.cfg.model
    # a block of the global cache: 1 full layer x 2 heads x (128 + 128) x 4 B
    assert llama.kv_block_bytes(m, 8) == 8 * 1 * 2 * 256 * 4
    assert core.cache_kinds[1].token_bytes(4) == 3 * 2 * 256 * 4
    costs = roofline.model_costs(m, weight_bytes=1.0)
    assert costs.window_groups == ((8, 3), (None, 1))
    assert [k.name for k in core.cache_kinds] == ["global", "window"]
