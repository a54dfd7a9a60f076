"""MiMo-V2-Flash-style language model (window and full layers with head
counts, parameter stacks and CACHES of their own; K/V heads of two widths;
partial rotary at two bases; a sink logit in the window layers; scaled
values; a leading dense layer; sigmoid-routed experts chosen with a
selection bias, of which this chip holds a share) against its ONE float32
reference, ``benchmarks/references/mimo_v2_flash.py``, at a tiny size where
the window binds (8 keys of contexts of 41-53), in float32.

(a) chunked prefill then decode through both page pools and the engine's
own programs, dense path and kernels; (b) every broken variant of the
reference fails the same tolerance; (c) the shares of the experts add up to
the whole layer; (d) a window page given back is never read again; (e) the
published config maps, and what cannot be honoured raises; (f) what moves
or re-enters blocks refuses the model by name; (g) the counters.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import mimo_v2_flash as ref
from dynamo_tpu.engine.cache import WindowPages, cache_kinds
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # served log-probability against the reference's, float32
TINY = {
    "model_type": "mimo_v2_flash", "hidden_size": 64, "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 128,
    "v_head_dim": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": None,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "routed_scaling_factor": None,
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "layernorm_epsilon": 1e-5, "vocab_size": 259,
    "tie_word_embeddings": False, "max_position_embeddings": 1024,
    "attention_bias": False, "hidden_act": "silu",
    "partial_rotary_factor": 0.334, "sliding_window": 8,
    "sliding_window_size": 8, "attention_chunk_size": 8,
    "attention_value_scale": 0.707,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1, 1, 1],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "swa_num_attention_heads": 4,
    "swa_num_key_value_heads": 2, "swa_head_dim": 128, "swa_v_head_dim": 64,
    # this chip: experts 2-5 of the router's 8
    "expert_shard": {"router_experts": 8, "first_expert": 2},
}


def published():
    """The catalog row's ``config`` as the benchmark's file holds it (the
    three keys the file reduces put back, the share taken off)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2-flash-7l.json")) as f:
        cfg = json.load(f)
    cfg.pop("benchmark")
    cfg.pop("expert_shard")
    cfg.update(num_hidden_layers=48, n_routed_experts=256, vocab_size=152576)
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
    ``TOL`` and every greedy token is the reference's best, through both
    page pools; the window pool gave pages back on the way."""
    tail, worst = against(state, served)
    assert served[1] == tail.argmax(-1).tolist()
    assert worst < TOL
    assert core.win.released_total >= 4
    assert core.win.pages_in_use == 0          # all back at the end
    assert core.pool.free_pages == core.pool.num_pages - 1


# ---- (b) -----------------------------------------------------------------
BROKEN = [v for v in ref.VARIANTS if v not in ("full", "experts_int8")]


@pytest.mark.parametrize("variant", BROKEN)
def test_every_broken_variant_fails_the_tolerance(state, served, variant):
    """The served path against the reference with ONE departure (no sink,
    a sink in the full layers too, the window off, one rotary base, rotary
    over all dims, v unscaled, softmax routing, the selection bias left out
    or used as a weight, one expert fewer, the probe's dropped layer and
    int8 weights): each is told apart at the tolerance (a) passes, twenty
    times over and more (the nearest is the selection bias used as a weight,
    56 times: a bias of spread 0.02 moves a gate by a fortieth)."""
    _, worst = against(state, served, variant)
    assert worst > 20 * TOL, (variant, worst)


# ---- (c) -----------------------------------------------------------------
@pytest.mark.parametrize("rows", [2, 16])       # dense / sorted dispatch
def test_shares_of_the_experts_add_up_to_the_whole_layer(rows):
    """8 experts in 4 shares of 2: each share routes over all 8, computes
    its own two experts' part with gates normalised over all chosen, and
    the four parts add up to the uncut layer's output; the chosen experts
    are the router's own in every share; and the reference's routed layer,
    given the whole, says the same."""
    D, F, E, K = 32, 16, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(rows), 6)
    x = jax.random.normal(ks[0], (1, rows, D), jnp.float32)
    wr = jax.random.normal(ks[1], (D, E), jnp.float32) / np.sqrt(D)
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.float32) / np.sqrt(D)
              for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D), jnp.float32) / np.sqrt(F)
    bias = 0.1 * jax.random.normal(ks[5], (E,), jnp.float32)
    law = dict(router="sigmoid_bias", bias=bias)
    whole, (hit, held), chosen = moe.moe_ffn(x, wr, wg, wu, wd, K, first=0,
                                             **law)
    assert int(held) == rows * K
    parts, n_held = 0.0, 0
    for first in range(0, E, 2):
        sl = slice(first, first + 2)
        assert moe.sorted_wins(rows, K, 2, 0.25) == (rows == 16)
        y, (hit, held), ch = moe.moe_ffn(x, wr, wg[sl], wu[sl], wd[sl], K,
                                         first=first, **law)
        np.testing.assert_array_equal(ch, chosen)
        assert 0 <= int(hit) <= 2
        parts, n_held = parts + y, n_held + int(held)
    assert n_held == rows * K
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    gates, idx, _ = ref.route(x[0], wr, bias, K, "sigmoid_bias", 0.0)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(chosen[0], -1))
    act = (jax.nn.silu(jnp.einsum("td,edf->tef", x[0], wg))
           * jnp.einsum("td,edf->tef", x[0], wu))
    np.testing.assert_allclose(
        jnp.einsum("tef,efd,te->td", act, wd, gates), whole[0], atol=1e-5)


# ---- (d) -----------------------------------------------------------------
def test_a_window_page_given_back_is_never_read_again(state):
    """Window 128 on pages of 64: a prompt of 120 tokens, then decode across
    positions 127, 128, 191 and 192 and two page boundaries. Every page the
    allocator gives back is overwritten with 1e4 at once (K and V, every
    window layer); had any later dispatch read it unmasked, its
    log-probabilities would leave the reference's."""
    hf = {**TINY, "sliding_window": 128, "sliding_window_size": 128,
          "attention_chunk_size": 128}
    st = {"params": state["params"], "dims": ref.hf_dims(hf)}
    c = engine(hf, st, "xla", page_size=64, max_context=384,
               prefill_chunk=64, decode_steps=4)
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
    prompt = prompt_of(120, 7)
    outs = generate(c, "w", prompt, 150)
    toks = [o.token for o in outs]
    tokens = np.asarray(prompt + toks[:-1], np.int32)
    _, ref_logp = ref.trace(st, tokens)
    tail = np.asarray(ref_logp[len(prompt) - 1:])
    got = np.asarray([o.token_logprob for o in outs])
    np.testing.assert_allclose(got, tail[np.arange(150), toks], atol=TOL)
    assert toks == tail.argmax(-1).tolist()
    # logical pages 0 and 1 went back while the sequence lived: page 0 once
    # a fetched dispatch's first query stood at 191 or beyond
    assert [(f, n) for _, f, n in spoiled] == [(0, 1), (1, 1)]
    assert all(p - 127 >= (f + n) * 64 for p, f, n in spoiled)
    # ... and a lane never held more than the window's pages and one ahead
    assert c.win.num_pages == 2 * (-(-(127 + 64) // 64) + 1) + 1


def test_window_pages_bookkeeping():
    w = WindowPages(num_pages=9, page_size=4, window=6)
    w.create("s")
    w.ensure("s", 10)                           # logical pages 0, 1, 2
    assert w.pages_in_use == 3 and w.free_pages == 5
    assert w.release_behind("s", 8) == 0        # 8 - 5 = 3: page 0 in reach
    assert w.release_behind("s", 9) == 1        # 9 - 5 = 4: page 0 behind
    row = w.table_row("s", 5)
    assert row[0] == 0 and all(row[1:3] > 0) and not row[3:].any()
    assert w.tokens_held("s", 10) == 6
    ids, pos, valid = w.read_window("s", 8, 2, 3)
    assert pos[0] == 4 and valid.tolist() == [True] * 6 + [False] * 6
    assert (ids[:2] == row[1:3]).all() and ids[2] == 0
    np.testing.assert_array_equal(
        w.write_slots("s", 8, 2), row[2] * 4 + np.arange(2))
    with pytest.raises(Exception, match="window cache"):
        w.ensure("s", 100)
    w.release("s")
    assert w.pages_in_use == 0 and w.released_total == 1


# ---- (e) -----------------------------------------------------------------
def test_the_published_config_maps():
    m = llama.LlamaConfig.from_hf_config(published())
    assert (m.num_layers, m.hidden_size, m.num_heads, m.head_dim,
            m.v_dim) == (48, 4096, 64, 192, 128)
    assert (m.num_kv_heads, m.window_kv_heads) == (4, 8)
    assert m.kind_layers(False) == (0, 5, 11, 17, 23, 29, 35, 41, 47)
    assert (m.rotary_dim, m.rope_theta, m.rope_local_theta) == (
        64, 5000000, 10000)
    assert (m.sliding_window, m.attn_value_scale, m.rms_eps) == (
        128, 0.707, 1e-5)
    assert (m.sink_window, m.sink_full) == (True, False)
    assert (m.num_experts, m.experts_per_token, m.expert_width, m.router,
            m.router_experts) == (256, 8, 2048, "sigmoid_bias", None)
    assert not m.layer_routed(0) and m.routed_layers == 47
    assert (m.intermediate_size, m.k_store_dim) == (16384, 256)
    g, w = cache_kinds(m)
    # 4 x (192 + 128) x 2 B a token a full layer, 8 x 320 x 2 a window one
    assert [g.token_bytes(2), w.token_bytes(2)] == [9 * 2560, 39 * 5120]
    assert (g.window, w.window, w.kv_heads) == (None, 128, 8)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mimo-v2-flash-7l.json")) as f:
        cut = json.load(f)
    cut.pop("benchmark")
    c = llama.LlamaConfig.from_hf_config(cut)
    assert (c.num_layers, c.num_experts, c.router_experts, c.expert_first,
            c.vocab_size) == (7, 16, 256, 0, 19072)
    assert c.layer_kinds == (0, 1, 1, 1, 1, 0, 1) and c.routed_layers == 6
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0)))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert abs(n - 3.43e9) < 0.005e9        # the issue's count: 6.86 GB


@pytest.mark.parametrize("change, says", [
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"scoring_func": "softmax"}, "softmax.*noaux_tc"),
    ({"topk_method": "greedy"}, "sigmoid.*greedy"),
    ({"routed_scaling_factor": 2.5}, "routed_scaling_factor"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"moe_layer_freq": [0, 1]}, "moe_layer_freq"),
    ({"hybrid_layer_pattern": [0] * 48}, "both kinds"),
    ({"sliding_window_size": 256}, "ONE sliding_window"),
    ({"swa_head_dim": 128}, "swa_head_dim"),
    ({"swa_num_attention_heads": 32}, "swa_num_attention_heads"),
    ({"expert_shard": {"router_experts": 256, "first_expert": 250}},
     "not among"),
    ({"expert_gate_noise": 0.1}, "expert keys this engine does not"),
])
def test_what_cannot_be_honoured_raises(change, says):
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config({**published(), **change})


def test_the_router_law_keys_no_longer_slip_past():
    """A dense config that names a router law the engine does not know is
    refused, where the keys used to pass unseen (ROADMAP Reach A2)."""
    base = {"vocab_size": 259, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 128}
    llama.LlamaConfig.from_hf_config(base)
    for key in ("scoring_func", "topk_method", "n_group", "topk_group"):
        with pytest.raises(ValueError, match="expert"):
            llama.LlamaConfig.from_hf_config({**base, key: 1})
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        llama.LlamaConfig.from_hf_config(
            {**base, "swa_num_key_value_heads": 8})


# ---- (f) -----------------------------------------------------------------
@pytest.mark.parametrize("kw, says", [
    ({"host_cache_blocks": 4}, "host / disk KV tiers"),
    ({"spec": "ngram"}, "speculative"),
    ({"tp": 2}, "one chip"),
    ({"pp": 2}, "one K/V cache"),
])
def test_what_moves_blocks_refuses_the_model_by_name(kw, says):
    model = llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)
    with pytest.raises(ValueError, match=says):
        EngineCore(JaxEngineConfig(model=model, page_size=8, max_batch=2,
                                   max_context=64, prefill_chunk=16,
                                   attn_impl="xla", **kw))


def test_block_moving_calls_refuse_and_no_block_is_hashed(core):
    for call in (lambda: core.extract_kv("x"),
                 lambda: core.stage_prefetch([1, 2, 3]),
                 lambda: core.prefill_extract("x", None),
                 lambda: core.inject_prefilled("x", None, None, None, 0, 0.0),
                 lambda: core.begin_stream_inject("x", {})):
        with pytest.raises(ValueError, match="cache of their own"):
            call()
    model = core.cfg.model
    with pytest.raises(ValueError, match="one K/V cache"):
        llama.forward_pp(core.params, model, jnp.zeros((1, 1, 1), jnp.int32),
                         *[None] * 7, mesh=None)
    with pytest.raises(ValueError, match="one K/V cache"):
        llama.forward_decode(core.params, model, jnp.zeros(2, jnp.int32),
                             core.k_pool, core.v_pool,
                             jnp.zeros((2, 8), jnp.int32),
                             jnp.ones(2, jnp.int32))
    from dynamo_tpu.llm.kvpage.programs import PagedPrograms
    assert "cache of their own" in PagedPrograms.validate(core.cfg)
    # the same prompt twice: nothing is matched, sealed or published
    generate(core, "p1", prompt_of(33, 9), 2)
    hit0 = core.prefix_hit_tokens
    generate(core, "p2", prompt_of(33, 9), 2)
    assert core.prefix_hit_tokens == hit0 == 0


def test_both_kinds_rows_are_written_by_the_kernel(monkeypatch):
    """At the published head widths (K 192 stored 256 wide, V 128) the paged
    kernel (in the interpreter) writes the decode step's rows of BOTH kinds, the
    full layers' into the global pools and the window layers' into the
    window pools through their own page tables, sink and all: the same
    tokens, logits and four pools, bit for bit, as ``kv_write`` in front of
    the same kernel."""
    cfg = llama.LlamaConfig.from_hf_config(dict(
        TINY, head_dim=192, v_head_dim=128, swa_head_dim=192,
        swa_v_head_dim=128))
    kinds = cache_kinds(cfg)
    assert [llama.kernel_writes(None, "pallas", k.k_store, k.fold)
            for k in kinds] == [True, True]
    assert not llama.kernel_writes(None, "pallas", 24, 1)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    page, n_pages = 16, 7
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    pools = [jax.random.normal(next(keys), shape, jnp.float32)
             .astype(cfg.dtype)
             for k in kinds for shape in k.pool_shapes(n_pages, page)]
    pt = jnp.asarray([[2, 5, 1], [4, 3, 6]], jnp.int32)
    # a window lane holds only the pages a query can still see
    wt = jnp.asarray([[3, 0, 0], [0, 1, 5]], jnp.int32)

    def serve():
        dec = jax.jit(lambda p, t, k, v, wk, wv, ln: llama.forward_decode(
            p, cfg, t, k, v, pt, ln, attn_impl="pallas", win=(wk, wv, wt)))
        tok = jnp.asarray([5, 7], jnp.int32)
        ln = jnp.asarray([15, 31], jnp.int32)       # ... 16/17, 32/33 next
        state, toks = list(pools), []
        for _ in range(3):
            lg, *state = dec(params, tok, *state, ln)
            tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
            toks.append(np.asarray(tok))
            ln = ln + 1
        return (np.stack(toks), np.asarray(lg, np.float32),
                *(np.asarray(a, np.float32) for a in state))

    got = serve()
    monkeypatch.setattr(llama, "kernel_writes", lambda *a: False)
    for g, w in zip(got, serve()):
        np.testing.assert_array_equal(g, w)


# ---- (g) -----------------------------------------------------------------
def test_counters_say_what_the_dispatches_did(core):
    st = core.stage
    series = (st.moe_assignments, st.moe_routed_assignments,
              st.moe_experts_hit, st.engine_dispatch_tokens,
              st.kv_resident_token_steps)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    rel0 = sum(st.kv_window_pages_released._values.values())
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    # K=2 experts a token in each of the 6 ROUTED layers (not the 7)
    assert moved["dyn_moe_routed_assignments_total", "prefill"] == 37 * 2 * 6
    assert moved["dyn_moe_routed_assignments_total", "decode"] == n * 2 * 6
    for kind in ("prefill", "decode"):
        held = moved["dyn_moe_assignments_total", kind]
        routed = moved["dyn_moe_routed_assignments_total", kind]
        # this chip holds 4 of the router's 8: about half, never all or none
        assert 0.2 * routed < held < 0.8 * routed
        assert moved["dyn_moe_experts_hit_total", kind] > 0
    assert sum(st.kv_window_pages_released._values.values()) - rel0 >= 3
    # a window of 8 on pages of 8: a lane holds 2-3 pages of its 38-45 tokens
    share = (moved["dyn_kv_resident_token_steps_total", "window"]
             / moved["dyn_kv_resident_token_steps_total", "global"])
    assert 0.2 < share < 0.65


def test_costs_and_block_bytes_are_by_kind(core):
    from dynamo_tpu.utils import roofline

    m = core.cfg.model
    # a block of the global cache: 2 full layers x 1 head x (128 + 64) x 4 B
    assert llama.kv_block_bytes(m, 8) == 8 * 2 * 192 * 4
    assert core.cache_kinds[1].token_bytes(4) == 5 * 2 * 192 * 4
    costs = roofline.model_costs(m, weight_bytes=1.0)
    assert costs.window_groups == ((8, 5), (None, 2))
    assert costs.group_kv_bytes == (2 * 192 * 4, 1 * 192 * 4)
    assert costs.kv_write_bytes_per_token == 5 * 1536 + 2 * 768
    # a decode query at length 50 reads 8 keys in a window layer, 50 in a
    # full one
    fl, by, tk = roofline.decode_cost(costs, [50], 1)
    assert by == 1.0 + 5 * 8 * 1536 + 2 * 50 * 768 + 5 * 1536 + 2 * 768
    assert [k.name for k in core.cache_kinds] == ["global", "window"]


# ---- a share dispatched dense keeps its decode program ---------------------
def test_a_share_dispatched_dense_keeps_its_decode_program(state,
                                                           monkeypatch):
    """4 held of the router's 8, two rows: the rule says dense, and such a
    share takes no notice of the dispatch's ``active`` mask (its crossing is
    another one: ROADMAP ``held-experts-hit``). The engine's own decode
    step lowers to the text it had before a decode step knew its busy rows
    (no ``ragged_dot``, no conditional under the experts' scope, no
    ``sorted`` column), and the host still scales ``held`` by the lanes."""
    from dynamo_tpu.utils import roofline
    from tests.test_lfm2_moe import parent_routing, primitives

    def decode_program(mp):
        seen = {}

        def spy(kind, fn, record):
            def call(*args, **kw):
                if kind == "decode" and not seen:
                    seen["text"] = fn.lower(*args, **kw).as_text()
                    seen["inside"] = primitives(
                        fn, *args, under="dynamo.moe_ffn", **kw)
                return fn(*args, **kw)
            return call

        mp.setattr(roofline, "instrument_compile", spy)
        core = engine(TINY, state, "xla")
        generate(core, "r", prompt_of(20, 1), 3)
        return core, seen

    with monkeypatch.context() as mp:
        core, now = decode_program(mp)
    assert core.moe_dispatch.startswith("decode:dense")
    assert not core._decode_routes_busy
    assert core._decode_cols == core._packed_cols == ("experts_hit", "held")
    inside = now["inside"]
    assert "top_k" in inside and not {"ragged_dot", "cond"} & inside, inside
    with monkeypatch.context() as mp:
        parent_routing(mp)
        _, was = decode_program(mp)
    assert now["text"] == was["text"]
