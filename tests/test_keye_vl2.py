"""Keye-VL-2.0-style language model (routed experts of their own width, a
learned top-k selection inside paged attention, q/k norm) against its ONE
float32 reference, ``benchmarks/references/keye_vl2.py``, at a tiny size
where the selection binds (top-8 of contexts of 24-48), in float32.

(a) chunked prefill then decode through the paged cache and the engine's own
programs; (b) the selected sets and chosen experts themselves; (c) a context
no longer than ``topk`` is full attention, bit for bit; (d) a prefix hit
brings the index keys with the pages; (e) the published config maps, and
what cannot be honoured raises; (f) expert width of its own, dense as before.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import keye_vl2 as ref
from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama
from dynamo_tpu.utils import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
TINY = {
    "model_type": "KeyeVL2", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "intermediate_size": 128, "moe_intermediate_size": 48, "num_experts": 8,
    "num_local_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "vocab_size": 259, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "attention_bias": False,
    "hidden_act": "silu",
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8, "q_chunk_size": 512,
                  "kv_chunk_size": 512},
}


def published():
    """The catalog row's ``config`` as the benchmark's file holds it (the
    one key the file reduces put back)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl2-30b-a3b-6l.json")) as f:
        cfg = json.load(f)
    cfg.pop("benchmark")
    cfg["num_hidden_layers"] = 48
    return cfg


@pytest.fixture(scope="module")
def state():
    return ref.build(TINY, 3)


@pytest.fixture(scope="module")
def model():
    return llama.LlamaConfig.from_hf_config(TINY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(state):
    # the reference's tensors (bfloat16 values), as float32
    return jax.tree.map(lambda a: a.astype(jnp.float32), state["params"])


@pytest.fixture(scope="module")
def core(model, params):
    c = EngineCore(JaxEngineConfig(
        model=model, page_size=PAGE, max_batch=2, max_context=64,
        prefill_chunk=16, decode_steps=2, attn_impl="pallas"))
    c.params = params
    return c


def generate(core, seq_id, prompt, n):
    core.submit(seq_id, BackendInput(token_ids=list(prompt),
                                     stop=StopConditions(max_tokens=n)))
    outs = []
    for _ in range(400):
        outs += [so for so in core.step() if so.seq_id == seq_id]
        if outs and outs[-1].finish is not None:
            return outs
    raise AssertionError("did not finish")


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 259, n).tolist()


# ---- (a) -----------------------------------------------------------------
def test_engine_prefill_and_decode_agree_with_the_reference(core, state):
    """41 prompt tokens in chunks of 16 (three dispatches, the last one
    partial), 12 tokens decoded two a dispatch through the paged kernel with
    its keep mask: every served log-probability is the reference's for that
    token to 1e-4 and every greedy token is the reference's best."""
    prompt = prompt_of(41)
    outs = generate(core, "a", prompt, 12)
    toks = [o.token for o in outs]
    _, _, logp = ref.trace(state, np.asarray(prompt + toks[:-1], np.int32))
    tail = np.asarray(logp[len(prompt) - 1:])
    assert toks == tail.argmax(-1).tolist()
    np.testing.assert_allclose([o.token_logprob for o in outs],
                               tail[np.arange(12), toks], atol=1e-4)
    assert (core.attn_impl, core.decode_attn_impl) == ("pallas", "pallas")


def test_counters_say_what_the_dispatches_did(core):
    """Host counters of the experts' and the indexer's work: 37 prompt
    tokens and the decode steps of one lane (those past the request's end
    that a chained dispatch computed among them), by hand."""
    st = core.stage
    series = (st.moe_assignments, st.moe_experts_hit, st.sparse_attn_context,
              st.sparse_attn_selected, st.engine_dispatch_tokens)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    before = read()
    generate(core, "cnt", prompt_of(37, 5), 5)
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    # K=2 experts a token, L=2 layers; first token from prefill, 4 decoded
    n = int(moved["dyn_engine_dispatch_tokens_total", "decode"])
    assert n >= 4 and n % 2 == 0
    assert moved["dyn_moe_assignments_total", "prefill"] == 37 * 2 * 2
    assert moved["dyn_moe_assignments_total", "decode"] == n * 2 * 2
    assert moved["dyn_sparse_attn_context_tokens_total", "prefill"] == \
        37 * 38 // 2
    assert moved["dyn_sparse_attn_selected_tokens_total", "prefill"] == \
        8 * 9 // 2 + 29 * 8
    assert moved["dyn_sparse_attn_context_tokens_total", "decode"] == \
        sum(38 + j for j in range(n))
    assert moved["dyn_sparse_attn_selected_tokens_total", "decode"] == n * 8
    # 2 lanes' rows (one of them padding) x K experts of 8 a layer and step
    # at most, K at least
    hit = moved["dyn_moe_experts_hit_total", "decode"]
    assert n * 2 * 2 <= hit <= n * 2 * 4


def test_a_capture_counts_its_own_dispatches_a_second_time(core):
    """While a ``DYN_PROFILE_DIR`` capture runs (the engine thread's loop
    sets ``capturing``), every dispatch's work also goes to
    ``dyn_profile_captured_work_total``, with the dispatch and its tokens:
    what the benchmark's roofline shares divide by the trace's device time.
    Outside a capture the series does not move."""
    st = core.stage
    series = (st.moe_assignments, st.moe_experts_hit, st.sparse_attn_context,
              st.sparse_attn_selected)
    read = lambda: {(c.name, k[0]): v for c in series
                    for k, v in c._values.items()}
    # (the series is the process's: what earlier tests of this worker left
    # there is the baseline)
    base = dict(st.profile_captured_work._values)
    seen = lambda: {k: v - base.get(k, 0.0)
                    for k, v in st.profile_captured_work._values.items()
                    if v != base.get(k, 0.0)}
    generate(core, "cap0", prompt_of(21, 7), 3)
    assert not seen()
    before = read()
    core.capturing = True
    try:
        generate(core, "cap1", prompt_of(37, 8), 5)
    finally:
        core.capturing = False
    moved = {k: v - before.get(k, 0.0) for k, v in read().items()}
    got = seen()
    for (name, kind), v in moved.items():
        assert got[name, kind] == v, (name, kind)
    assert got["dispatches", "prefill"] == 3 and got["tokens", "prefill"] == 37
    # every context bucket of this engine is longer than topk 8: all scored
    assert got["scored_keys", "prefill"] == 37 * 38 // 2
    assert got["scoring_tokens", "prefill"] == 37
    assert got["scored_keys", "decode"] == moved[
        "dyn_sparse_attn_context_tokens_total", "decode"]
    generate(core, "cap2", prompt_of(21, 9), 3)
    assert seen() == got


# ---- (b), (c): the layer loop itself, chunked through the paged pools ----
def run_chunked(model, params, tokens, impl, S, chunk=16):
    """Prefill ``tokens`` in chunks through ``llama.forward`` exactly as the
    prefill program calls it (pages in order, write-then-gather), then two
    more positions through ``forward_decode``: -> (log-softmax [T, V], per
    layer keep rows [T, S] or None, per layer chosen experts [T, K])."""
    T = len(tokens)
    n_pages = S // PAGE
    shape = (model.num_layers, model.num_kv_heads, n_pages + 1, PAGE,
             model.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    ip = jnp.zeros(llama.index_pool_shape(model, n_pages + 1, PAGE),
                   jnp.float32)
    pages = np.arange(1, n_pages + 1, dtype=np.int32)[None]   # page 0 scratch
    slots = (pages[0][:, None] * PAGE + np.arange(PAGE)[None]).reshape(-1)
    rpos = np.arange(S, dtype=np.int32)[None]
    logits, keeps, chosen = [], [], []
    n_dec = 2

    @jax.jit
    def prefill(toks, pos, kp, vp, ip, w, valid):
        stats = {"keep": [], "chosen": []}
        out = llama.forward(
            params, model, toks, pos, kp, vp, w, None, jnp.asarray(rpos),
            valid, attn_impl=impl, read_pages=jnp.asarray(pages), i_pool=ip,
            stats=stats)
        return out, stats

    @jax.jit
    def decode(tok, kp, vp, ip, length):
        stats = {"keep": [], "chosen": []}
        out = llama.forward_decode(
            params, model, tok, kp, vp, jnp.asarray(pages), length,
            attn_impl="pallas" if impl == "flash" else "xla", i_pool=ip,
            stats=stats)
        return out, stats

    for c0 in range(0, T - n_dec, chunk):
        c1 = min(c0 + chunk, T - n_dec)
        pos = np.arange(c0, c1, dtype=np.int32)[None]
        (lg, kp, vp, ip), stats = prefill(
            np.asarray(tokens[c0:c1])[None], pos, kp, vp, ip,
            slots[c0:c1][None], rpos < c1)
        logits.append(lg[0])
        keeps.append(stats["keep"])
        chosen.append(stats["chosen"])
    for t in range(T - n_dec, T):
        (lg, kp, vp, ip), stats = decode(
            np.asarray([tokens[t]]), kp, vp, ip, np.asarray([t + 1]))
        logits.append(lg[0])
        keeps.append(stats["keep"])
        chosen.append(stats["chosen"])
    out = jax.nn.log_softmax(jnp.concatenate(logits), axis=-1)
    layer = lambda per_call, l: np.concatenate(
        [np.asarray(c[l][0]) for c in per_call])
    L = model.num_layers
    return (out, [None if keeps[0][l] is None else layer(keeps, l)
                  for l in range(L)], [layer(chosen, l) for l in range(L)])


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_selected_sets_and_experts_are_the_references(model, params, state,
                                                      impl):
    """Every query of every layer selects exactly the reference's set (top-8
    of up to 44 visible keys: the selection binds from position 8 on) and
    routes to the reference's experts; the logits follow to 1e-4. ``flash``
    runs the kernels (interpreted) with their keep operand."""
    tokens = prompt_of(44, 7)
    got, keeps, chosen = run_chunked(model, params, tokens, impl, S=48)
    sel, want_chosen, want = ref.trace(state, np.asarray(tokens, np.int32))
    sel = np.asarray(sel)
    assert sel.sum(-1).tolist() == [[min(t + 1, 8) for t in range(44)]] * 2
    for l in range(model.num_layers):
        np.testing.assert_array_equal(keeps[l][:, :44], sel[l])
        assert not keeps[l][:, 44:].any()
        np.testing.assert_array_equal(np.sort(chosen[l], -1),
                                      np.sort(np.asarray(want_chosen[l]), -1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_context_within_topk_is_full_attention_bit_for_bit(model, params,
                                                           impl):
    """40 tokens in a context bucket of 48: with ``topk`` 40 the indexer
    scores and keeps every visible key; with ``topk`` 48 the bucket is no
    longer than ``topk`` and the scoring is skipped (the index keys are
    written all the same). Same bits."""
    tokens = prompt_of(40, 9)
    scored = llama.LlamaConfig(**{**model.__dict__, "index_topk": 40})
    skipped = llama.LlamaConfig(**{**model.__dict__, "index_topk": 48})
    a, keeps, _ = run_chunked(scored, params, tokens, impl, S=48)
    b, none, _ = run_chunked(skipped, params, tokens, impl, S=48)
    assert all(k is None for k in none)
    causal = np.tril(np.ones((40, 48), bool))
    assert all((k == causal).all() for k in keeps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_topk_keep_is_exact_with_ties_to_the_lower_position():
    """``topk_keep`` against ``lax.top_k`` on scores full of exact ties
    (integers), with holes in what is visible."""
    from dynamo_tpu.ops.attention import topk_keep

    rng = np.random.default_rng(1)
    scores = rng.integers(-3, 4, (5, 7, 50)).astype(np.float32)
    scores[0, 0, :10] = -0.0
    visible = rng.random((5, 7, 50)) < 0.7
    visible[1, 2] = False
    visible[2, 3, 5:] = False                       # fewer visible than k
    got = np.asarray(topk_keep(jnp.asarray(scores), jnp.asarray(visible), 9))
    masked = jnp.where(visible, jnp.where(scores == 0, 0.0, scores),
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, 9)
    want = np.zeros_like(visible)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(got, want & visible)
    assert (got.sum(-1) == np.minimum(visible.sum(-1), 9)).all()


# ---- (d) -----------------------------------------------------------------
def test_prefix_hit_brings_the_index_keys_with_the_pages(core):
    """The same 45-token prompt twice: the second request reuses the first
    one's sealed pages on the device and must select, and so read, what the
    first did."""
    prompt = prompt_of(45, 11)
    first = generate(core, "p1", prompt, 6)
    second = generate(core, "p2", prompt, 6)
    assert core.last_prefix_hit >= 4 * PAGE
    assert [o.token for o in first] == [o.token for o in second]
    np.testing.assert_allclose([o.token_logprob for o in first],
                               [o.token_logprob for o in second], atol=1e-5)


# ---- (e) -----------------------------------------------------------------
def test_the_published_config_maps_as_it_stands():
    m = llama.LlamaConfig.from_hf_config(published())
    assert (m.num_layers, m.hidden_size, m.num_heads, m.num_kv_heads,
            m.head_dim, m.vocab_size) == (48, 2048, 32, 4, 128, 151936)
    assert (m.num_experts, m.experts_per_token, m.expert_width,
            m.intermediate_size) == (128, 8, 768, 6144)
    assert (m.index_heads, m.index_head_dim, m.index_topk) == (16, 64, 2048)
    assert m.qk_norm and m.has_indexer and not m.tie_embeddings
    assert m.rope_theta == 1e7 and m.max_position == 262144
    # text only: sectioned rotary is ordinary rotary
    assert llama._rope_inv_freq(m).shape == (64,)
    six = llama.LlamaConfig(**{**m.__dict__, "num_layers": 6})
    # 12,288 B of K/V and 768 B of index keys a token
    assert llama.kv_block_bytes(six, 64) == 64 * (12288 + 768)
    assert llama.index_pool_shape(six, 3085, 64) == (6, 1, 3085, 32, 128)


@pytest.mark.parametrize("change, says", [
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"norm_topk_prob": None}, "norm_topk_prob"),          # key removed
    ({"mlp_only_layers": [0, 3]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"shared_expert_intermediate_size": 512}, "shared_expert"),
    ({"num_local_experts": 64}, "num_local_experts"),
    ({"num_experts_per_tok": None}, "num_experts_per_tok"),
    ({"sa_config": {**TINY["sa_config"], "indexer_num_kv_heads": 2}},
     "indexer_num_kv_heads"),
    ({"sa_config": {**TINY["sa_config"], "window": 128}}, "window"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 4.0}}, "rotary"),
])
def test_what_cannot_be_honoured_raises(change, says):
    cfg = {**copy.deepcopy(TINY), **change}
    cfg = {k: v for k, v in cfg.items() if v is not None}
    with pytest.raises(ValueError, match=says):
        llama.LlamaConfig.from_hf_config(cfg)


def test_no_published_sparse_config_is_served_dense():
    """What the parent did with this very config: a dense model of width
    6144, without an error."""
    m = llama.LlamaConfig.from_hf_config(published())
    assert m.num_experts and m.expert_width != m.intermediate_size
    shapes = jax.eval_shape(lambda: llama.init_params(
        llama.LlamaConfig(**{**m.__dict__, "num_layers": 1}),
        jax.random.PRNGKey(0)))
    assert shapes["layers"]["wg"].shape == (1, 128, 2048, 768)


@pytest.mark.parametrize("kw, says", [
    ({"host_cache_blocks": 8}, "host / disk KV tiers"),
    ({"host_cache_blocks": 8, "cluster_writethrough": True}, "tiers"),
    ({"spec": "ngram", "spec_k": 2}, "speculative"),
    ({"host_cache_blocks": 64, "kvpage_budget": 8, "kvpage_seg_pages": 2},
     "tiers"),
    ({"tp": 2}, "one chip"),
    ({"attn_impl": "ring", "sp": 2}, "ring"),
])
def test_block_moving_features_refuse_an_indexer_by_name(model, kw, says):
    with pytest.raises(ValueError, match=says):
        EngineCore(JaxEngineConfig(model=model, page_size=PAGE, max_batch=2,
                                   max_context=64, prefill_chunk=16, **kw))


@pytest.mark.parametrize("call", [
    lambda c: c.extract_kv("x"),
    lambda c: c.prefill_extract("x", None),
    lambda c: c.inject_prefilled("x", None, None, None, 0, 0.0),
    lambda c: c.begin_stream_inject("x", None),
    lambda c: c.stage_prefetch([1, 2, 3]),
])
def test_disagg_and_tier_calls_refuse_an_indexer(core, call):
    with pytest.raises(ValueError, match="index keys"):
        call(core)


def test_other_paths_refuse_an_indexer(model, params, tmp_path):
    from dynamo_tpu.engine.loader import load_llama_params_host
    from dynamo_tpu.llm.kvpage.programs import PagedPrograms

    with pytest.raises(ValueError, match="tensor names"):
        load_llama_params_host(str(tmp_path), model)
    cfg = JaxEngineConfig(model=model)
    assert "indexer" in PagedPrograms.validate(
        JaxEngineConfig(model=llama.LlamaConfig(
            **{**model.__dict__, "num_experts": 0})))
    assert cfg.model.has_indexer
    z = jnp.zeros((1, 1, 4), jnp.int32)
    with pytest.raises(ValueError, match="forward_pp"):
        llama.forward_pp(params, model, z, z, None, None, z, z, z, z > 0,
                         None)
    with pytest.raises(ValueError, match="index-key pool"):
        llama.forward_decode(params, model, jnp.zeros(1, jnp.int32),
                             jnp.zeros((2, 2, 2, PAGE, model.head_dim)),
                             jnp.zeros((2, 2, 2, PAGE, model.head_dim)),
                             jnp.zeros((1, 1), jnp.int32),
                             jnp.ones(1, jnp.int32))


# ---- (f) -----------------------------------------------------------------
def test_expert_width_of_its_own_and_dense_as_before(model):
    p = jax.eval_shape(lambda: llama.init_params(model,
                                                 jax.random.PRNGKey(0)))
    lay = p["layers"]
    assert lay["wg"].shape == lay["wu"].shape == (2, 8, 64, 48)
    assert lay["wd"].shape == (2, 8, 48, 64) and lay["wr"].shape == (2, 64, 8)
    assert lay["wiq"].shape == (2, 64, 2, 16) and lay["wik"].shape == (
        2, 64, 16)
    assert lay["ln_q"].shape == (2, 128) and lay["ln_ik_b"].shape == (2, 16)
    specs = llama.param_specs(model)
    assert set(specs["layers"]) == set(lay)
    # Mixtral's keys: experts of the dense width, no indexer
    mix = llama.LlamaConfig.from_hf_config({
        **{k: v for k, v in TINY.items() if k not in (
            "sa_config", "num_experts", "moe_intermediate_size",
            "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
            "model_type")}, "num_local_experts": 4})
    assert (mix.num_experts, mix.expert_width, mix.has_indexer,
            mix.qk_norm) == (4, 128, False, False)
    for name in ("qwen2-1.5b", "mistral-7b-16l"):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            hf = json.load(f)
        hf.pop("benchmark")
        d = llama.LlamaConfig.from_hf_config(hf)
        assert (d.num_experts, d.moe_intermediate_size, d.has_indexer,
                d.qk_norm) == (0, None, False, False)
        assert d.expert_width == d.intermediate_size == hf["intermediate_size"]
        assert llama.kv_block_bytes(d, 64) == (
            2 * d.num_layers * d.num_kv_heads * 64 * d.head_dim * 2)


def test_goodput_costs_count_active_experts_and_selected_keys():
    m = llama.LlamaConfig.from_hf_config(published())
    c = roofline.model_costs(m)
    D, L = 2048, 48
    attn = D * 32 * 128 * 2 + 2 * D * 4 * 128 + D * (16 * 64 + 64 + 16)
    experts = 8 * 3 * D * 768 + D * 128
    assert c.mat_flops_per_token == 2.0 * L * (attn + experts)
    # 8 x 768 active columns are the dense width's 6144; what tells the two
    # apart is the weights a step streams: 30.6 B parameters, not 2.9 B
    assert 30.4e9 * 2 < c.weight_bytes < 30.8e9 * 2
    # a query at 10,000 keys attends to 2048 and scores all 10,000
    fl, rd = roofline._attn_cost(c, 10_000)
    assert fl == (4.0 * 32 * 128 * 2048 + 2.0 * 16 * 64 * 10_000) * L
    assert rd == (2 * 4 * 128 * 2 * 2048 + 64 * 2 * 10_000) * L
    dense = roofline.model_costs(llama.preset("mistral-7b"))
    assert dense.index_topk == 0 and roofline._attn_cost(dense, 100) == (
        dense.attn_flops_coef * 100 * 32,
        100 * 32 * dense.kv_bytes_per_tok_layer)


# ---- a decode step routes its busy rows alone ------------------------------
@pytest.mark.parametrize("rows, form", [(16, "sorted"), (4, "by_hit")])
def test_a_decode_step_routes_its_busy_rows_alone(model, params, rows, form):
    """8 experts, 2 a token: the old rule (sorted from 16 rows on), so a
    16-row decode step is sorted and stays sorted (the benchmark's 12-row
    program is, by the measured rule), a 4-row one holds both forms (7
    experts hit at most: under the crossing's 6 or not). Either way the busy
    rows' logits are the unmasked step's and the counts are theirs alone."""
    from dynamo_tpu.models import moe
    from tests.test_lfm2_moe import check_busy_rows_alone, primitives

    assert moe.dispatch_form(rows, 2, 8, masked=True) == form
    assert moe.dispatch_form(12, 8, 128, masked=True) == "sorted"
    P = 2
    shape = (model.num_layers, model.num_kv_heads, rows * P + 1, PAGE,
             model.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    ip = jnp.zeros(llama.index_pool_shape(model, rows * P + 1, PAGE),
                   jnp.float32)
    tables = jnp.arange(1, rows * P + 1, dtype=jnp.int32).reshape(rows, P)
    tokens = jnp.asarray(prompt_of(rows, 3), jnp.int32)

    def step(stats, active):
        return llama.forward_decode(
            params, model, tokens, kp, vp, tables,
            jnp.ones(rows, jnp.int32), i_pool=ip, stats=stats,
            active=active)[0]

    active = jnp.arange(rows) % 3 == 1
    if form == "by_hit":
        # the branch is the device's: count what it took
        stats = {}
        step(stats, active)
        assert 0 <= int(stats["sorted"]) <= model.routed_layers
        assert "cond" in primitives(lambda: step({}, active))
        assert "cond" not in primitives(lambda: step({}, None))
        form = "sorted" if int(stats["sorted"]) == model.routed_layers else (
            "dense" if int(stats["sorted"]) == 0 else None)
        if form is None:
            pytest.skip("the two layers took different branches")
    else:
        assert "cond" not in primitives(lambda: step({}, active))
    check_busy_rows_alone(step, model, active, form)
