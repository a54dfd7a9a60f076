"""Model-level correctness: paged chunked prefill + decode must reproduce the
full-sequence forward pass exactly (same pool, same masks). Pools are
head-major [L, Hkv, n_pages, page, Dh] with page size 8 here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama

CFG = llama.preset("tiny-byte")


def full_logits(params, tokens):
    """Whole sequence in one chunk against a fresh pool."""
    T = len(tokens)
    L, Hkv, Dh = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    pool_k = jnp.zeros((L, Hkv, (64 + T + 7) // 8 + 1, 8, Dh), CFG.dtype)
    pool_v = jnp.zeros_like(pool_k)
    tok = jnp.asarray(tokens, jnp.int32)[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    idx = (jnp.arange(T, dtype=jnp.int32) + 64)[None]
    valid = jnp.ones((1, T), bool)
    logits, _, _ = llama.forward(params, CFG, tok, pos, pool_k, pool_v,
                                 idx, idx, pos, valid)
    return np.asarray(logits[0])


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0))


def test_chunked_prefill_matches_full(params):
    tokens = list(range(1, 25))
    ref = full_logits(params, tokens)

    # same computation split into chunks of 8 against a paged pool
    L, Hkv, Dh = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    pool_k = jnp.zeros((L, Hkv, 32, 8, Dh), CFG.dtype)
    pool_v = jnp.zeros_like(pool_k)
    # pages out of order to exercise the indirection: tokens t -> slot map
    pages = [3, 1, 2]  # page size 8, 24 tokens
    slot_of = lambda t: pages[t // 8] * 8 + t % 8
    all_slots = np.array([slot_of(t) for t in range(24)], np.int32)
    last = None
    for start in range(0, 24, 8):
        tok = jnp.asarray(tokens[start:start + 8], jnp.int32)[None]
        pos = jnp.arange(start, start + 8, dtype=jnp.int32)[None]
        widx = jnp.asarray(all_slots[start:start + 8])[None]
        S = start + 8
        ridx = jnp.asarray(all_slots[:S])[None]
        rpos = jnp.arange(S, dtype=jnp.int32)[None]
        rvalid = jnp.ones((1, S), bool)
        logits, pool_k, pool_v = llama.forward(
            params, CFG, tok, pos, pool_k, pool_v, widx, ridx, rpos, rvalid)
        last = np.asarray(logits[0])
    np.testing.assert_allclose(last[-1], ref[-1], rtol=2e-2, atol=2e-2)


def test_decode_matches_full(params):
    tokens = list(range(40, 56))
    ref = full_logits(params, tokens)

    L, Hkv, Dh = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    pool_k = jnp.zeros((L, Hkv, 16, 8, Dh), CFG.dtype)
    pool_v = jnp.zeros_like(pool_k)
    # prefill the first 8, then decode the rest one token at a time
    slots = np.arange(16, dtype=np.int32)  # contiguous slots starting at 0
    tok = jnp.asarray(tokens[:8], jnp.int32)[None]
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    logits, pool_k, pool_v = llama.forward(
        params, CFG, tok, pos, pool_k, pool_v,
        jnp.asarray(slots[:8])[None], jnp.asarray(slots[:8])[None],
        pos, jnp.ones((1, 8), bool))
    for t in range(8, 16):
        tokp = jnp.asarray([[tokens[t]]], jnp.int32)
        posp = jnp.asarray([[t]], jnp.int32)
        S = t + 1
        logits, pool_k, pool_v = llama.forward(
            params, CFG, tokp, posp, pool_k, pool_v,
            jnp.asarray([[slots[t]]]), jnp.asarray(slots[:S])[None],
            jnp.arange(S, dtype=jnp.int32)[None], jnp.ones((1, S), bool))
    np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[-1],
                               rtol=2e-2, atol=2e-2)


def test_padding_invariance(params):
    """Extra masked-out read slots must not change the result."""
    tokens = list(range(10, 20))
    T = len(tokens)
    L, Hkv, Dh = CFG.num_layers, CFG.num_kv_heads, CFG.head_dim
    pool_k = jnp.zeros((L, Hkv, 16, 8, Dh), CFG.dtype)
    pool_v = jnp.zeros_like(pool_k)
    tok = jnp.asarray(tokens, jnp.int32)[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    idx = jnp.arange(T, dtype=jnp.int32)[None]
    lo, _, _ = llama.forward(params, CFG, tok, pos, pool_k, pool_v,
                             idx, idx, pos, jnp.ones((1, T), bool))
    # padded read view: 64 slots, only first T valid
    ridx = jnp.zeros((1, 64), jnp.int32).at[0, :T].set(jnp.arange(T))
    rpos = jnp.zeros((1, 64), jnp.int32).at[0, :T].set(jnp.arange(T))
    rvalid = jnp.zeros((1, 64), bool).at[0, :T].set(True)
    lp, _, _ = llama.forward(params, CFG, tok, pos,
                             jnp.zeros_like(pool_k), jnp.zeros_like(pool_v),
                             idx, ridx, rpos, rvalid)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lp))


def test_hf_config_mapping():
    cfg = llama.LlamaConfig.from_hf_config({
        "vocab_size": 128256, "hidden_size": 4096, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "intermediate_size": 14336, "rope_theta": 500000.0,
        "max_position_embeddings": 8192, "rms_norm_eps": 1e-5,
    })
    assert cfg.head_dim == 128 and cfg.num_kv_heads == 8


def test_llama3_rope_scaling_applies():
    base = llama.preset("tiny-byte")
    scaled = llama.preset("tiny-byte", rope_scaling={
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 64})
    f_base = llama._rope_inv_freq(base)
    f_scaled = llama._rope_inv_freq(scaled)
    assert not np.allclose(f_base, f_scaled)


# ---------------------------------------------------------------------------
# the four forwards share one decoder layer (llama.layer_in / layer_out):
# each must agree with the sequential ``forward`` for the model families
# whose layer terms differ. Every forward here runs under jax.jit (a closure
# over cfg and mesh, as the engine's programs are): eagerly the pp case
# alone costs 90 s. One set of seeded float32 parameters per preset.
# ---------------------------------------------------------------------------
PAGE = 8


@pytest.fixture(scope="module")
def preset_params():
    made = {}

    def get(name):
        if name not in made:
            cfg = llama.preset(name, dtype=jnp.float32)
            made[name] = cfg, llama.init_params(cfg, jax.random.PRNGKey(7))
        return made[name]
    return get


def _pools(cfg, n_pages):
    z = jnp.zeros((cfg.num_layers, cfg.num_kv_heads, n_pages, PAGE,
                   cfg.head_dim), cfg.dtype)
    return z, jnp.zeros_like(z)


def _lanes(page_tables):
    """-> (token slot, position) of every slot of each lane's pages, both
    [B, S]: what ``forward`` addresses by, from what ``forward_decode``
    addresses by."""
    pt = jnp.asarray(page_tables, jnp.int32)
    t = jnp.arange(pt.shape[1] * PAGE, dtype=jnp.int32)
    return (pt[:, t // PAGE] * PAGE + t % PAGE,
            jnp.broadcast_to(t, (pt.shape[0], t.shape[0])))


def _decode_agrees(cfg, params):
    """Prefill 12 tokens of two lanes through ``forward``, then 8 steps of
    ``forward_decode`` (page tables) against ``forward`` (token slots) on
    the same pools: logits and both pools after every step."""
    B, T0 = 2, 12
    fwd = jax.jit(lambda p, *a: llama.forward(p, cfg, *a))
    dec = jax.jit(lambda p, *a: llama.forward_decode(p, cfg, *a))
    tokens = np.random.RandomState(0).randint(1, 250, (B, T0 + 8))
    pt = jnp.asarray([[2, 5, 1], [4, 3, 6]], jnp.int32)     # pages, per lane
    slots, rpos = _lanes(pt)
    k, v = _pools(cfg, 7)
    _, k, v = fwd(params, jnp.asarray(tokens[:, :T0], jnp.int32),
                  rpos[:, :T0], k, v, slots[:, :T0], slots, rpos, rpos < T0)
    for n in range(T0, T0 + 8):
        tok = jnp.asarray(tokens[:, n], jnp.int32)
        lengths = jnp.full((B,), n + 1, jnp.int32)
        lg_d, k_d, v_d = dec(params, tok, k, v, pt, lengths)
        lg_f, k, v = fwd(params, tok[:, None], rpos[:, n:n + 1], k, v,
                         slots[:, n:n + 1], slots, rpos, rpos <= n)
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_f),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(k_d), np.asarray(k), atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_d), np.asarray(v), atol=1e-5)


def _pp_agrees(cfg, params):
    """``forward_pp`` at pp = 2, one microbatch, xla attention, against
    ``forward``: logits and the K pool the stages wrote."""
    from jax.sharding import Mesh

    from dynamo_tpu.parallel.mesh import AXIS_PP
    mesh = Mesh(np.array(jax.devices()[:2]), (AXIS_PP,))
    B, T = 2, 8
    tokens = jnp.asarray(np.random.RandomState(1).randint(1, 250, (B, T)),
                         jnp.int32)
    slots, rpos = _lanes([[1, 2], [3, 4]])
    args = (tokens, rpos[:, :T], *_pools(cfg, 5), slots[:, :T], slots, rpos,
            rpos < T)
    lg, k, _ = jax.jit(lambda p, *a: llama.forward(p, cfg, *a))(params, *args)
    lg_pp, k_pp, _ = jax.jit(
        lambda p, *a: llama.forward_pp(p, cfg, *a, mesh))(
        params, *(a[None] if a.ndim < 5 else a for a in args))
    np.testing.assert_allclose(np.asarray(lg_pp[0]), np.asarray(lg),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(k_pp), np.asarray(k), atol=1e-5)


def _pager_agrees(cfg, params):
    """The pager's segmented forward through the engine, token identity
    against the unpaged engine on the same seeded weights: a prompt of 8x
    the device budget (tests/test_kvpage.py pins 16x on tiny-byte)."""
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions

    prompt = [(i * 7 + 3) % 251 for i in range(8 * 4 * 16 + 5)]
    common = dict(model=cfg, max_batch=1, page_size=16, prefill_chunk=32,
                  decode_steps=4)

    def serve(**kw):
        core = EngineCore(JaxEngineConfig(**common, **kw))
        try:
            core.submit("s", BackendInput(
                token_ids=prompt, stop=StopConditions(max_tokens=6)))
            got = []
            while not (got and got[-1].finish is not None):
                got += core.step()
            assert all(so.error is None for so in got)
            return [so.token for so in got], core
        finally:
            core.close()

    ref, _ = serve(max_context=1024, kvpage_budget=0)
    toks, core = serve(max_context=128, host_cache_blocks=96,
                       kvpage_budget=4, kvpage_seg_pages=4,
                       kvpage_prefetch=2, kvpage_max_context=1024)
    assert toks == ref and len(toks) == 6
    assert core.kvpager.pager.pageins > 0


@pytest.mark.parametrize("preset,agrees", [
    ("tiny-qwen", _decode_agrees),      # q/k/v bias
    ("tiny-gemma2", _decode_agrees),    # sandwich norms, softcap, sliding
    ("tiny-gemma3", _decode_agrees),    # q/k norm, two rotary bases
    ("tiny-moe", _decode_agrees),       # routed feed-forward
    ("tiny-qwen", _pp_agrees),          # bias through a pipeline stage
    ("tiny-qwen", _pager_agrees),       # bias through the pager's programs
], ids=lambda p: p if isinstance(p, str) else p.__name__.strip("_"))
def test_forwards_agree(preset_params, preset, agrees):
    agrees(*preset_params(preset))


# ---------------------------------------------------------------------------
# a decode step whose paged kernel writes the new K/V rows itself against the
# same step with ``kv_write`` in front of the kernel (in the interpreter)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset,over", [
    ("tiny-qwen", {"head_dim": 128}),                   # bias, rows of a tile
    ("tiny-gemma2", {"head_dim": 128}),                 # softcap, sliding
    ("tiny-keye", {}),                                  # a selection beside it
    ("tiny-byte", {"head_dim": 64, "kv_fold": 2}),      # two tokens a row
    ("tiny-byte", {"head_dim": 64}),                    # ... stored unfolded
], ids=["qwen-128", "gemma2-128", "keye-128", "fold2", "unfolded-64"])
def test_decode_with_the_write_in_the_kernel_is_kv_write_then_the_kernel(
        monkeypatch, preset, over):
    """Four chained decode steps of three lanes (one of them the engine's
    inactive lane: length 1, page 0) through ``forward_decode``: the same
    tokens, the same logits and the same pools, bit for bit. A pool the
    kernel cannot write (64-lane rows stored unfolded) keeps ``kv_write``
    by what ``kernel_writes`` observes, and is then the same program."""
    page = 16
    cfg = llama.preset(preset, **over)
    fold = cfg.kv_fold
    writes = llama.kernel_writes(None, "pallas", cfg.k_store_dim, fold)
    assert writes is (over != {"head_dim": 64})
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_pages = 7
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    lead = (cfg.num_layers, cfg.num_kv_heads, n_pages, page // fold)
    pools = {"k": jax.random.normal(ks[0], (*lead, fold * cfg.k_store_dim),
                                    jnp.float32).astype(cfg.dtype),
             "v": jax.random.normal(ks[1], (*lead, fold * cfg.v_dim),
                                    jnp.float32).astype(cfg.dtype)}
    if cfg.has_indexer:
        pools["i"] = jax.random.normal(
            ks[2], llama.index_pool_shape(cfg, n_pages, page),
            jnp.float32).astype(cfg.dtype)
    pt = jnp.asarray([[2, 5, 1], [4, 3, 6], [0, 0, 0]], jnp.int32)

    def serve():
        dec = jax.jit(lambda p, t, k, v, ln, *i: llama.forward_decode(
            p, cfg, t, k, v, pt, ln, attn_impl="pallas",
            **({"i_pool": i[0]} if i else {})))
        tok = jnp.asarray([5, 7, 9], jnp.int32)
        ln = jnp.asarray([15, 31, 1], jnp.int32)   # ... 16/17 and 32/33 next
        state, toks = list(pools.values()), []
        for _ in range(4):
            lg, *state = dec(params, tok, *state[:2], ln, *state[2:])
            tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
            toks.append(np.asarray(tok))
            ln = ln + 1
        return (np.stack(toks), np.asarray(lg, np.float32),
                *(np.asarray(a, np.float32) for a in state))

    got = serve()
    monkeypatch.setattr(llama, "kernel_writes", lambda *a: False)
    want = serve()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("preset,over", [
    ("tiny-qwen", {"head_dim": 128}),
    ("tiny-gemma2", {"head_dim": 128}),
    ("tiny-keye", {}),
    ("tiny-byte", {"head_dim": 64, "kv_fold": 2}),
    ("tiny-byte", {"head_dim": 64}),                    # kv_write scatters
], ids=["qwen-128", "gemma2-128", "keye-128", "fold2", "unfolded-64"])
def test_a_lane_the_step_does_not_serve_is_skipped_by_the_kernel(
        monkeypatch, preset, over):
    """Two chained decode steps of four lanes, two of them the engine's
    unserved lanes (length 1, an all-zero table), through ``forward_decode``
    on the paged kernel (interpreter), scratch page 0 NaN in both pools. With
    ``active`` the kernel is handed those lanes as length 0 and skips them:
    every logit of every lane is finite (page 0 was never read) and a kernel
    that writes leaves page 0 as it was. The served lanes' tokens, logits
    and pages are, bit for bit, those of the program as it was before the
    kernel skipped (the same step, the kernel handed length 1 for those
    lanes, over a page 0 that can be read)."""
    page = 16
    cfg = llama.preset(preset, **over)
    fold = cfg.kv_fold
    writes = llama.kernel_writes(None, "pallas", cfg.k_store_dim, fold)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_pages = 7
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    lead = (cfg.num_layers, cfg.num_kv_heads, n_pages, page // fold)
    pools = [jax.random.normal(ks[0], (*lead, fold * cfg.k_store_dim),
                               jnp.float32).astype(cfg.dtype),
             jax.random.normal(ks[1], (*lead, fold * cfg.v_dim),
                               jnp.float32).astype(cfg.dtype)]
    if cfg.has_indexer:
        pools.append(jax.random.normal(
            ks[2], llama.index_pool_shape(cfg, n_pages, page),
            jnp.float32).astype(cfg.dtype))
    poisoned = [p.at[:, :, 0].set(jnp.nan) for p in pools[:2]] + pools[2:]
    pt = jnp.asarray([[0, 0, 0], [2, 5, 1], [0, 0, 0], [4, 3, 6]], jnp.int32)
    served = np.asarray([False, True, False, True])

    def serve(pools):
        dec = jax.jit(lambda p, t, k, v, ln, *i: llama.forward_decode(
            p, cfg, t, k, v, pt, ln, attn_impl="pallas",
            active=jnp.asarray(served), **({"i_pool": i[0]} if i else {})))
        tok = jnp.asarray([0, 5, 0, 7], jnp.int32)
        ln = jnp.asarray([1, 16, 1, 31], jnp.int32)
        state, out = list(pools), []
        for _ in range(2):
            lg, *state = dec(params, tok, *state[:2], ln, *state[2:])
            tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
            out += [np.asarray(tok), np.asarray(lg, np.float32)]
            ln = jnp.where(served, ln + 1, 1)     # as the engine: 1 again
        return out, [np.asarray(a, np.float32) for a in state]

    got, got_pools = serve(poisoned)
    from dynamo_tpu.ops import attention as A

    skipping = A.paged_attention
    monkeypatch.setattr(
        A, "paged_attention", lambda q, k, v, pt, ln, *a, **kw: skipping(
            q, k, v, pt, jnp.maximum(ln, 1), *a, **kw))
    want, want_pools = serve(pools)
    for g, w in zip(got, want):
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g[served], w[served])
        # (the other lanes attended over page 0 there, over nothing here)
        assert g.dtype != np.float32 or (g[~served] != w[~served]).any()
    for g, w in zip(got_pools[:2], want_pools[:2]):
        np.testing.assert_array_equal(g[:, :, 1:], w[:, :, 1:])
        if writes:
            assert np.isnan(g[:, :, 0]).all()
