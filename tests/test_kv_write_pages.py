"""A prefill chunk's K/V write by page (``llama.kv_write_pages``) against the
row scatter (``llama.kv_write``): the accessor alone on every pool geometry
the configurations have, the engine's two chunk programs served side by side
on the tiny presets, what the engine counts, and the lowered program's text.

A chunk the engine cuts starts on a page's first slot, so lane ``b``'s tokens
``j * page ..`` lie in the page of ``write_idx[b, j * page]``, in order; the
page form writes that run as one window a head. It also writes the slots of
the run that hold no real token (scratch page 0 for a run of padding, the
lane's own unsealed page past its last token), which no read finds valid:
the comparisons below are on the VALID slots and on every page neither form
may touch.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
from dynamo_tpu.models import llama
from dynamo_tpu.utils import jaxenv

PAGE = 64

# name: (heads, row width as written, tokens folded into a pool row)
POOLS = {
    "plain": (2, 128, 1),
    "fold2": (2, 64, 2),            # 64-lane rows, two tokens a pool row
    "fold8": (2, 16, 8),            # granite's tiny preset: eight
    "k_wide": (2, 256, 1),          # K rows stored wider than head_dim
    "latent": (1, 128, 1),          # one row a token for all heads
    "window": (4, 128, 1),          # a window pool's own head count
}
# name: (chunk bucket C, real tokens of lane 0)
CHUNKS = {
    "C32": (32, 32),                # shorter than a page: one run of 32
    "C64": (64, 64),
    "C256": (256, 256),
    "short_last": (256, 150),       # ends mid-page; a run of padding too
    "one_token": (64, 1),
}


def _chunk(C, count, lanes=2):
    """write_idx [lanes, C] of a chunk whose lane 0 holds ``count`` real
    tokens from a page's first slot and whose other lanes are padding."""
    pages = np.array([3, 9, 5, 7])          # out of order, as a pool leases
    t = np.arange(count)
    w = np.zeros((lanes, C), np.int32)
    w[0, :count] = pages[t // PAGE] * PAGE + t % PAGE
    return w, pages[:-(-count // PAGE)]


@pytest.mark.parametrize("chunk", list(CHUNKS))
@pytest.mark.parametrize("pool_kind", list(POOLS))
def test_page_runs_write_what_the_rows_write(pool_kind, chunk):
    """Both forms leave the same rows at every slot that holds a real token,
    and neither touches a page that is not the lane's or scratch page 0 (a
    padded lane's runs and a run of padding land there)."""
    H, Dh, f = POOLS[pool_kind]
    C, count = CHUNKS[chunk]
    L, NP, layer = 2, 12, 1
    rng = np.random.default_rng(hash((pool_kind, chunk)) % 2**31)
    pool = jnp.asarray(rng.standard_normal(
        (L, H, NP, PAGE // f, f * Dh)).astype(np.float32))
    w, own = _chunk(C, count)
    rows = jnp.asarray(rng.standard_normal(
        (w.size, H, Dh)).astype(np.float32))
    flat = jnp.asarray(w.reshape(-1))
    by_row = llama.kv_write(pool, layer, flat // PAGE, flat % PAGE, rows)
    pages = jnp.asarray(w[:, ::PAGE] // PAGE)
    assert pages.shape == (2, -(-C // PAGE))
    by_page = jax.jit(llama.kv_write_pages, static_argnums=1)(
        pool, layer, pages, rows)
    assert by_page.shape == pool.shape and by_page.dtype == pool.dtype

    valid = jnp.asarray(w[0, :count])
    got = llama.kv_rows(by_page, layer, valid // PAGE, valid % PAGE, f)
    np.testing.assert_array_equal(got, rows[:count])
    np.testing.assert_array_equal(
        got, llama.kv_rows(by_row, layer, valid // PAGE, valid % PAGE, f))
    others = [p for p in range(NP) if p not in (0, *own)]
    for wrote in (by_row, by_page):
        np.testing.assert_array_equal(wrote[:, :, others], pool[:, :, others])
        np.testing.assert_array_equal(wrote[0], pool[0])   # the other layer
    assert np.isfinite(np.asarray(by_page)).all()


def test_index_keys_fold_into_page_runs():
    """An indexer's keys (one head, two tokens a 128-lane row) go through
    the same accessor: what ``index_write`` leaves at the real tokens."""
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal(
        (2, 1, 12, PAGE // 2, 128)).astype(np.float32))
    w, _ = _chunk(256, 150)
    keys = jnp.asarray(rng.standard_normal((w.size, 64)).astype(np.float32))
    flat = jnp.asarray(w.reshape(-1))
    by_row = llama.index_write(pool, 1, flat // PAGE, flat % PAGE, keys)
    by_page = llama.kv_write_pages(
        pool, 1, jnp.asarray(w[:, ::PAGE] // PAGE), keys[:, None])
    valid = jnp.asarray(w[0, :150])
    read = lambda p: llama.kv_rows(p, 1, valid // PAGE, valid % PAGE, 2)
    np.testing.assert_array_equal(read(by_page), read(by_row))
    np.testing.assert_array_equal(read(by_page)[:, 0], keys[:150])


def test_rows_that_fill_no_page_runs_are_refused():
    pool = jnp.zeros((1, 2, 4, PAGE // 8, 128))
    with pytest.raises(ValueError, match="page runs"):
        llama.kv_write_pages(pool, 0, jnp.zeros((1, 1), jnp.int32),
                             jnp.zeros((4, 2, 16)))    # 4 tokens, fold 8
    with pytest.raises(ValueError, match="page runs"):
        llama.kv_write_pages(pool, 0, jnp.zeros((1, 3), jnp.int32),
                             jnp.zeros((64, 2, 16)))   # 64 rows, 3 runs


# ---------------------------------------------------------------------------
# through the engine: the tiny presets, the two chunk programs side by side
# ---------------------------------------------------------------------------

def _tiny(module):
    import importlib
    return llama.LlamaConfig.from_hf_config(
        importlib.import_module(module).TINY, dtype=jnp.float32)


MODELS = {
    # name: (model, engine arguments)
    "qwen2": (lambda: llama.preset("tiny-qwen"),
              dict(page_size=16, prefill_chunk=32, attn_impl="xla")),
    "granite": (lambda: _tiny("test_granite_hybrid"),        # fold 8, state
                dict(page_size=16, prefill_chunk=16, attn_impl="pallas")),
    "mimo": (lambda: _tiny("test_mimo_v2_flash"),            # window pools
             dict(page_size=8, prefill_chunk=16, attn_impl="pallas")),
    "keye": (lambda: _tiny("test_keye_vl2"),                 # index keys
             dict(page_size=8, prefill_chunk=16, attn_impl="pallas")),
    "deepseek": (lambda: _tiny("test_deepseek_v2"),          # latent rows
                 dict(page_size=8, prefill_chunk=16, attn_impl="xla")),
}


def _writes(core):
    return dict((k[0], v) for k, v in
                core.stage.engine_prefill_kv_writes._values.items())


def _moved(core, before):
    return {k: v - before.get(k, 0.0) for k, v in _writes(core).items()
            if v != before.get(k, 0.0)}


def _serve(core, seq_id, prompt, n=6, **kw):
    core.submit(seq_id, BackendInput(token_ids=list(prompt),
                                     stop=StopConditions(max_tokens=n), **kw))
    outs = []
    for _ in range(400):
        outs += [so for so in core.step() if so.seq_id == seq_id]
        if outs and outs[-1].finish is not None:
            assert outs[-1].error is None, outs[-1].error
            return [o.token for o in outs], [o.token_logprob for o in outs]
    raise AssertionError("did not finish")


@pytest.mark.parametrize("name", list(MODELS))
def test_engine_serves_the_same_by_page_and_by_row(name, monkeypatch):
    """A prompt of three chunks (the last one short), greedy: the tokens and
    log-probabilities served with the chunks written by page are those
    served with the form forced to rows, and the counter says which form
    each of the three dispatches took."""
    model, args = MODELS[name]
    core = EngineCore(JaxEngineConfig(
        model=model(), max_batch=2, max_context=96, decode_steps=2,
        enable_prefix_reuse=False, **args))
    assert core.prefill_kv_write == "page"
    C = core.cfg.prefill_chunk
    prompt = np.random.default_rng(3).integers(0, 259, 2 * C + C // 2 + 1)
    before = _writes(core)
    by_page = _serve(core, "p", prompt.tolist())
    assert _moved(core, before) == {"page": 3.0}

    monkeypatch.setattr(core, "prefill_kv_write", "row")
    before = _writes(core)
    by_row = _serve(core, "r", prompt.tolist())
    assert _moved(core, before) == {"row": 3.0}
    assert by_page == by_row
    forms = {k[-1] for k in core._prefill_batch_fns}
    assert forms == {"page", "row"}


def test_a_chunk_that_starts_mid_page_runs_the_row_form():
    """The engine checks on the host, chunk by chunk, what the page form
    needs: a chunk cut shorter than a page leaves the next one starting
    mid-page, and that dispatch (alone) takes the row form; an engine whose
    chunks are no whole pages reports ``row`` and builds nothing else."""
    core = EngineCore(JaxEngineConfig(
        model=llama.preset("tiny-byte"), page_size=8, max_batch=2,
        max_context=64, prefill_chunk=16, enable_prefix_reuse=False))
    assert core._chunk_form(16) == "page"
    assert core._chunk_form(16, aligned=False) == "row"
    assert core._chunk_form(16, mm=True) == "row"
    slot_of = lambda: next(s for s in core.slots if s is not None)
    core.submit("a", BackendInput(token_ids=list(range(1, 38)),
                                  stop=StopConditions(max_tokens=2)))
    before = _writes(core)
    core.step()
    assert _moved(core, before) == {"page": 1.0}
    slot_of().prefill_done -= 4            # as if the chunk had been cut short
    before = _writes(core)
    core.step()
    assert _moved(core, before) == {"row": 1.0}

    odd = EngineCore(JaxEngineConfig(
        model=llama.preset("tiny-byte"), page_size=8, max_batch=2,
        max_context=64, prefill_chunk=12))
    assert odd.prefill_kv_write == "row" and odd._chunk_form(12) == "row"


def test_an_image_wave_counts_row():
    from test_multimodal import IMG, MM_TOK, image, run, vlm_core

    core = vlm_core(prefill_chunk=8)
    assert core.prefill_kv_write == "page"
    prompt = [3] * 6 + [IMG] * MM_TOK + [8, 9, 10, 11, 12, 13]
    before = _writes(core)
    toks, err = run(core, "img", prompt, [image(1)])
    assert err is None and len(toks) == 4
    moved = _moved(core, before)
    assert set(moved) == {"row"} and moved["row"] >= 2
    before = _writes(core)
    run(core, "txt", [3, 4, 5, 6, 7, 8, 9, 10, 11], None)
    assert _moved(core, before) == {"page": 2.0}


def test_a_verify_round_counts_row():
    from test_jax_engine import drain, make_cfg, req

    core = EngineCore(make_cfg(max_batch=2, spec="ngram", spec_k=2))
    before = _writes(core)
    core.submit("s", req([5, 6, 7, 5, 6, 7, 5, 6], max_tokens=6))
    drain(core, ["s"])
    moved = _moved(core, before)
    assert moved.pop("page") == 1.0         # the prompt's one chunk
    assert moved == {"row": float(core.spec_dispatch_total)}
    assert core.spec_dispatch_total >= 1


def test_engine_info_names_the_form():
    from dynamo_tpu.engine.engine import JaxEngine

    eng = JaxEngine(JaxEngineConfig(
        model=llama.preset("tiny-byte"), page_size=8, max_batch=2,
        max_context=64, prefill_chunk=16))
    try:
        text = eng.core.stage.registry.render()
    finally:
        eng.shutdown()
    assert 'prefill_kv_write="page"' in text


# ---------------------------------------------------------------------------
# the program's text
# ---------------------------------------------------------------------------

_SCATTER = re.compile(
    r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>), (tensor<[^>]*>), '
    r'(tensor<[^>]*>)\)[^\n]*loc\((#loc\d+)\)', re.S)


def _kv_scatters(core, form, C, S):
    """(indices, updates) tensor types of every scatter under
    ``dynamo.kv_write`` in the engine's chunk program, lowered for a TPU."""
    fn = core._prefill_fn(1, C, S, form=form)
    zt = np.zeros((1, C), np.int32)
    zs = np.zeros((1, S), np.int32)
    s = core.sampling
    args = (core.params, zt, zt, core.k_pool, core.v_pool, zt, zs, zs,
            np.zeros((1, S), bool), np.zeros(1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32), s.key[jnp.asarray(np.zeros(1, np.int32))])
    was = {k: getattr(jax.config, k) for k in jaxenv.PROGRAM_LOCATIONS}
    for key, value in jaxenv.PROGRAM_LOCATIONS.items():
        jax.config.update(key, value)
    try:
        text = fn.jitted.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        for key, value in was.items():
            jax.config.update(key, value)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    return [(idx, upd) for _, idx, upd, loc in _SCATTER.findall(text)
            if "dynamo.kv_write" in names.get(loc, "")]


def test_the_lowered_chunk_program_scatters_page_windows():
    """Mistral's head shapes (8 KV heads of 128, a chunk of 256 over pages of
    64), two layers, lowered for a TPU: under ``dynamo.kv_write`` the page
    form holds one scatter a layer and pool of ``Hkv x C / page`` = 32
    windows of [64, 128] and no scatter of ``C x Hkv`` = 2,048 single rows;
    the row form, compiled on first use, still holds those."""
    core = EngineCore(JaxEngineConfig(
        model=llama.LlamaConfig(
            vocab_size=259, hidden_size=128, num_layers=2, num_heads=8,
            num_kv_heads=8, head_dim=128, intermediate_size=128),
        page_size=PAGE, max_batch=2, max_context=512, prefill_chunk=256,
        num_pages=12, attn_impl="xla"))
    dt = "bf16" if core.cfg.model.dtype == jnp.bfloat16 else "f32"
    by_page = _kv_scatters(core, "page", 256, 512)
    assert by_page == [("tensor<8x4x3xi32>", f"tensor<8x4x64x128x{dt}>")] * 4
    by_row = _kv_scatters(core, "row", 256, 512)
    assert by_row == [("tensor<256x8x4xi32>", f"tensor<256x8x128x{dt}>")] * 4
    # the 32-token bucket: one run of 32 rows from the page's first slot
    # (the slot, 0, is the index vector's fourth component)
    short = _kv_scatters(core, "page", 32, 512)
    assert short == [("tensor<8x1x4xi32>", f"tensor<8x1x32x128x{dt}>")] * 4
