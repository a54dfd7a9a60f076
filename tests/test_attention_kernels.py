"""Pallas attention kernels vs. the dense XLA reference.

Runs in interpreter mode on CPU — the same kernel code the TPU compiles.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import attend
from dynamo_tpu.ops.attention import flash_attention, paged_attention


def _dense_ref(q, k, v, q_pos, k_pos, k_valid):
    mask = k_valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])
    return attend(q, k, v, mask)


def _dense_ref_full(q, k, v, q_pos, k_pos, k_valid, scale=None,
                    softcap=None, window=None):
    mask = k_valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    return attend(q, k, v, mask, scale=scale, softcap=softcap)


def _dense_paged_ref(q, k_pages, v_pages, page_tables, lengths, **kw):
    """Dense reference over ONE layer's [Hkv, n_pages, page, Dh] pool."""
    Hkv, _, page, Dh = k_pages.shape
    B, S = q.shape[0], page_tables.shape[1] * page
    rows = []
    for b in range(B):
        ctx_k = (k_pages[:, page_tables[b]].transpose(1, 2, 0, 3)
                 .reshape(S, Hkv, Dh))
        ctx_v = (v_pages[:, page_tables[b]].transpose(1, 2, 0, 3)
                 .reshape(S, Hkv, Dh))
        k_pos = jnp.arange(S, dtype=jnp.int32)[None]
        q_pos = jnp.full((1, 1), lengths[b] - 1, jnp.int32)
        rows.append(_dense_ref_full(
            q[b][None, None], ctx_k[None], ctx_v[None], q_pos, k_pos,
            k_pos < lengths[b], **kw)[0, 0])
    return jnp.stack(rows)


@pytest.mark.parametrize("B,T,S,Hq,Hkv,Dh", [
    (1, 32, 128, 4, 2, 16),
    (2, 64, 128, 8, 8, 32),   # MHA (G=1)
    (1, 16, 64, 4, 1, 16),    # extreme GQA
])
def test_flash_matches_dense(B, T, S, Hq, Hkv, Dh):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32).astype(jnp.bfloat16)
    # queries are a chunk at positions [ctx, ctx+T); context covers [0, n)
    ctx = S // 2 - T // 2
    n = ctx + T
    q_pos = jnp.broadcast_to(jnp.arange(ctx, ctx + T, dtype=jnp.int32), (B, T))
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    k_valid = k_pos < n

    got = flash_attention(q, k, v, q_pos, k_pos, k_valid, interpret=True)
    want = _dense_ref(q, k, v, q_pos, k_pos, k_valid)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


def test_flash_fully_padded_rows_are_finite():
    B, T, S, Hq, Hkv, Dh = 1, 32, 64, 4, 2, 16
    q = jnp.ones((B, T, Hq, Dh), jnp.bfloat16)
    k = jnp.ones((B, S, Hkv, Dh), jnp.bfloat16)
    v = jnp.ones((B, S, Hkv, Dh), jnp.bfloat16)
    q_pos = jnp.zeros((B, T), jnp.int32)
    k_pos = jnp.arange(S, dtype=jnp.int32)[None]
    k_valid = jnp.zeros((B, S), bool)  # nothing valid at all
    out = flash_attention(q, k, v, q_pos, k_pos, k_valid, interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()


@pytest.mark.parametrize("B,Hq,Hkv,Dh,page,P", [
    (2, 4, 2, 16, 16, 4),
    (3, 8, 8, 32, 8, 3),
])
def test_paged_matches_dense(B, Hq, Hkv, Dh, page, P):
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k_pages = jax.random.normal(
        ks[1], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    v_pages = jax.random.normal(
        ks[2], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    # sequence b owns pages [1 + b*P, 1 + (b+1)*P), variable lengths
    page_tables = (jnp.arange(P, dtype=jnp.int32)[None]
                   + jnp.arange(B, dtype=jnp.int32)[:, None] * P + 1)
    lengths = jnp.asarray(
        [min(page * P, 3 + b * (page + 1)) for b in range(B)], jnp.int32)

    got = paged_attention(q, k_pages, v_pages, page_tables, lengths,
                          interpret=True)

    want = _dense_paged_ref(q, k_pages, v_pages, page_tables, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


# tests/test_chip_compile.py's GEOMETRIES x VARIANTS (what the v5e compiler
# is shown), cut to what the interpreter runs in a second: a quarter of the
# heads at the same group and widths, a window the short contexts straddle
_GEOMETRIES = [(8, 2, 64), (8, 2, 128), (4, 2, 256),
               (4, 2, 16)]      # ... and the tiny presets' rows: fold 8
_VARIANTS = {
    "plain": {},
    "window": {"window": 40},
    "softcap": {"softcap": 50.0, "scale": 0.0625},
}


@pytest.mark.parametrize("variant", list(_VARIANTS))
@pytest.mark.parametrize("geom", _GEOMETRIES,
                         ids=lambda g: "x".join(map(str, g)))
def test_the_public_entry_matches_dense(geom, variant):
    """``paged_attention`` as the decode step calls it (no kernel picked, no
    block size given: the one kernel at its own ``PAGES_PER_BLOCK``) over a
    table wider than a block, a lane of no tokens among the batch: the
    served lanes are the dense reference's, the empty one zeros."""
    Hq, Hkv, Dh = geom
    B, page, P = 4, 16, 10
    kw = _VARIANTS[variant]
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.bfloat16)
    k_pages, v_pages = (jax.random.normal(
        key, (Hkv, n_pages, page, Dh), jnp.bfloat16) for key in ks[1:])
    lengths = jnp.asarray([page * P, 0, 1, 7 * page + 3], jnp.int32)
    tables = (1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
              ) * (lengths > 0)[:, None]
    got = np.asarray(paged_attention(q, k_pages, v_pages, tables, lengths,
                                     **kw), np.float32)
    served = np.asarray(lengths) > 0
    assert not got[~served].any()
    want = _dense_paged_ref(q[served], k_pages, v_pages, tables[served],
                            lengths[served], **kw)
    np.testing.assert_allclose(got[served], np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("Dh,Dv,page,kw,says", [
    (16, 16, 16, {"fold": 4}, "stored folded by 4 at head_dim 16"),
    (24, 24, 8, {}, "page size 8 not divisible by fold 5"),
    (16, 16, 8, {"keep": True}, "a selection only at head_dim >= 128"),
    (64, 32, 8, {}, "V rows of their own width"),
    (64, 64, 8, {"latent": True}, "latent attention reads unfolded rows"),
], ids=["fold-not-the-kernels", "page-not-whole-rows", "selection-narrow",
        "v-width-narrow", "latent-narrow"])
def test_the_kernel_refuses_a_geometry_it_cannot_tile(Dh, Dv, page, kw, says):
    """The rules of ``_paged_attention_tpu`` hold on every platform, the
    interpreter included: a page is a whole number of 128-lane rows, a pool
    is stored folded by ``128 // Dh`` or not at all, and a selection, V rows
    of their own width and latent rows need K rows of a lane tile."""
    B, Hq, Hkv, P = 2, 4, 2, 2
    kw = dict(kw)
    f = kw.get("fold", 1)
    k = jnp.zeros((Hkv, 5, page // f, f * Dh), jnp.bfloat16)
    v = jnp.zeros((Hkv, 5, page // f, f * Dv), jnp.bfloat16)
    if kw.get("keep"):
        kw["keep"] = jnp.ones((B, P * page), bool)
    if kw.get("latent"):
        kw["latent"] = jnp.zeros((B, Hq, Dv), jnp.bfloat16)
    with pytest.raises(ValueError, match=says):
        paged_attention(jnp.zeros((B, Hq, Dh), jnp.bfloat16), k, v,
                        jnp.ones((B, P), jnp.int32),
                        jnp.full((B,), 3, jnp.int32), **kw)


@pytest.mark.parametrize("L,kernel_writes", [
    (None, False), (3, False), (None, True), (3, True)])
def test_paged_inside_scan_with_donated_pool(L, kernel_writes):
    """The decode loop shape: pools carried through lax.scan and donated,
    every layer's row written in place (head index spelt out, as
    forward_decode does) and read by the kernel from the whole pool by
    layer index. ``L=None`` is the single-layer [Hkv, n_pages, page, Dh]
    form. ``kernel_writes``: the kernel (in the interpreter here) takes
    the rows as ``new`` and hands the pools back as its second and third
    result, aliased to its operands."""
    B, Hq, Hkv, Dh, page, P = 2, 4, 2, 16, 8, 2
    if kernel_writes:
        Dh = 128            # rows the kernel reads as stored
    n_pages = 8
    lead = () if L is None else (L,)
    # (scores of a fraction, at either width: the new rows must not take all
    # the softmax weight a bfloat16 can tell from one)
    q = jnp.full((B, Hq, Dh), 1.0 / Dh, jnp.bfloat16)
    k_pool = jnp.ones(lead + (Hkv, n_pages, page, Dh), jnp.bfloat16)
    v_pool = jnp.ones(lead + (Hkv, n_pages, page, Dh), jnp.bfloat16)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    hh = jnp.arange(Hkv)[None, :]

    @partial(jax.jit, donate_argnums=(1, 2))
    def run(q, k_pool, v_pool, pt, lengths):
        def body(carry, _):
            ln, kp, vp = carry
            outs = []
            for l in ([None] if L is None else range(L)):
                pos = ln - 1
                wp = jnp.take_along_axis(pt, (pos // page)[:, None], 1)
                at = ((hh, wp, (pos % page)[:, None]) if l is None
                      else (l, hh, wp, (pos % page)[:, None]))
                new = jnp.full((B, Hkv, Dh), 2.0, kp.dtype)
                layer = None if l is None else jnp.int32(l)
                if kernel_writes:
                    out, kp, vp = paged_attention(
                        q, kp, vp, pt, ln, layer, interpret=True,
                        new=(new, new))
                    outs.append(out)
                    continue
                kp, vp = kp.at[at].set(new), vp.at[at].set(new)
                outs.append(paged_attention(q, kp, vp, pt, ln, layer,
                                            interpret=True))
            return (ln + 1, kp, vp), jnp.stack(outs)
        (_, kp, vp), outs = jax.lax.scan(
            body, (lengths, k_pool, v_pool), None, length=3)
        return outs, kp, vp

    lowered = run.lower(q, k_pool, v_pool, pt, lengths)
    outs, kp, vp = run(q, k_pool, v_pool, pt, lengths)
    assert k_pool.is_deleted() and v_pool.is_deleted()
    assert "tf.aliasing_output" in lowered.as_text()
    assert outs.shape == (3, 1 if L is None else L, B, Hq, Dh)
    out = np.asarray(outs, np.float32)
    assert np.isfinite(out).all()
    # the rows written inside the scan are read back by the kernel: V holds
    # 1s and (t+1) 2s per lane at step t, attention weights are uniform
    # among equal keys, so the output lies strictly between 1 and 2
    assert (out > 1.0).all() and (out < 2.0).all()
    assert float(np.asarray(kp, np.float32).max()) == 2.0
    # three rows a lane and layer, every head, and nothing else
    assert int((np.asarray(kp, np.float32) == 2.0).all(-1).sum()) == (
        3 * B * Hkv * (L or 1))


@pytest.mark.parametrize("window,softcap,scale", [
    (24, None, None),            # gemma3-style sliding
    (None, 50.0, None),          # gemma2 softcap
    (24, 30.0, 1.0 / math.sqrt(24.0)),   # all three (gemma2 27b-style)
])
def test_flash_window_softcap_scale(window, softcap, scale):
    B, T, S, Hq, Hkv, Dh = 2, 32, 128, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32).astype(jnp.bfloat16)
    ctx = S // 2 - T // 2
    n = ctx + T
    q_pos = jnp.broadcast_to(jnp.arange(ctx, ctx + T, dtype=jnp.int32), (B, T))
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    k_valid = k_pos < n

    got = flash_attention(q, k, v, q_pos, k_pos, k_valid, interpret=True,
                          scale=scale, softcap=softcap, window=window)
    want = _dense_ref_full(q, k, v, q_pos, k_pos, k_valid, scale=scale,
                           softcap=softcap, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("window,softcap,scale", [
    (12, None, None),
    (None, 50.0, None),
    (12, 30.0, 1.0 / math.sqrt(24.0)),
    (1000, 50.0, None),          # window wider than any context: == causal
])
def test_paged_window_softcap_scale(window, softcap, scale):
    B, Hq, Hkv, Dh, page, P = 3, 4, 2, 16, 8, 4
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k_pages = jax.random.normal(
        ks[1], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    v_pages = jax.random.normal(
        ks[2], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    page_tables = (jnp.arange(P, dtype=jnp.int32)[None]
                   + jnp.arange(B, dtype=jnp.int32)[:, None] * P + 1)
    # lengths straddle window boundaries: shorter, equal, and longer than
    # the window (the page-range clamp only engages in the last case)
    lengths = jnp.asarray([5, 12, page * P], jnp.int32)

    got = paged_attention(q, k_pages, v_pages, page_tables, lengths,
                          interpret=True, scale=scale, softcap=softcap,
                          window=window)
    want = _dense_paged_ref(q, k_pages, v_pages, page_tables, lengths,
                            scale=scale, softcap=softcap, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("window,softcap,ppb", [
    (None, None, 2),             # baseline: full causal through the DMA path
    (12, None, 2),
    (12, 30.0, 3),               # ppb=3 forces a padded page table too
    (1000, 50.0, 2),             # window wider than any context
])
def test_paged_dma_variant_window_softcap(window, softcap, ppb):
    """The double-buffered DMA kernel (the TPU serving path) in interpret
    mode: window clamps the active block range at both ends — the prefetch
    chain must stay correctly linked when lanes start mid-table."""
    from dynamo_tpu.ops.attention import _paged_attention_tpu

    B, Hq, Hkv, Dh, page, P = 3, 4, 2, 16, 8, 4
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k_pages = jax.random.normal(
        ks[1], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    v_pages = jax.random.normal(
        ks[2], (Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    page_tables = (jnp.arange(P, dtype=jnp.int32)[None]
                   + jnp.arange(B, dtype=jnp.int32)[:, None] * P + 1)
    lengths = jnp.asarray([5, 12, page * P], jnp.int32)

    got = _paged_attention_tpu(
        q.reshape(B, Hkv, Hq // Hkv, Dh), k_pages[None], v_pages[None],
        jnp.zeros((1,), jnp.int32), page_tables, lengths,
        pages_per_block=ppb, softcap=softcap, window=window,
        interpret=True).reshape(B, Hq, Dh)
    want = paged_attention(q, k_pages, v_pages, page_tables, lengths,
                           interpret=True, softcap=softcap, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("Dh,G,window,softcap,scale,ppb", [
    (128, 4, None, None, None, 2),     # the cells' head geometry class
    (64, 4, None, None, None, 2),      # llama-3.2-1b: fold 2
    (128, 6, 12, None, None, 3),       # qwen2's group of 6, sliding, padded
    (64, 6, None, 50.0, None, 8),      # ppb wider than the table
    (64, 4, 12, 30.0, 1.0 / math.sqrt(24.0), 1),
    (16, 4, None, None, None, 2),      # fold 8 == page: one folded row
])
def test_paged_whole_pool_by_layer(Dh, G, window, softcap, scale, ppb):
    """The kernel contract forward_decode relies on: the WHOLE 5-D pool and
    a (traced) layer index give, for every layer, what the dense reference
    and the single-layer form give for that layer's slice."""
    from dynamo_tpu.ops.attention import _paged_attention_tpu

    L, B, Hkv, page, P = 3, 3, 2, 8, 4
    Hq = Hkv * G
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.float32).astype(jnp.bfloat16)
    k_pool = jax.random.normal(
        ks[1], (L, Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    v_pool = jax.random.normal(
        ks[2], (L, Hkv, n_pages, page, Dh), jnp.float32).astype(jnp.bfloat16)
    page_tables = (jnp.arange(P, dtype=jnp.int32)[None]
                   + jnp.arange(B, dtype=jnp.int32)[:, None] * P + 1)
    lengths = jnp.asarray([5, 12, page * P], jnp.int32)
    kw = dict(scale=scale, softcap=softcap, window=window)

    @jax.jit                        # the layer index is traced: one program
    def whole(layer):
        return _paged_attention_tpu(
            q.reshape(B, Hkv, G, Dh), k_pool, v_pool, layer.reshape(1),
            page_tables, lengths, pages_per_block=ppb, interpret=True,
            **kw).reshape(B, Hq, Dh)

    for l in range(L):
        got = np.asarray(whole(jnp.int32(l)), np.float32)
        want = _dense_paged_ref(q, k_pool[l], v_pool[l], page_tables,
                                lengths, **kw)
        np.testing.assert_allclose(got, np.asarray(want, np.float32),
                                   atol=3e-2, rtol=3e-2)
        per_layer = paged_attention(q, k_pool[l], v_pool[l], page_tables,
                                    lengths, interpret=True, **kw)
        np.testing.assert_allclose(got, np.asarray(per_layer, np.float32),
                                   atol=3e-2, rtol=3e-2)
    assert whole._cache_size() == 1


def test_paged_layer_needs_whole_pool():
    z = jnp.zeros((2, 3, 8, 16), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole 5-D pool"):
        paged_attention(jnp.zeros((1, 4, 16), jnp.bfloat16), z, z,
                        jnp.zeros((1, 1), jnp.int32),
                        jnp.ones((1,), jnp.int32), layer=1, interpret=True)


# ---------------------------------------------------------------------------
# The dma kernel that writes a decode step's new rows itself
# ---------------------------------------------------------------------------

# lengths: the new token on the first row of a fresh page, the last row of a
# page, rows 15 / 16 of a tile group, mid-page in a second block, the table's
# last row, an EMPTY lane (length 0), one token
_WRITE_LENGTHS = [33, 32, 16, 17, 77, 128, 0, 1]


@pytest.mark.parametrize("case,Dh,Dv,fold,window,selected,sunk,ppb,lanes", [
    ("fold1", 128, 128, 1, None, False, False, 2, 8),
    ("fold2", 64, 64, 2, None, False, False, 2, 8),
    ("fold1-window", 128, 128, 1, 40, False, False, 2, 8),
    ("fold2-window", 64, 64, 2, 40, False, False, 3, 8),
    ("dv", 256, 128, 1, None, False, False, 2, 8),       # mimo's full layers
    ("dv-window-sunk", 256, 128, 1, 40, False, True, 2, 8),  # ... window ones
    ("selected", 128, 128, 1, None, True, False, 2, 8),  # keye
    ("selected-sunk", 128, 128, 1, None, True, True, 4, 8),
    ("fold2-sunk", 64, 64, 2, None, False, True, 8, 8),  # ppb wider than P
    # the new rows reach the kernel eight lanes a block
    ("two-row-blocks", 128, 128, 1, None, False, False, 2, 16),
    ("no-multiple-of-8", 64, 64, 2, 40, False, False, 2, 12),
])
def test_the_kernel_that_writes_is_kv_write_then_the_kernel(
        case, Dh, Dv, fold, window, selected, sunk, ppb, lanes):
    """``new`` rows handed to the dma kernel (interpreter) against
    ``kv_write`` followed by the kernel that does not write: the SAME
    attention output and the SAME pools, bit for bit, every variant a cell
    runs. Excepted and named: the empty lane's scratch row. ``kv_write``
    takes position -1 for it and puts its rows at the page table's LAST
    entry, offset page - 1; the kernel skips a lane of no tokens: exact
    zeros out, nothing written."""
    from dynamo_tpu.models.llama import kv_write
    from dynamo_tpu.ops.attention import _paged_attention_tpu

    L, layer, Hkv, G, page, P = 2, 1, 2, 2, 32, 4
    lanes = (_WRITE_LENGTHS * 2)[:lanes]
    B = len(lanes)
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(23), 7)
    bf = jnp.bfloat16

    def rand(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(bf)

    q = rand(ks[0], B, Hkv, G, Dh)
    k_pool = rand(ks[1], L, Hkv, n_pages, page // fold, fold * Dh)
    v_pool = rand(ks[2], L, Hkv, n_pages, page // fold, fold * Dv)
    k_new, v_new = rand(ks[3], B, Hkv, Dh), rand(ks[4], B, Hkv, Dv)
    page_tables = (jnp.arange(P, dtype=jnp.int32)[None]
                   + jnp.arange(B, dtype=jnp.int32)[:, None] * P + 1)
    lengths = jnp.asarray(lanes, jnp.int32)
    kw = dict(pages_per_block=ppb, window=window, interpret=True,
              stored_fold=fold)
    if selected:
        kw["keep"] = jax.random.bernoulli(ks[5], 0.5, (B, P * page))
    if sunk:
        kw["sink"] = jax.random.normal(ks[6], (Hkv * G,), jnp.float32)
    ly = jnp.asarray([layer], jnp.int32)

    pos = lengths - 1
    w_page = jnp.take_along_axis(page_tables, (pos // page)[:, None], 1)[:, 0]
    k_want = kv_write(k_pool, layer, w_page, pos % page, k_new)
    v_want = kv_write(v_pool, layer, w_page, pos % page, v_new)
    want = _paged_attention_tpu(q, k_want, v_want, ly, page_tables,
                                lengths, **kw)
    got, k_got, v_got = _paged_attention_tpu(
        q, k_pool, v_pool, ly, page_tables, lengths, new=(k_new, v_new), **kw)

    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    empty = [b for b, n in enumerate(lanes) if n == 0]
    assert empty
    assert not np.asarray(got, np.float32)[empty].any()
    assert np.asarray(got, np.float32)[lanes.index(1)].any()
    for got_pool, want_pool, old in ((k_got, k_want, k_pool),
                                     (v_got, v_want, v_pool)):
        got_pool, want_pool = (np.array(a, np.float32)
                               for a in (got_pool, want_pool))
        for b in empty:
            scratch = (layer, slice(None), int(page_tables[b, -1]),
                       (page - 1) // fold)
            # the one row they differ by, and the kernel left it as it was
            assert (got_pool[scratch] != want_pool[scratch]).any()
            np.testing.assert_array_equal(
                got_pool[scratch], np.asarray(old, np.float32)[scratch])
            want_pool[scratch] = got_pool[scratch]
        np.testing.assert_array_equal(got_pool, want_pool)


def test_new_rows_only_where_a_kernel_writes():
    """Over rows narrower than a lane tile stored unfolded nothing writes
    ``new`` rows (the kernel is handed a re-laid slice of such a pool, never
    the pool): the entry point says so rather than attend over a pool that
    lacks them. One predicate, whatever the platform."""
    from dynamo_tpu.ops.attention import paged_kernel_writes

    assert paged_kernel_writes(128, 1) and not paged_kernel_writes(64, 1)
    z = jnp.zeros((2, 2, 3, 8, 64), jnp.bfloat16)
    rows = jnp.zeros((1, 2, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="the caller scatters"):
        paged_attention(jnp.zeros((1, 4, 64), jnp.bfloat16), z, z,
                        jnp.zeros((1, 1), jnp.int32),
                        jnp.ones((1,), jnp.int32), layer=1, interpret=True,
                        new=(rows, rows))


@pytest.mark.parametrize("Dh,fold,writes", [
    (128, 1, True), (256, 1, True), (64, 2, True),
    (64, 1, False),              # 64-lane rows stored unfolded: re-laid
    (16, 1, False),
])
def test_which_pools_the_kernel_writes(Dh, fold, writes):
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.attention import paged_kernel_writes

    assert paged_kernel_writes(Dh, fold) is writes
    # the decode step's own question: the same, on the kernel's path alone
    assert llama.kernel_writes(None, "pallas", Dh, fold) is writes
    assert not llama.kernel_writes(None, "xla", Dh, fold)


# ---------------------------------------------------------------------------
# The dma kernel copies only the pages that hold a token the lane can see
# ---------------------------------------------------------------------------

# lengths over a table of 8 pages of 32 (four blocks at ppb 2): an EMPTY lane
# (as the decode program hands the kernel a lane the dispatch does not serve)
# and one token, a whole page and a page + 1, a whole block and a block + 1,
# mid-page in the second block, several blocks, the table's last rows
_LIVE_LENGTHS = [0, 1, 32, 33, 64, 65, 77, 128, 130, 200, 255, 256]
_LIVE_CASES = [
    # case, Dh, Dv, fold, window, selected, sunk
    ("plain", 128, 128, 1, None, False, False),
    ("window", 128, 128, 1, 40, False, False),
    ("selected", 128, 128, 1, None, True, False),        # keye
    ("sunk-dv", 256, 128, 1, None, False, True),
    ("window-sunk-dv", 256, 128, 1, 40, False, True),    # mimo's window layers
    ("fold2", 64, 64, 2, None, False, False),            # granite
    ("fold2-window", 64, 64, 2, 70, False, False),
]


def _visible_pages(n, page, window):
    """Pages that hold a token a query at ``n - 1`` sees, token by token (a
    lane of 0 sees none)."""
    return sorted({t // page
                   for t in range(max(n - window, 0) if window else 0, n)})


@pytest.mark.parametrize("writes", [False, True], ids=["reads", "writes"])
@pytest.mark.parametrize("case,Dh,Dv,fold,window,selected,sunk", _LIVE_CASES,
                         ids=[c[0] for c in _LIVE_CASES])
def test_dead_pages_are_never_read(case, Dh, Dv, fold, window, selected,
                                   sunk, writes):
    """Every table entry past a lane's last token, and behind its window,
    names ONE page that is NaN in both pools and every layer, and so does
    EVERY entry of the empty lane's table; scratch page 0 is NaN too. The
    empty lane comes out as exact zeros (it is skipped: no page read, no
    row written); for the others the dma kernel
    (interpreter) still gives what a float32 reference gives over the visible
    tokens, and with ``new`` the pools it hands back are ``kv_write``'s, the
    poisoned page as it was. The kernel as it stood before it told a block's
    pages apart FAILS this by construction: it copied every page of an
    active block and ``p . V`` multiplied a weight of 0 by the NaN (0 x NaN
    = NaN). So a pass also says which pages the interpreted kernel fetched:
    all the visible ones (or the output is wrong) and no other (or it is
    NaN), and their count a lane is ``paged_live_pages``'."""
    from dynamo_tpu.models.llama import kv_write
    from dynamo_tpu.ops.attention import (_paged_attention_tpu,
                                          paged_live_pages)

    L, layer, Hkv, G, page, ppb, P = 2, 1, 2, 2, 32, 2, 8
    lanes = _LIVE_LENGTHS
    B = len(lanes)
    n_pages = B * P + 2
    poison = n_pages - 1
    ks = jax.random.split(jax.random.PRNGKey(41), 7)
    bf = jnp.bfloat16

    def rand(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(bf)

    tables = np.full((B, P), poison, np.int32)
    for b, n in enumerate(lanes):
        seen = _visible_pages(n, page, window)      # none for the empty lane
        tables[b, seen] = 1 + b * P + np.asarray(seen, np.int32)
    live, visited = paged_live_pages(lanes, P, page, ppb, window)
    assert list(live) == [len(_visible_pages(n, page, window))
                          for n in lanes]
    assert list(live) == list((tables != poison).sum(1))
    assert (live < visited).any() and (live <= visited).all()
    assert (live[0], visited[0]) == (0, 0)

    q = rand(ks[0], B, Hkv, G, Dh)
    pools = (rand(ks[1], L, Hkv, n_pages, page // fold, fold * Dh),
             rand(ks[2], L, Hkv, n_pages, page // fold, fold * Dv))
    k_pool, v_pool = (p.at[:, :, (0, poison), :].set(jnp.nan) for p in pools)
    k_new, v_new = rand(ks[3], B, Hkv, Dh), rand(ks[4], B, Hkv, Dv)
    lengths = jnp.asarray(lanes, jnp.int32)
    page_tables = jnp.asarray(tables)
    keep = sink = None
    kw = dict(pages_per_block=ppb, window=window, interpret=True,
              stored_fold=fold)
    if selected:
        kw["keep"] = keep = jax.random.bernoulli(ks[5], 0.5, (B, P * page))
    if sunk:
        kw["sink"] = sink = jax.random.normal(ks[6], (Hkv * G,), jnp.float32)
    ly = jnp.asarray([layer], jnp.int32)

    if writes:
        pos = lengths - 1
        w_page = jnp.take_along_axis(page_tables, (pos // page)[:, None],
                                     1)[:, 0]
        want_pools = [kv_write(p, layer, w_page, pos % page, new)
                      for p, new in ((k_pool, k_new), (v_pool, v_new))]
        got, *got_pools = _paged_attention_tpu(
            q, k_pool, v_pool, ly, page_tables, lengths, new=(k_new, v_new),
            **kw)
        for got_pool, want_pool, old in zip(got_pools, want_pools,
                                            (k_pool, v_pool)):
            got_pool, want_pool, old = (np.array(a, np.float32)
                                        for a in (got_pool, want_pool, old))
            # kv_write's scratch row for the empty lane (position -1: the
            # table's last entry, offset page - 1: the poisoned page, NaN
            # before the scatter); the kernel writes none: both NaN pages
            # are as they were and no other row differs from kv_write's
            scratch = (layer, slice(None), poison, (page - 1) // fold)
            want_pool[scratch] = old[scratch]
            assert np.isnan(got_pool[:, :, (0, poison)]).all()
            np.testing.assert_array_equal(got_pool, want_pool)
        ref_pools = got_pools
    else:
        got = _paged_attention_tpu(q, k_pool, v_pool, ly, page_tables,
                                   lengths, **kw)
        ref_pools = (k_pool, v_pool)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert lanes[0] == 0 and not got[0].any()

    # the reference: float32, over the tokens a lane's query sees alone (none
    # for the empty lane: zeros)
    rk, rv = [np.asarray(p[layer], np.float32).reshape(Hkv, n_pages, page, -1)
              for p in ref_pools]
    qf = np.asarray(q, np.float32)
    for b, n in enumerate(lanes):
        t = np.arange(max(n - window, 0) if window else 0, n)
        if keep is not None:
            t = t[np.asarray(keep[b])[t]]
        at = (slice(None), tables[b, t // page], t % page)
        s = np.einsum("hgd,htd->hgt", qf[b], rk[at]) / math.sqrt(Dh)
        m = s.max(-1, keepdims=True) if len(t) else np.zeros((Hkv, G, 1))
        p = np.exp(s - m)
        den = p.sum(-1, keepdims=True)
        if sink is not None:
            den = den + np.exp(np.asarray(sink).reshape(Hkv, G, 1) - m)
        want = np.einsum("hgt,htd->hgd", p, rv[at]) / np.where(den == 0, 1,
                                                              den)
        np.testing.assert_allclose(got[b], want, atol=2e-2, rtol=2e-2,
                                   err_msg=f"lane {b} of {n}")


# ---------------------------------------------------------------------------
# A lane of no tokens is skipped: zeros out, no page read, nothing written
# ---------------------------------------------------------------------------

_SKIP_CASES = [
    # case, Dh, Dv, fold, window, selected, sunk, latent
    ("plain", 128, 128, 1, None, False, False, False),
    ("window", 128, 128, 1, 40, False, False, False),
    ("selected", 128, 128, 1, None, True, False, False),     # keye
    ("sunk-dv", 256, 128, 1, None, False, True, False),      # mimo, full
    ("window-sunk-dv", 256, 128, 1, 40, False, True, False),  # mimo, window
    ("latent", 128, 256, 1, None, False, False, True),       # deepseek
    ("fold2", 64, 64, 2, None, False, False, False),         # granite, lfm2
    ("fold2-window", 64, 64, 2, 40, False, False, False),
]
# six lanes over tables of four pages of 32 (two blocks at ppb 2); 0 = a lane
# the dispatch does not serve. The prime has to find the first served lane,
# the chain to hop over one empty lane, over several, and off the end
_SKIP_LAYOUTS = {
    "first": [0, 70, 33, 128, 1, 64],
    "last": [70, 33, 128, 1, 64, 0],
    "adjacent": [70, 0, 0, 0, 128, 33],
    "ends-and-between": [0, 0, 65, 0, 1, 0],
    "all": [0, 0, 0, 0, 0, 0],
}


# every layout with the kernel that writes (what the cells run); the kernel
# that only reads where the chain hops most and where it never starts
_SKIP_RUNS = [(layout, writes) for layout in _SKIP_LAYOUTS
              for writes in (True, False)
              if writes or layout in ("ends-and-between", "all")]


@pytest.mark.parametrize("shape,dtype,want", [
    ((32, 2, 6, 128), jnp.bfloat16, 32 * 2 * 16 * 128 * 2),   # chat's queries
    ((64, 1, 1024), jnp.bfloat16, 64 * 16 * 1024 * 2),  # granite's new rows
    ((16, 1, 128, 512), jnp.bfloat16, 16 * 128 * 512 * 2),  # deepseek: whole
    ((8, 4, 1), jnp.float32, 8 * 8 * 128 * 4),
])
def test_vmem_bytes_pad_the_last_two_dimensions_to_a_tile(shape, dtype, want):
    from dynamo_tpu.ops.attention import _vmem_bytes

    assert _vmem_bytes(shape, dtype) == want


@pytest.mark.parametrize("lanes,a_lane,want", [
    (32, 1 << 15, 1),                    # chat: 1 MiB in all
    (64, (12 << 20) // 64, 1),           # just fits, whole
    (64, (12 << 20) // 64 + 1, 4),       # ... and just not: the pipeline
    (64, 1 << 18, 4),                    # holds two blocks of 16 lanes
    (64, 1 << 16, 1), (64, 3 << 16, 1),
    (6, 5 << 20, 6), (7, 2 << 20, 7),    # a lane a step; 7 has no divisor
])
def test_lane_groups_are_the_fewest_that_fit(lanes, a_lane, want):
    from dynamo_tpu.ops.attention import _lane_groups

    assert _lane_groups(lanes, a_lane) == want


@pytest.mark.parametrize("groups", [2, 6], ids=["two-groups", "a-lane-a-step"])
@pytest.mark.parametrize("case", ["plain", "latent", "selected",
                                  "fold2-window"])
def test_a_batch_walked_in_groups_of_lanes_is_skipped_the_same(
        monkeypatch, case, groups):
    """The hardest layout of the test below, kernel that writes, for a batch
    whose per-lane operands do not fit in VMEM whole (every call of this file
    does): several grid steps, the chain of copies and the ring of
    write-backs running on across them."""
    from dynamo_tpu.ops import attention as A

    monkeypatch.setattr(A, "_lane_groups", lambda lanes, a_lane: (
        groups if lanes % groups == 0 else lanes))
    at = [c[0] for c in _SKIP_CASES].index(case)
    test_a_lane_of_no_tokens_is_skipped(*_SKIP_CASES[at], "ends-and-between",
                                        True)


@pytest.mark.parametrize("layout,writes", _SKIP_RUNS, ids=[
    f"{layout}-{'writes' if writes else 'reads'}"
    for layout, writes in _SKIP_RUNS])
@pytest.mark.parametrize("case,Dh,Dv,fold,window,selected,sunk,latent",
                         _SKIP_CASES, ids=[c[0] for c in _SKIP_CASES])
def test_a_lane_of_no_tokens_is_skipped(case, Dh, Dv, fold, window, selected,
                                        sunk, latent, layout, writes):
    """Lanes of length 0 as the decode program hands the dma kernel
    (interpreter) the lanes a dispatch does not serve: an all-zero table,
    and scratch page 0, which every table's unused entries name too, NaN in
    both pools and every layer. (a) No page of such a lane is read: every
    output is finite. (b) Its output is exact zeros. (c) Nothing is written
    for it: page 0 stays NaN, and with no lane served the pools come back as
    they went in. And the lanes that ARE served are not touched by the
    skipping: outputs and pools are, bit for bit, those of a call whose batch
    holds the served lanes alone."""
    from dynamo_tpu.ops.attention import _paged_attention_tpu

    L, layer, G, page, ppb, P = 2, 1, 2, 32, 2, 4
    Hkv = 1 if latent else 2
    lanes = np.asarray(_SKIP_LAYOUTS[layout])
    B = len(lanes)
    served = np.flatnonzero(lanes)
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.PRNGKey(46), 8)
    bf = jnp.bfloat16

    def rand(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(bf)

    tables = np.zeros((B, P), np.int32)
    for b in served:
        held = -(-int(lanes[b]) // page)
        tables[b, :held] = 1 + b * P + np.arange(held)
    q = rand(ks[0], B, Hkv, G, Dh)
    k_pool, v_pool = (
        rand(key, L, Hkv, n_pages, page // fold, fold * d)
        .at[:, :, 0].set(jnp.nan) for key, d in ((ks[1], Dh), (ks[2], Dv)))
    new = (rand(ks[3], B, Hkv, Dh), rand(ks[4], B, Hkv, Dv))
    by_lane = {}                      # operands with a row a lane
    if selected:
        # a lane's own token is always among its selected keys
        by_lane["keep"] = jax.random.bernoulli(
            ks[5], 0.5, (B, P * page)).at[served, lanes[served] - 1].set(True)
    if latent:
        by_lane["latent"] = rand(ks[7], B, Hkv, G, Dv)
    kw = dict(pages_per_block=ppb, window=window, interpret=True,
              stored_fold=fold)
    if sunk:
        kw["sink"] = jax.random.normal(ks[6], (Hkv * G,), jnp.float32)
    ly = jnp.asarray([layer], jnp.int32)

    def call(rows):
        res = _paged_attention_tpu(
            q[rows], k_pool, v_pool, ly, jnp.asarray(tables[rows]),
            jnp.asarray(lanes[rows], jnp.int32),
            **{n: a[rows] for n, a in by_lane.items()}, **kw,
            **({"new": tuple(a[rows] for a in new)} if writes else {}))
        out, *pools = res if writes else (res,)
        return (np.asarray(out, np.float32),
                *(np.asarray(p, np.float32) for p in pools))

    got, *got_pools = call(np.arange(B))
    assert np.isfinite(got).all()
    assert not got[lanes == 0].any()
    for pool in got_pools:
        assert np.isnan(pool[:, :, 0]).all()
    if not len(served):
        for pool, old in zip(got_pools, (k_pool, v_pool)):
            np.testing.assert_array_equal(pool, np.asarray(old, np.float32))
        return
    want, *want_pools = call(served)
    assert want.any(axis=(1, 2, 3)).all()
    np.testing.assert_array_equal(got[served], want)
    for pool, other in zip(got_pools, want_pools):
        np.testing.assert_array_equal(pool, other)


@pytest.mark.parametrize("sunk", [False, True], ids=["plain", "sunk"])
def test_the_public_entry_gives_a_lane_of_no_tokens_zeros(sunk):
    """``paged_attention`` as every caller reaches it: exact zeros for a
    lane of length 0 (the page its table names is a NaN one and is never
    read), the other lanes as they are without it in the batch."""
    B, Hq, Hkv, Dh, page, P = 4, 4, 2, 128, 32, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, Hq, Dh), jnp.bfloat16)
    k, v = (jax.random.normal(key, (Hkv, B * P + 1, page, Dh), jnp.bfloat16)
            .at[:, 0].set(jnp.nan) for key in ks[1:3])
    lengths = jnp.asarray([0, 40, 0, 1], jnp.int32)
    tables = (1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
              ) * (lengths > 0)[:, None]
    kw = {"sink": jax.random.normal(ks[3], (Hq,), jnp.float32)} if sunk else {}
    got = np.asarray(paged_attention(q, k, v, tables, lengths, interpret=True,
                                     **kw), np.float32)
    assert np.isfinite(got).all()
    assert not got[[0, 2]].any() and got[[1, 3]].any(axis=(1, 2)).all()
    alone = np.asarray(paged_attention(q[1::2], k, v, tables[1::2],
                                       lengths[1::2], interpret=True, **kw),
                       np.float32)
    np.testing.assert_array_equal(got[1::2], alone)


@pytest.mark.parametrize("window", [None, 40, 64, 200])
@pytest.mark.parametrize("ppb,P", [(2, 8), (8, 8), (8, 3), (3, 8)])
def test_live_pages_counted_token_by_token(window, ppb, P):
    """``paged_live_pages`` (the host's copy of the kernel's predicate)
    against a count over tokens: a page is live if it holds a token the
    query sees, a block is active if it holds a live page; shaped as the
    lengths it is given."""
    from dynamo_tpu.ops.attention import paged_live_pages

    page = 32
    lengths = [n for n in _LIVE_LENGTHS + [31, 96, 97, 127] if n <= P * page]
    width = min(ppb, P)
    seen = [_visible_pages(n, page, window) for n in lengths]
    live, visited = paged_live_pages(lengths, P, page, ppb, window)
    assert list(live) == [len(pgs) for pgs in seen]
    assert list(visited) == [len({p // width for p in pgs}) * width
                             for pgs in seen]
    # the lane the kernel skips: nothing copied, no block it is in
    assert lengths[0] == 0 and (live[0], visited[0]) == (0, 0)
    steps = np.asarray(lengths)[:, None] + np.arange(3)
    steps = np.minimum(steps, P * page)
    live2, visited2 = paged_live_pages(steps, P, page, ppb, window)
    assert live2.shape == visited2.shape == steps.shape
    assert list(live2[:, 0]) == list(live)
    assert list(visited2[:, 0]) == list(visited)


# ---------------------------------------------------------------------------
# The latent flash call's block schedule: several tokens' heads a query
# block, and no copy (nor computation) of a key block no query of it sees
# ---------------------------------------------------------------------------

_LAT = dict(Hq=8, Dh=128, Dv=128, BS=128, S=512)


def _latent_inputs(T, p0, S, holes=(), seed=0):
    """A chunk of ``T`` tokens at positions ``[p0, p0 + T)`` of one lane over
    a bucket of ``S`` slots of which the first ``p0 + T`` are written, the
    slots of ``holes`` (key-block numbers) marked invalid; a second lane
    that is all padding, as a bucket's unused lane is."""
    Hq, Dh, Dv, BS = (_LAT[k] for k in ("Hq", "Dh", "Dv", "BS"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = lambda key, shape: jax.random.normal(
        key, shape, jnp.float32).astype(jnp.bfloat16)
    q, lat = bf(ks[0], (2, T, Hq, Dh)), bf(ks[1], (2, T, Hq, Dv))
    k, v = bf(ks[2], (2, S, 1, Dh)), bf(ks[3], (2, S, 1, Dv))
    n = p0 + T
    slots = np.arange(S)
    k_valid = np.zeros((2, S), bool)
    k_valid[0] = slots < n
    for j in holes:
        k_valid[0, j * BS:(j + 1) * BS] = False
    k_pos = np.zeros((2, S), np.int32)
    k_pos[0] = np.where(slots < n, slots, 0)     # cache.read_slots' padding
    q_pos = np.zeros((2, T), np.int32)
    q_pos[0] = p0 + np.arange(T)
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(k_pos),
            jnp.asarray(k_valid)), lat


def _latent_dense(args, lat, scale):
    from dynamo_tpu.models.llama import latent_attend

    q, k, v, q_pos, k_pos, k_valid = args
    mask = k_valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])

    class cfg:
        attn_scale = scale
    return latent_attend(cfg, (q, lat), k, v, mask)


def _parent_schedule(args, lat, scale):
    """The call as it was before it had a schedule of its own: one token's
    heads a query block, every key block copied, the positions' guard
    alone."""
    from dynamo_tpu.ops import attention as A

    q, k, v, q_pos, k_pos, k_valid = args
    B, T, Hq, _ = q.shape
    rows = lambda a: a.reshape(B, T * Hq, 1, a.shape[-1])
    out = A._flash_call(rows(q), k, v, jnp.repeat(q_pos, Hq, axis=1), k_pos,
                        k_valid, True, scale, None, None, None, None,
                        rows(lat), (Hq, _LAT["BS"]))
    return out.reshape(B, T, Hq, -1)


_SHARES = {"30%": 0.30, "75%": 0.75, "100%": 1.0}
_LATENT_CASES = [
    pytest.param(T, int(_LAT["S"] * share) - T, (), tokens,
                 id=f"T{T}-live{name}-{tokens}tok")
    for T in (8, 32) for name, share in _SHARES.items()
    for tokens in (1, 4, 8)
] + [
    # the live key blocks are NOT a prefix: block 1 of 0..2 holds nothing
    pytest.param(32, 352, (1,), 4, id="hole-in-the-middle"),
    # the chunk's positions 252..259 lie on both sides of a key block's edge
    pytest.param(8, 252, (), 4, id="chunk-across-a-key-block-edge"),
    # a chunk at the lane's start: one live block, a query block of the lot
    pytest.param(32, 0, (), 8, id="first-chunk"),
]


@pytest.mark.parametrize("T,p0,holes,tokens", _LATENT_CASES)
def test_latent_flash_schedule(T, p0, holes, tokens):
    """The latent call with ``tokens`` tokens' heads a query block and no
    dead key block copied: the dense absorbed form within the flash
    tolerance, the schedule it had before BIT FOR BIT, and the same bits
    again with every dead key block full of NaN (a block the call is not
    given cannot reach the result; one it computed on would)."""
    from dynamo_tpu.ops import attention as A

    scale = 0.1147
    BS, S = _LAT["BS"], _LAT["S"]
    args, lat = _latent_inputs(T, p0, S, holes)
    got = flash_attention(*args, interpret=True, scale=scale, latent=lat,
                          blocks=(tokens, BS))
    want = _latent_dense(args, lat, scale)
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
        atol=3e-2, rtol=3e-2)
    before = _parent_schedule(args, lat, scale)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(before, np.float32))

    q, k, v, q_pos, k_pos, k_valid = args
    fetch = np.asarray(A.latent_flash_fetch(
        np.asarray(q_pos), np.asarray(k_pos), np.asarray(k_valid), tokens,
        BS, xp=np))
    dead = ~(fetch == np.arange(S // BS)).any(axis=1)         # [B, nJ]
    assert dead[0].sum() == S // BS - (-(-(p0 + T) // BS) - len(holes))
    poison = jnp.asarray(np.repeat(dead, BS, axis=1))[:, :, None, None]
    again = flash_attention(q, jnp.where(poison, jnp.nan, k),
                            jnp.where(poison, jnp.nan, v), q_pos, k_pos,
                            k_valid, interpret=True, scale=scale, latent=lat,
                            blocks=(tokens, BS))
    assert np.array_equal(np.asarray(again, np.float32),
                          np.asarray(got, np.float32))


@pytest.mark.parametrize("T,S,heads,want", [
    (256, 16384, 128, (8, 512)),     # the benchmark's cell: 1024 rows a block
    (32, 1024, 128, (8, 512)),
    (2, 256, 128, (2, 256)),
    (1, 128, 128, (1, 128)),
    (32, 512, 16, (32, 512)),        # a tensor-parallel shard's 16 heads
    (12, 128, 3, (12, 128)),         # 4 x 3 rows, no multiple of 8: the lot
    (6, 384, 8, (2, 128)),
])
def test_latent_flash_blocks(T, S, heads, want):
    from dynamo_tpu.ops.attention import latent_flash_blocks

    assert latent_flash_blocks(T, S, heads) == want


def test_latent_flash_copies_are_counted_step_by_step():
    """``latent_flash_copies`` against a walk of the grid, a step at a time,
    over tables with a window, a hole and an empty lane."""
    from dynamo_tpu.ops import attention as A

    rng = np.random.default_rng(0)
    for window in (None, 200):
        for p0, T in ((0, 32), (100, 32), (480, 32), (250, 8)):
            q_pos = np.zeros((2, T), np.int32)
            q_pos[0] = p0 + np.arange(T)
            k_pos = np.zeros((2, 512), np.int32)
            k_pos[0, :p0 + T] = np.arange(p0 + T)
            k_valid = np.zeros((2, 512), bool)
            k_valid[0, :p0 + T] = True
            k_valid[0, 128:256] &= rng.random() < 0.5
            fetch = A.latent_flash_fetch(q_pos, k_pos, k_valid, 4, 128,
                                         window, xp=np)
            steps = copies = 0
            for lane in fetch:
                held = None
                for given in lane.reshape(-1):
                    steps += 1
                    copies += given != held
                    held = given
            assert A.latent_flash_copies(fetch) == (steps, copies)
            # a block is given to the steps that follow it until the next
            # live one, never to a step before the first
            mask = (k_valid[:, None, :] & (k_pos[:, None, :]
                                           <= q_pos[:, :, None]))
            if window is not None:
                mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
            seen = mask.reshape(2, T // 4, 4, 4, 128).any(axis=(2, 4))
            own = fetch == np.arange(4)
            assert (own | ~seen).all()       # every visible block is given


def test_a_call_without_a_latent_operand_lowers_as_it_did():
    """The schedule hangs on the ``latent`` operand: a flash call without it
    (GQA, a window, a selection, a sink) lowers for the chip to the text it
    had before the latent call got a table (SHA-256 taken on the parent
    commit with this very function; a change MEANT to alter every flash call
    renews it)."""
    import hashlib

    from dynamo_tpu.utils import jaxenv

    B, T, S, Hq, Hkv, Dh = 1, 64, 512, 8, 2, 128
    sds = jax.ShapeDtypeStruct
    shapes = (sds((B, T, Hq, Dh), jnp.bfloat16),
              sds((B, S, Hkv, Dh), jnp.bfloat16),
              sds((B, S, Hkv, Dh), jnp.bfloat16),
              sds((B, T), jnp.int32), sds((B, S), jnp.int32),
              sds((B, S), jnp.bool_))

    def text(**kw):
        extra = []
        if kw.pop("keep", False):
            extra.append(("keep", sds((B, T, S), jnp.bool_)))
        if kw.pop("sink", False):
            extra.append(("sink", sds((Hq,), jnp.float32)))
        names = [n for n, _ in extra]

        def f(*a):
            return flash_attention(*a[:6], interpret=False,
                                   **dict(zip(names, a[6:])), **kw)
        low = jax.jit(f).trace(*shapes, *[s for _, s in extra]).lower(
            lowering_platforms=("tpu",))
        return hashlib.sha256(low.as_text().encode()).hexdigest()

    # locations as every engine entry point sets them: names, no file paths
    was = {k: getattr(jax.config, k) for k in jaxenv.PROGRAM_LOCATIONS}
    for name, value in jaxenv.PROGRAM_LOCATIONS.items():
        jax.config.update(name, value)
    try:
        got = {"plain": text(), "window": text(window=128, softcap=30.0),
               "selected-sunk": text(keep=True, sink=True)}
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
    assert got == _PARENT_FLASH_TEXT, got


_PARENT_FLASH_TEXT = {
    "plain":
        "047450bd56be15c836a63456af2f4693f393bd71d01d33f7b1318701f9fd3768",
    "window":
        "f3f1f39fac045c421e1f6b6ca1e37c9a40f557eaf689c8b809f29c69ca845a32",
    "selected-sunk":
        "34acc360fd59f372c9a3fed8ffe0fc3608013beedce6850402a30b7e3f7da352",
}
