"""Pallas TPU attention kernels for the serving engine.

Two kernels cover the two hot paths:

- :func:`flash_attention` — blockwise online-softmax attention for prefill
  chunks. Queries/keys carry explicit positions + validity so it drops into
  the engine's paged write-then-gather scheme unchanged: the [T,S] score
  matrix never materializes in HBM.
- :func:`paged_attention` — decode attention that reads KV *pages* directly
  from the HBM pool through a scalar-prefetched page table (one grid step per
  page, Pallas double-buffers the page DMAs). This removes the
  gather-into-contiguous-context copy entirely, which is the dominant HBM
  traffic of decode.

On a TPU both kernels always compile (unless a caller passes
``interpret=True``); off-TPU ``interpret=None`` selects the Pallas
interpreter so the CPU test suite exercises the same kernel bodies. The
platform test is :func:`dynamo_tpu.utils.jaxenv.on_tpu`, keyed on the
device: callers that know their mesh pass ``interpret=not on_tpu(device)``.

Reference capability: the CUDA paged/flash attention vLLM supplies behind the
reference's engine adapters (SURVEY §2.1 engine rows; §7 "Pallas paged
attention + flash kernels"). This file is original TPU-first work, not a
translation.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.jaxenv import on_tpu

NEG_INF = -1e30


def _pick_block(n: int, align: int, cap: int = 128) -> int:
    """Block size for an axis of length n under Mosaic's tiling rule: a
    block's last two dims must be multiples of (8, 128) or span the whole
    axis. Returns the largest power-of-two block <= cap that divides n and
    is a multiple of ``align`` (8 for a second-minor axis, 128 for a minor
    one); when none does, n itself — one block over the whole axis.

    The engine keeps the whole-axis case small: prefill chunks are powers of
    two, context buckets above 128 are multiples of 128 (engine.py), so only
    short axes (a spec-verify chunk of k+1 tokens, sub-128 contexts) are
    taken whole."""
    b = cap
    while b >= align:
        if n % b == 0:
            return b
        b //= 2
    return n


# ---------------------------------------------------------------------------
# Learned top-k selection (DeepSeek-Sparse-Attention indexer)
#
# A model with an indexer attends, per query, to the ``k`` visible keys with
# the largest index score. Both kernels below take that set as a per-key keep
# mask in the lane's logical order and apply it beside their causal / length
# mask: every page is still read, which is the whole mathematics and a sound
# first form (a kernel that gathers the selected keys is PERF.md §7).
# ---------------------------------------------------------------------------

def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """I[b,t,s] = sum_j w[b,t,j] * relu(qi[b,t,j] . ki[b,s]), accumulated in
    float32. qi [B,T,Hi,Di]; ki [B,S,Di] (ONE index key head); w [B,T,Hi].
    Any positive scale leaves the selected set as it is, so none is
    applied."""
    with jax.named_scope("dynamo.index_select"):
        dots = jnp.einsum("bthd,bsd->bths", qi, ki,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bths,bth->bts", jax.nn.relu(dots),
                          w.astype(jnp.float32),
                          preferred_element_type=jnp.float32)


def topk_keep(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The exact top-``min(k, visible)`` of ``scores`` [..., S] (float32)
    among the ``visible`` keys, as a bool keep mask [..., S]; a tie at the
    threshold goes to the LOWER position, as ``lax.top_k`` breaks it.

    No sort: the k-th largest score is found bit by bit on the scores'
    order-preserving integer image (32 counting passes), then the ties at
    that value are cut by position the same way (one pass per bit of S).
    A context no longer than ``k`` is the identity by construction and
    computes nothing."""
    S = scores.shape[-1]
    if S <= k:
        return visible
    with jax.named_scope("dynamo.index_select"):
        # -0.0 and +0.0 compare equal; give them one image
        scores = jnp.where(scores == 0.0, 0.0, scores.astype(jnp.float32))
        bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
        # monotone image of the float order in uint32: flip the magnitude
        # of negatives, then the sign bit; invisible keys take the minimum
        mono = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        u = jax.lax.bitcast_convert_type(mono, jnp.uint32) ^ jnp.uint32(
            0x80000000)
        u = jnp.where(visible, u, jnp.uint32(0))

        def count(m):
            return jnp.sum(m.astype(jnp.int32), axis=-1, keepdims=True)

        def value_bit(i, t):
            cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
                jnp.uint32)))
            return jnp.where(count(u >= cand) >= k, cand, t)

        # largest t with at least k keys >= t: the k-th largest image
        thr = jax.lax.fori_loop(
            0, 32, value_bit, jnp.zeros((*u.shape[:-1], 1), jnp.uint32))
        above, tied = u > thr, u == thr
        need = k - count(above)                 # >= 1 of the tied keys
        pos = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
        nbits = max(1, (S - 1).bit_length())

        def pos_bit(i, p):
            # largest p with FEWER than ``need`` tied keys below p: the
            # need-th tied key sits at position p
            cand = p | (jnp.int32(1) << (jnp.int32(nbits - 1) - i))
            return jnp.where(count(tied & (pos < cand)) < need, cand, p)

        cut = jax.lax.fori_loop(
            0, nbits, pos_bit, jnp.zeros((*u.shape[:-1], 1), jnp.int32))
        return visible & (above | (tied & (pos <= cut)))


# ---------------------------------------------------------------------------
# Flash attention (prefill over gathered context)
# ---------------------------------------------------------------------------

def _flash_kernel(qpos_ref, kpos_ref, kval_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, G: int, softcap: Optional[float],
                  window: Optional[int], selected: bool,
                  sunk: bool = False):
    # a model with an indexer adds ONE operand, the keep mask of its
    # selection, and a layer with a sink one, the heads' sink logits; every
    # other model's kernel is what it always was
    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    sink_ref, rest = (rest[0], rest[1:]) if sunk else (None, rest)
    o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qp = qpos_ref[0]                                       # [BT, 1]
    kp = kpos_ref[0]                                       # [1, BS]
    kv = kval_ref[0]
    # dead-block skip: a key block entirely in the causal future — or, on
    # sliding layers, entirely below every query's window — contributes
    # nothing; skip its matmuls (positions are dynamic, so this is a
    # run-time guard; the BlockSpec copies still happen)
    live = jnp.min(kp) <= jnp.max(qp)
    if window is not None:
        live = live & (jnp.max(kp) > jnp.min(qp) - window)

    @pl.when(live)
    def _():
        q = q_ref[0]                                       # [G, BT, Dh] bf16
        BS, Dh = k_ref.shape[-2], k_ref.shape[-1]
        k = jnp.broadcast_to(k_ref[0][None], (G, BS, Dh))  # [G, BS, Dh]
        v = jnp.broadcast_to(v_ref[0][None], (G, BS, v_ref.shape[-1]))
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [G, BT, BS]
        if softcap is not None:
            # Gemma2 attention-score softcapping, BEFORE masking (tanh of
            # the NEG_INF sentinel would turn masked slots into finite ±cap)
            s = jnp.tanh(s / softcap) * softcap

        mask = ((kp <= qp) & (kv > 0))[None]               # [1, BT, BS]
        if window is not None:
            # sliding layers: keys within the last `window` positions.
            # The paged lane's per-layer-class cold programs
            # (llm/kvpage/programs.py) apply this same `kp > qp - window`
            # rule to staged segments — the two must stay in lockstep or
            # paged and dense forwards diverge on Gemma2/3-style models.
            mask = mask & (kp > qp - window)[None]
        if keep_ref is not None:
            mask = mask & (keep_ref[0] > 0)[None]          # [1, BT, BS]

        m_prev = m_scr[:]
        m_cur = jnp.max(jnp.where(mask, s, NEG_INF), axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # mask p explicitly: with a finite NEG_INF sentinel, exp(s - m) of a
        # fully masked row would otherwise be exp(0) = 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # [G, BT, BS] f32
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [G, BT, Dh]
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_scr[:]
        if sink_ref is not None:
            # the sink: one more key of logit sink[h] and value zero
            l = l + jnp.exp(sink_ref[0][:, :, None] - m_scr[:])
        o = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, k_pos: jax.Array, k_valid: jax.Array,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    window: Optional[int] = None,
                    keep: Optional[jax.Array] = None,
                    sink: Optional[jax.Array] = None) -> jax.Array:
    """Blockwise attention with explicit positions.

    q: [B, T, Hq, Dh] ; k: [B, S, Hkv, Dh] ; v: [B, S, Hkv, Dv] (gathered
    context, GQA; V heads may have a width of their own)
    q_pos: [B, T] int32 ; k_pos: [B, S] int32 ; k_valid: [B, S] bool
    A query at position p attends to context slots with k_pos <= p & valid;
    with ``window`` additionally k_pos > p - window (Gemma2/3 sliding
    layers). ``softcap`` tanh-caps scores before the online softmax;
    ``scale`` overrides the rsqrt(Dh) default (query_pre_attn_scalar).
    ``keep`` [B, T, S] bool (a model with an indexer: :func:`topk_keep`)
    restricts each query to its selected keys, every head alike.
    ``sink`` [Hq] float32: a logit a head that takes softmax weight and
    gives no value. Returns [B, T, Hq, Dv] in q.dtype.
    """
    B, T, Hq, Dh = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    if interpret is None:
        interpret = not on_tpu()
    BT = _pick_block(T, 8)
    BS = _pick_block(S, 128)
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)

    # head-major layouts: fold (B, Hkv) into the leading grid axis
    q5 = q.reshape(B, T, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    q5 = q5.reshape(B * Hkv, G, T, Dh)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dv)
    # positions/validity carry a singleton middle axis: a [B, S] array with
    # block (1, BS) violates Mosaic's last-two-dims tiling rule whenever
    # B > 1 (block dim 1 is neither 8-divisible nor equal to B); as
    # [B, 1, S] the trailing dims are (1, BS) against overall (1, S), legal
    # for every batch size
    kval = k_valid.astype(jnp.int32)[:, None, :]       # [B, 1, S]
    kpos3 = k_pos[:, None, :]                          # [B, 1, S]
    qpos_col = q_pos[:, :, None]                       # [B, T, 1]

    selected = keep is not None
    sel_specs, sel_args = [], []
    if selected:
        sel_specs = [pl.BlockSpec((1, BT, BS),
                                  lambda bh, i, j: (bh // Hkv, i, j))]
        sel_args = [keep.astype(jnp.int32)]
    if sink is not None:
        sel_specs.append(pl.BlockSpec((1, G, 1),
                                      lambda bh, i, j: (bh % Hkv, 0, 0)))
        sel_args.append(sink.astype(jnp.float32).reshape(Hkv, G, 1))
    grid = (B * Hkv, T // BT, S // BS)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, G=G,
                          softcap=softcap, window=window, selected=selected,
                          **({} if sink is None else {"sunk": True})),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BT, 1), lambda bh, i, j: (bh // Hkv, i, 0)),
            pl.BlockSpec((1, 1, BS), lambda bh, i, j: (bh // Hkv, 0, j)),
            pl.BlockSpec((1, 1, BS), lambda bh, i, j: (bh // Hkv, 0, j)),
            pl.BlockSpec((1, G, BT, Dh), lambda bh, i, j: (bh, 0, i, 0)),
            pl.BlockSpec((1, BS, Dh), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, BS, Dv), lambda bh, i, j: (bh, j, 0)),
            *sel_specs,
        ],
        out_specs=pl.BlockSpec((1, G, BT, Dv), lambda bh, i, j: (bh, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, T, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, BT, 1), jnp.float32),    # m
            pltpu.VMEM((G, BT, 1), jnp.float32),    # l
            pltpu.VMEM((G, BT, Dv), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(qpos_col, kpos3, kval, q5, k3, v3, *sel_args)

    out = out.reshape(B, Hkv, G, T, Dv).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, T, Hq, Dv)


# ---------------------------------------------------------------------------
# Paged attention (decode directly over the HBM page pool)
#
# TPU path: multi-page double-buffered DMA kernel. The KV pool stays in HBM
# (memory_space=ANY); each grid step (b, j) copies the next block of
# ``pages_per_block`` pages for sequence b — ALL kv heads in one strided
# DMA per page — into a VMEM double buffer while the previous block
# computes, and accumulates online softmax in VMEM scratch. One DMA per
# page (not per page×head) matters: DMA issue overhead dominated the
# per-(b,h,j) variant, which moved the same bytes in 8× more copies and
# reached only ~9% of HBM bandwidth. Work is skipped (copies AND compute)
# for page blocks beyond a sequence's length, so cost scales with actual
# context, not the padded table width. This is the same design as
# jax.experimental.pallas.ops.tpu.paged_attention, which we cannot use
# directly: for GQA group sizes not divisible by 8 (Llama 8B/1B are 32q/8kv
# = 4) its m/l pallas outputs lower to illegal (…,1) blocks in this JAX
# version. Keeping m/l in scratch sidesteps that and drops two HBM outputs.
# ---------------------------------------------------------------------------


def _lane_block(b, j, *prefetched):
    """Index map of the per-lane q / output block of both paged kernels."""
    return (b, 0, 0, 0)


def _paged_dma_kernel(pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                      scale: float, page: int, ppb: int, hkv: int,
                      fold: int, dh: int, softcap: Optional[float],
                      window: Optional[int], selected: bool,
                      dv: Optional[int] = None, sunk: bool = False,
                      writes: bool = False):
    """Pools are the WHOLE stored pool, [L, Hkv, n_pages, page//fold,
    fold*Dh], left in HBM; ``layer_ref[0]`` picks the layer inside the copy
    descriptor, so no per-layer slice of the pool is ever materialised and
    one Mosaic kernel serves every layer of a (window, softcap) class. With
    ``fold`` > 1 (Dh < 128) a row holds ``fold`` consecutive tokens,
    handled as ``fold`` score slices. Buffers are head-major ([2, Hkv, ppb,
    rows, fold*Dh]) so the per-page all-head DMA lands as a contiguous
    per-head reshape for the batched matmul.

    With ``window``, each lane's active block range is clamped at BOTH ends:
    blocks wholly below ``length - window`` are never DMA'd nor computed
    (the page-range clamp — sliding decode reads O(window) bytes, not
    O(context)), and in-block tokens below the window start are masked.

    ``selected``: one more operand, the keep mask of a model with an indexer
    ([1, 1, L2] int32 of this lane and block, logical order, fold 1 only);
    without it the kernel is what it always was. ``dv``: V rows of a width
    of their own (fold 1 only). ``sunk``: one more operand, the heads' sink
    logits [Hkv, G, 1] float32 (a key of that logit and value zero).

    ``writes``: a one-token decode step's new rows come as two more operands
    (``k_new`` / ``v_new`` [1, 1, Hkv*fold*D]: this lane's rows, the heads
    side by side, a row repeated ``fold`` times across its lanes) and the
    pools come back as two more
    results, aliased to the operands. The row of token ``length - 1``
    lies in the LAST page of the lane's last active block, which the kernel
    holds in VMEM once that block's copies are waited for: there the tile
    group of rows around it (:func:`_write_group`) is overlaid with the new
    row (write, then attend: the scores see it as they would after
    ``kv_write``), staged, and copied back to HBM, all heads in one strided
    copy a pool. The copy is NOT waited for in its grid step: the staging
    ring holds ``_WRITE_RING`` lanes' groups, a ring slot's copy is waited
    for when the slot comes round again and every one still out at the last
    grid step. A lane of length 0 (an empty slot) attends like a lane of
    length 1, as it always did, and writes nothing. What keeps the copy back
    from racing a read: the page that holds ``length - 1`` belongs to its
    lane alone (prefix reuse shares sealed pages only: engine/cache.py), so
    no other lane's prefetch names it, and the lane's own read of it was
    waited for before the overlay."""
    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    sink_ref, rest = (rest[0], rest[1:]) if sunk else (None, rest)
    if writes:
        (kn_ref, vn_ref, o_ref, k_out, v_out, k_buf, v_buf, sem, m_scr, l_scr,
         acc_scr, state, kw_buf, vw_buf, wsem) = rest
    else:
        o_ref, k_buf, v_buf, sem, m_scr, l_scr, acc_scr, state = rest
    dv = dh if dv is None else dv
    b = pl.program_id(0)
    j = pl.program_id(1)
    L2 = ppb * page           # tokens per compute block
    rows_pp = page // fold    # folded rows per page
    rows = L2 // fold         # folded rows per compute block

    def length_of(bb):
        # every lane covers >= 1 block: the prefetch chain below would leave
        # a DMA slot un-consumed after a lane of none and stall the next
        return jnp.maximum(len_ref[bb], 1)

    def nblocks(bb):
        return (length_of(bb) + L2 - 1) // L2

    def jstart(bb):
        # first block holding any in-window token. The decode query sits at
        # length-1, so the window covers [length - window, length).
        if window is None:
            return 0
        return jnp.maximum(length_of(bb) - window, 0) // L2

    layer = layer_ref[0]

    def copy_descs(bb, jj, slot):
        descs = []
        for i in range(ppb):
            pidx = pt_ref[bb, jj * ppb + i]
            # one strided DMA per page covering every kv head
            descs.append(pltpu.make_async_copy(
                k_hbm.at[layer, :, pidx], k_buf.at[slot, :, i],
                sem.at[slot, 0]))
            descs.append(pltpu.make_async_copy(
                v_hbm.at[layer, :, pidx], v_buf.at[slot, :, i],
                sem.at[slot, 1]))
        return descs

    def start(bb, jj, slot):
        for d in copy_descs(bb, jj, slot):
            d.start()

    nb = nblocks(b)
    j0 = jstart(b)
    active = (j >= j0) & (j < nb)

    # first grid step: prime the pipeline with lane 0's first active block.
    # Steps of lane 0 before its window start are dead, so the prime fires
    # at (0, jstart(0)) — for full attention that is (0, 0) as before.
    first = (b == 0) & (j == jstart(0))

    @pl.when(first)
    def _():
        state[0] = 0
        if writes:
            state[1] = 0          # write-backs started so far
        start(b, j, 0)

    def write_wait(w):
        # a wait needs the semaphore and the copy's size, not its address
        for i, (wbuf, pool) in enumerate(((kw_buf, k_out), (vw_buf, v_out))):
            pltpu.make_async_copy(
                wbuf.at[w], pool.at[layer, :, 0, pl.ds(0, wbuf.shape[2])],
                wsem.at[w, i]).wait()

    def write_back(slot):
        """Overlay the new rows on block ``slot`` and start their way back
        to the pool (the last active block of lane ``b``, copies waited
        for)."""
        tok = len_ref[b] - 1
        pg = tok // page
        off = tok % page
        r = off // fold                       # the pool row of the token
        grp = kw_buf.shape[2]
        g0 = pl.multiple_of((r // grp) * grp, grp)
        n = state[1]
        w = n % _WRITE_RING

        @pl.when(n >= _WRITE_RING)
        def _():
            write_wait(w)

        for i, (buf, new_ref, wbuf, pool, d) in enumerate((
                (k_buf, kn_ref, kw_buf, k_out, dh),
                (v_buf, vn_ref, vw_buf, v_out, dv))):
            shape = (grp, fold * d)
            hit = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == r - g0
            if fold > 1:
                hit = hit & (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                             // d == off % fold)
            for h in range(hkv):
                at = (slot, h, pg % ppb, pl.ds(g0, grp), slice(None))
                row = new_ref[0, :, h * fold * d:(h + 1) * fold * d]
                merged = jnp.where(hit, row, buf[at])        # [grp, f*d]
                buf[at] = merged
                wbuf[w, h] = merged
            pltpu.make_async_copy(
                wbuf.at[w], pool.at[layer, :, pt_ref[b, pg], pl.ds(g0, grp)],
                wsem.at[w, i]).start()
        state[1] = n + 1

    @pl.when(active)
    def _():
        slot = state[0]

        @pl.when(j == j0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        # prefetch the next ACTIVE step's block into the other buffer.
        # flat order: j within b, then b; j outside [jstart, nblocks) is
        # dead (never copied, never computed).
        nj, nb_ = j + 1, b
        wrap_b = nj >= nb
        nb_ = jnp.where(wrap_b, b + 1, nb_)
        # clamp the lookup lane: when nb_ == num_programs there is no next
        # step (has_next gates the start), but jstart still indexes len_ref
        nj = jnp.where(wrap_b,
                       jstart(jnp.minimum(nb_, pl.num_programs(0) - 1)), nj)
        has_next = nb_ < pl.num_programs(0)

        @pl.when(has_next)
        def _():
            start(nb_, nj, slot ^ 1)

        # wait for our block's DMAs
        for d in copy_descs(b, j, slot):
            d.wait()

        if writes:
            @pl.when((j == nb - 1) & (len_ref[b] > 0))
            def _():
                write_back(slot)

        q = q_ref[0]                                        # [Hkv, G, Dh]
        kf = k_buf[slot].reshape(hkv, rows, fold * dh)
        vf = v_buf[slot].reshape(hkv, rows, fold * dv)
        # token index of folded row r, slice f: within this block the page
        # is r // rows_pp and the in-page row r % rows_pp
        ridx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)
        base = (ridx // rows_pp) * page + (ridx % rows_pp) * fold + j * L2
        length = length_of(b)

        s_parts, mask_parts = [], []
        for f in range(fold):
            kslice = kf[:, :, f * dh:(f + 1) * dh]          # [Hkv, rows, Dh]
            s = jax.lax.dot_general(
                q, kslice, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [Hkv, G, rows]
            if softcap is not None:
                # cap BEFORE masking (tanh(NEG_INF) would be a finite ±cap)
                s = jnp.tanh(s / softcap) * softcap
            mask = (base + f) < length
            if window is not None:
                mask = mask & ((base + f) >= length - window)
            if keep_ref is not None:
                mask = mask & (keep_ref[...] > 0)           # [1, 1, rows]
            s_parts.append(jnp.where(mask, s, NEG_INF))
            mask_parts.append(mask)

        m_prev = m_scr[:]
        m_cur = s_parts[0].max(axis=-1, keepdims=True)
        for s in s_parts[1:]:
            m_cur = jnp.maximum(m_cur, s.max(axis=-1, keepdims=True))
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:]
        acc = acc_scr[:] * alpha
        for f in range(fold):
            p = jnp.where(mask_parts[f], jnp.exp(s_parts[f] - m_new), 0.0)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            vslice = vf[:, :, f * dv:(f + 1) * dv]          # [Hkv, rows, Dv]
            acc = acc + jax.lax.dot_general(
                p.astype(vf.dtype), vslice, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)         # [Hkv, G, Dh]
        l_scr[:] = l_new
        acc_scr[:] = acc
        m_scr[:] = m_new
        state[0] = slot ^ 1

        @pl.when(j == nb - 1)
        def _():
            l = l_scr[:]
            if sink_ref is not None:
                l = l + jnp.exp(sink_ref[...] - m_scr[:])
            o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(o_ref.dtype)

    if writes:
        @pl.when((b == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
        def _():
            for w in range(_WRITE_RING):
                @pl.when(state[1] > w)
                def _():
                    write_wait(w)


# lanes whose write-back may be on its way at once (the staging ring of
# _paged_dma_kernel): a lane's grid steps take about as long as one copy
_WRITE_RING = 4


def _write_group(rows_pp: int, dtype) -> int:
    """Rows of a page the kernel copies back around a new one: the
    sublane-tile group that holds it (16 rows of bfloat16: no copy starts or
    ends inside a tile), or the whole page where a page's rows do not divide
    into tiles."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return tile if rows_pp % tile == 0 else rows_pp


def _paged_attention_tpu(q4, k_pool, v_pool, layer, page_tables, lengths,
                         *, pages_per_block: int = 8,
                         scale: Optional[float] = None,
                         softcap: Optional[float] = None,
                         window: Optional[int] = None,
                         keep: Optional[jax.Array] = None,
                         sink: Optional[jax.Array] = None,
                         interpret: bool = False,
                         stored_fold: int = 1,
                         new: Optional[Tuple[jax.Array, jax.Array]] = None):
    """q4: [B, Hkv, G, Dh]; pools [L, Hkv, n_pages, page, Dh] (V: Dv), or
    STORED folded ([.., page // f, f * Dh], ``stored_fold`` f = 128 // Dh:
    the order the copies take, read in place); layer: [1]
    int32; keep: [B, P * page] bool or None. Returns q4-shaped. ``interpret`` exists for the CPU test suite
    only — the serving path always compiles this variant (paged_attention
    gates it to real TPUs). ``new`` = (k_new [B, Hkv, Dh], v_new [B, Hkv,
    Dv]): the kernel writes the rows of token ``lengths - 1`` itself
    (``lengths`` unclamped: a lane of 0 writes nothing) and the result is
    (out, k_pool, v_pool), the pools aliased to the operands; they must be
    stored as the kernel reads them (:func:`paged_kernel_writes`)."""
    B, Hkv, G, Dh = q4.shape
    L, _, n_pages, page, _ = k_pool.shape
    page *= stored_fold
    Dv = v_pool.shape[-1] // stored_fold
    P = page_tables.shape[1]
    ppb = min(pages_per_block, P)
    if P % ppb:
        page_tables = jnp.pad(page_tables, ((0, 0), (0, ppb - P % ppb)))
        P = page_tables.shape[1]
    NB = P // ppb
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)

    # Dh < 128: fold tokens so DMA rows are 128-lane aligned. At Dh >= 128
    # nothing is folded and the kernel reads the pool as stored, as it does
    # a pool that is STORED folded (``LlamaConfig.kv_fold``); a pool stored
    # [.., page, Dh] with Dh < 128 is folded here, a relayout in tiled HBM,
    # which is why paged_attention hands this function one layer's slice of
    # such a pool and never the pool.
    fold = max(1, 128 // Dh)
    if stored_fold not in (1, fold):
        raise ValueError(f"a pool stored folded by {stored_fold} at head_dim "
                         f"{Dh}: the kernel's rows hold {fold} tokens")
    if page % fold:
        raise ValueError(f"page size {page} not divisible by fold {fold}")
    if fold > 1 and Dv != Dh:
        raise ValueError(f"V rows of their own width ({Dv}) need K rows of "
                         f"at least a lane tile (got {Dh})")
    if new is not None and stored_fold != fold:
        raise ValueError(
            f"the kernel writes only into a pool stored as it reads it: "
            f"rows of {fold} token(s) at head_dim {Dh}, got {stored_fold}")
    stored = k_pool.shape, v_pool.shape
    k_pool = k_pool.reshape(L, Hkv, n_pages, page // fold, fold * Dh)
    v_pool = v_pool.reshape(L, Hkv, n_pages, page // fold, fold * Dv)

    selected = keep is not None
    sel_specs, sel_args = [], []
    if selected:
        if fold > 1:
            raise ValueError(
                f"the paged dma kernel takes a selection only at head_dim "
                f">= 128 (got {Dh}): folded rows hold {fold} tokens")
        L2 = ppb * page
        keep = keep.astype(jnp.int32)
        keep = jnp.pad(keep, ((0, 0), (0, NB * L2 - keep.shape[1])))
        sel_specs = [pl.BlockSpec((1, 1, L2), lambda b, j, *_: (b, 0, j))]
        sel_args = [keep[:, None, :]]
    own = {}                 # static parameters only a per-kind model sets
    if sink is not None:
        sel_specs.append(pl.BlockSpec((Hkv, G, 1), lambda b, j, *_: (0, 0, 0)))
        sel_args.append(sink.astype(jnp.float32).reshape(Hkv, G, 1))
        own["sunk"] = True
    if Dv != Dh:
        own["dv"] = Dv
    out_specs = pl.BlockSpec((1, Hkv, G, Dv), _lane_block)
    out_shape = jax.ShapeDtypeStruct((B, Hkv, G, Dv), q4.dtype)
    scratch, aliases = [], {}
    if new is not None:
        # the new rows as [B, 1, Hkv * fold * D], a lane a block: the heads
        # side by side, a head's row repeated across its lanes so that one
        # select places it in a folded pool row. Of the forms tried this is
        # the one XLA builds the rest of the step around best (rows heads
        # outermost cost fewer copies a layer and more overall: the sampler's
        # operands and ``wv`` leave the chip's fast memory; PERF.md section
        # 6, PR 38)
        for a, pool in zip(new, (k_pool, v_pool)):
            sel_specs.append(pl.BlockSpec((1, 1, Hkv * pool.shape[-1]),
                                          lambda b, j, *_: (b, 0, 0)))
            sel_args.append(jnp.tile(a.astype(pool.dtype), (1, 1, fold))
                            .reshape(B, 1, -1))
        own["writes"] = True
        hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
        # the attention output stays the FIRST result: a trace names a
        # tuple-valued custom call by its first type, and the benchmark's
        # operation lists name the kernel by it
        out_specs = [out_specs, hbm, hbm]
        out_shape = [out_shape, *(jax.ShapeDtypeStruct(p.shape, p.dtype)
                                  for p in (k_pool, v_pool))]
        grp = _write_group(page // fold, k_pool.dtype)
        scratch = [
            pltpu.VMEM((_WRITE_RING, Hkv, grp, fold * Dh), k_pool.dtype),
            pltpu.VMEM((_WRITE_RING, Hkv, grp, fold * Dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((_WRITE_RING, 2)),       # [ring, k/v]
        ]
        # operands count from the three prefetched scalars: q4 is 3
        aliases = {4: 1, 5: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), _lane_block),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            *sel_specs,
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, ppb, page // fold, fold * Dh), k_pool.dtype),
            pltpu.VMEM((2, Hkv, ppb, page // fold, fold * Dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),                 # [slot, k/v]
            pltpu.VMEM((Hkv, G, 1), jnp.float32),            # m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),            # l
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),           # acc
            # buffer slot; with ``new``, the write-backs started
            pltpu.SMEM((1 if new is None else 2,), jnp.int32),
            *scratch,
        ],
    )
    res = pl.pallas_call(
        functools.partial(_paged_dma_kernel, scale=scale, page=page,
                          ppb=ppb, hkv=Hkv, fold=fold, dh=Dh,
                          softcap=softcap, window=window, selected=selected,
                          **own),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        **({"input_output_aliases": aliases} if aliases else {}),
    )(page_tables, lengths, layer, q4, k_pool, v_pool, *sel_args)
    if new is None:
        return res
    out, k_pool, v_pool = res
    return out, k_pool.reshape(stored[0]), v_pool.reshape(stored[1])


def _paged_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, page: int, softcap: Optional[float],
                  window: Optional[int], selected: bool,
                  sunk: bool = False):
    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    sink_ref, rest = (rest[0], rest[1:]) if sunk else (None, rest)
    o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    npages = (length + page - 1) // page
    if window is None:
        in_range = p < npages
    else:
        # page-range clamp: pages wholly below the window start contribute
        # nothing — skip their compute entirely
        pstart = jnp.maximum(length - window, 0) // page
        in_range = (p >= pstart) & (p < npages)

    @pl.when(in_range)
    def _():
        q = q_ref[0]                                       # [Hkv, G, Dh]
        k = k_ref[0, :, 0]                                 # [Hkv, page, Dh]
        v = v_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # [Hkv, G, page]
        if softcap is not None:
            # cap BEFORE masking (tanh(NEG_INF) would be a finite ±cap)
            s = jnp.tanh(s / softcap) * softcap
        tok = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2) + p * page
        mask = tok < length
        if window is not None:
            mask = mask & (tok >= length - window)
        if keep_ref is not None:
            mask = mask & (keep_ref[...] > 0)              # [1, 1, page]
        m_prev = m_scr[:]
        m_cur = jnp.max(jnp.where(mask, s, NEG_INF), axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pw = jnp.where(mask, jnp.exp(s - m_new), 0.0)      # [Hkv, G, page]
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(pw, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pw.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [Hkv, G, Dh]
        m_scr[:] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        l = l_scr[:]
        if sink_ref is not None:
            l = l + jnp.exp(sink_ref[...] - m_scr[:])
        o = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


def paged_kernel_variant(interpret: bool) -> str:
    """Which paged-attention kernel :func:`paged_attention` runs:
    ``dma`` (multi-page double-buffered, compiled — the default on a TPU),
    ``simple`` (one page per grid step, compiled —
    ``DYNAMO_TPU_PAGED_KERNEL=simple``) or ``simple[interpret]`` (off-TPU).
    The engine reports this same value, so what a run says it ran is what
    the kernel entry point selected."""
    variant = os.environ.get("DYNAMO_TPU_PAGED_KERNEL", "dma")
    if variant not in ("dma", "simple"):
        # repo convention: a typo'd env flag must not silently select the
        # slow path (cf. DYNAMO_TPU_DATAPLANE / DYNAMO_TPU_STORE)
        raise ValueError(f"DYNAMO_TPU_PAGED_KERNEL={variant!r} "
                         f"(expected dma|simple)")
    return "simple[interpret]" if interpret else variant


def paged_kernel_writes(interpret: bool, head_dim: int, fold: int) -> bool:
    """Whether :func:`paged_attention` can take a decode step's new rows
    (``new``) and write them itself: on the dma kernel, into a pool stored as
    that kernel reads it (rows of ``head_dim`` >= 128, or ``fold`` = 128 //
    ``head_dim`` tokens to a row). Any other pool keeps ``kv_write``; the
    engine reports which (``dyn_engine_info{decode_kv_write}``)."""
    return (paged_kernel_variant(interpret) == "dma"
            and fold == max(1, 128 // head_dim))


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_tables: jax.Array, lengths: jax.Array,
                    layer=None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    window: Optional[int] = None,
                    keep: Optional[jax.Array] = None,
                    sink: Optional[jax.Array] = None,
                    fold: int = 1,
                    new: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Decode attention straight over the paged KV pool.

    q: [B, Hq, Dh] (one new token per sequence, already rope'd)
    k_pool, v_pool: [L, Hkv, n_pages, page, Dh] — the WHOLE stored pool,
      read in place; ``layer`` (an int or a traced int32 scalar) picks the
      layer inside the kernel's page copies, so no caller slices the pool.
      A single layer's [Hkv, n_pages, page, Dh] is taken as a pool of one
      layer (``layer`` must then be left out).
    page_tables: [B, P] int32 page ids (rows padded with page 0)
    lengths: [B] int32 — tokens to attend per sequence (including current)
    Returns [B, Hq, Dh]. Sequences attend to tokens [0, length); with
    ``window`` only [max(0, length - window), length). The DMA kernel
    clamps its active block range, so out-of-window pages cost neither
    copies nor compute (sliding decode reads O(window) bytes); the simple
    kernel skips only their compute — its BlockSpec pipeline still copies
    every page. ``softcap`` tanh-caps scores pre-softmax (Gemma2);
    ``scale`` overrides rsqrt(Dh) (query_pre_attn_scalar). ``window`` and
    ``softcap`` are static (one Mosaic kernel per class); ``layer`` is
    dynamic, so all layers of a class share that kernel. ``keep`` [B, P *
    page] bool (a model with an indexer: :func:`topk_keep` over the lane's
    logical positions) restricts the lane to its selected keys; every page
    is still read. V rows may have a width of their own (``v_pool`` [...,
    Dv]: the result is [B, Hq, Dv]); ``sink`` [Hq] float32 is a logit a head
    that takes softmax weight and gives no value. ``fold`` f > 1: the pools
    are STORED folded, [L, Hkv, n_pages, page // f, f * Dh] (models/llama.py
    "KV pool access"): the dma kernel copies such rows as they lie, whole
    pool and traced ``layer`` as at Dh >= 128. ``new`` = (k_new [B, Hkv,
    Dh], v_new [B, Hkv, Dv]): the rows of each lane's token ``lengths - 1``,
    NOT yet in the pools: the dma kernel puts them there and attends over
    them (a lane of length 0 writes nothing), and the result is (out,
    k_pool, v_pool) with the pools updated in place where the caller donates
    them. Only where :func:`paged_kernel_writes` says so.

    On a TPU this runs the multi-page double-buffered DMA kernel above
    (``DYNAMO_TPU_PAGED_KERNEL=simple`` selects the BlockSpec-pipelined
    one-page-per-step kernel below, compiled); off-TPU (and under
    ``interpret=True``) the simple kernel runs in interpreter mode so the
    CPU test suite exercises the same contract. See
    :func:`paged_kernel_variant`.
    """
    whole = k_pool.ndim == 5
    if not whole:
        if layer is not None:
            raise ValueError("a layer index needs the whole 5-D pool")
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B, Hq, Dh = q.shape
    if interpret is None:
        interpret = not on_tpu()
    if new is not None:
        if not paged_kernel_writes(interpret, Dh, fold):
            raise ValueError(
                f"no paged kernel writes these pools (kernel "
                f"{paged_kernel_variant(interpret)!r}, head_dim {Dh}, rows "
                f"of {fold} token(s)): the caller scatters (kv_write)")
    if fold > 1 and paged_kernel_variant(interpret) != "dma":
        # the one-page-a-step kernel blocks [page, Dh]: unfold (a plain
        # reshape: a folded page's rows are its tokens in order)
        k_pool, v_pool = (p.reshape(*p.shape[:3], p.shape[3] * fold,
                                    p.shape[4] // fold)
                          for p in (k_pool, v_pool))
        fold = 1
    _, Hkv, n_pages, page, _ = k_pool.shape
    page *= fold
    Dv = v_pool.shape[-1] // fold
    G = Hq // Hkv
    P = page_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if Dh < 128 and fold == 1 and k_pool.shape[0] > 1:
        # rows narrower than a lane tile, stored [.., page, Dh]: XLA's
        # programs keep such a pool in another order than the kernels'
        # operands take (pages minor; seen on a v5e), so every call re-lays
        # what it is given. Give it one layer's slice to re-lay, never the
        # pool. A pool stored FOLDED (``fold``) has whole-tile rows, is kept
        # as stored and is read in place (PERF.md §7 b).
        k_pool, v_pool = (jax.lax.dynamic_index_in_dim(p, layer[0], 0)
                          for p in (k_pool, v_pool))
        layer = jnp.zeros_like(layer)
    # The TPU kernel's prefetch chain assumes every lane covers >=1 block
    # (nblocks==0 would leave a DMA slot un-consumed and stall the next
    # active lane). Enforce the invariant here rather than relying on
    # callers to pad lengths (a kernel that writes tells a lane of 0, which
    # writes nothing, by the unclamped value, and clamps for itself).
    if new is None:
        lengths = jnp.maximum(lengths, 1)
    if paged_kernel_variant(interpret) == "dma":
        q4 = q.reshape(B, Hkv, G, Dh)
        # DMA depth knob for on-chip tuning sweeps (read the kernel's time
        # in a traced benchmark run's ops_by_module) — larger blocks
        # amortize DMA issue latency, smaller ones cut the tail wasted on
        # the final partial block. Validated like the sibling
        # DYNAMO_TPU_PAGED_KERNEL knob: a typo must fail loudly, not
        # surface as a ZeroDivisionError deep in the grid math.
        raw_ppb = os.environ.get("DYNAMO_TPU_PAGED_PPB", "8")
        try:
            ppb = int(raw_ppb)
        except ValueError:
            ppb = -1
        if not 1 <= ppb <= 64:
            raise ValueError(f"DYNAMO_TPU_PAGED_PPB={raw_ppb!r} "
                             f"(expected an integer in [1, 64])")
        out = _paged_attention_tpu(q4, k_pool, v_pool, layer, page_tables,
                                   lengths, pages_per_block=ppb,
                                   scale=scale, softcap=softcap,
                                   window=window, keep=keep,
                                   interpret=interpret,
                                   **({} if sink is None else {"sink": sink}),
                                   **({} if fold == 1
                                      else {"stored_fold": fold}),
                                   **({} if new is None else {"new": new}))
        if new is None:
            return out.reshape(B, Hq, Dv)
        out, k_pool, v_pool = out
        if not whole:
            k_pool, v_pool = k_pool[0], v_pool[0]
        return out.reshape(B, Hq, Dv), k_pool, v_pool
    selected = keep is not None
    if selected and not interpret:
        raise ValueError(
            "DYNAMO_TPU_PAGED_KERNEL=simple takes no selection when "
            "compiled (a [1, 1, page] block does not tile); a model with "
            "an indexer decodes through the dma kernel")
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)

    q4 = q.reshape(B, Hkv, G, Dh)

    def page_map(b, p, pt, ln, ly):
        return (ly[0], 0, pt[b, p], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), _lane_block),
            pl.BlockSpec((1, Hkv, 1, page, Dh), page_map),
            pl.BlockSpec((1, Hkv, 1, page, Dv), page_map),
            *([pl.BlockSpec((1, 1, page), lambda b, p, *_: (b, 0, p))]
              if selected else []),
            *([pl.BlockSpec((Hkv, G, 1), lambda b, p, *_: (0, 0, 0))]
              if sink is not None else []),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dv), _lane_block),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # m
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # l
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page=page,
                          softcap=softcap, window=window, selected=selected,
                          **({} if sink is None else {"sunk": True})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(page_tables, lengths, layer, q4, k_pool, v_pool,
      *([keep.astype(jnp.int32)[:, None, :]] if selected else []),
      *([sink.astype(jnp.float32).reshape(Hkv, G, 1)]
        if sink is not None else []))
    return out.reshape(B, Hq, Dv)
