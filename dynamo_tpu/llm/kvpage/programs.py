"""Jitted programs for paged (working-set-bounded) prefill and decode.

The standard engine runs attention as ONE dispatch over the whole
context — every KV page must be device-resident when it runs. These
programs decompose each layer's attention into *partial* passes with
online-softmax accumulators (the flash-attention recurrence, applied
across dispatches instead of across kernel tiles):

- the ``attn_hot`` program attends over the device-resident tail
  (read through the pool, causally masked, window-masked on sliding
  layers);
- the ``attn_cold`` program attends over one staged segment of demoted
  blocks per lane, uploaded h2d into a shared [B, ...] staging slot (all
  cold positions strictly precede every query, so causality is free;
  sliding layers additionally window-mask against each lane's own
  segment positions);
- :meth:`PagedPrograms.layer_out` normalizes the merged accumulators and
  finishes the layer (o-proj, residual, FFN).

Splitting per (layer, segment) is what makes bounded residency possible:
between partial passes only the tiny per-chunk activations and the f32
(o, m, d) accumulators persist on device, so the cold tail can stream
through a fixed pair of staging slots regardless of context length.
Exactness: softmax reassociation is the only difference from the dense
path — accumulation stays f32 end to end, and the long-context bench
lane pins token-identity against an unpaged run. Batching is exact too:
masked/padded positions contribute exactly ``0.0`` to the f32 sums and
sampling is row-independent, so each lane's token stream is
byte-identical at any batch width (``tests/test_kvpage.py`` pins B=4
against B=1 against the dense engine).

The layer index rides every program as a TRACED scalar (stacked layer
params are gathered with it), so the whole layer stack replays a
constant number of compiled variants, not O(L). Models with per-layer
STATIC structure (sliding-window masks, dual-base rope) compile one
variant per layer *class* instead: the window span and rope-table choice
are closure constants of the class's programs (mirroring the dense
path's ``flash_for`` per-class kernel cache), which is what lifted the
former sliding-window/dual-rope exclusions — Gemma2/3-style models have
exactly two classes, so the program count stays constant.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ...models import llama
from ...models.llama import NEG_INF


def _merge(o0, m0, d0, o1, m1, d1):
    """Online-softmax merge of two partial-attention accumulators.
    Shapes: o [B, Hkv, G, T, Dh] f32; m, d [B, Hkv, G, T] f32."""
    m = jnp.maximum(m0, m1)
    a0 = jnp.exp(m0 - m)
    a1 = jnp.exp(m1 - m)
    return (o0 * a0[..., None] + o1 * a1[..., None],
            m, d0 * a0 + d1 * a1)


def _partial_attend(cfg, q, k, v, mask):
    """Unnormalized attention stats for one KV span.

    q: [B, T, Hq, Dh]; k, v: [B, S, Hkv, Dh]; mask: [B, T, S] bool.
    Returns (o [B,Hkv,G,T,Dh], m [B,Hkv,G,T], d [B,Hkv,G,T]), all f32.
    Scores mirror :func:`llama.attend` (scale then softcap then mask).
    Rows whose mask is all-False yield (0, NEG_INF, 0): an exact no-op
    under :func:`_merge`, which is what makes padded lanes free."""
    Hq = cfg.num_heads
    Hkv = cfg.num_kv_heads
    G = Hq // Hkv
    B, T, _, Dh = q.shape
    qg = q.reshape(B, T, Hkv, G, Dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * cfg.attn_scale
    if cfg.attn_logit_softcap:
        scores = jnp.tanh(scores / cfg.attn_logit_softcap) \
            * cfg.attn_logit_softcap
    mg = mask[:, None, None, :, :]                      # [B,1,1,T,S]
    scores = jnp.where(mg, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                        # [B,Hkv,G,T]
    p = jnp.where(mg, jnp.exp(scores - m[..., None]), 0.0)
    d = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgts,bshd->bhgtd", p, v.astype(jnp.float32))
    return o, m, d


class PagedPrograms:
    """The compiled-program surface of the paged path, built once per
    engine. All programs take a leading batch dim (1 for prefill-chunk
    dispatches, the lane count for batched decode); per-layer-static
    model structure selects a compiled variant via
    :attr:`layer_programs`."""

    def __init__(self, cfg, mesh, rep_sharding, kv_sharding):
        self.cfg = cfg
        m = cfg.model
        rep, kv = rep_sharding, kv_sharding
        page = cfg.page_size

        # Layer classes: the per-layer STATIC attention structure.
        # (window span, local-rope?) — full-attention layers are
        # (None, False); Gemma2/3 sliding layers carry their window and
        # (gemma3) the local-theta rope table. Each distinct class gets
        # its own compiled qkv/attn_hot/attn_cold variants with the
        # statics baked in as closure constants; the layer index stays
        # traced WITHIN a class.
        classes: List[Tuple[Optional[int], bool]] = []
        layer_cls: List[int] = []
        for l in range(m.num_layers):
            if m.layer_sliding(l):
                key = (int(m.sliding_window),
                       m.rope_local_theta is not None)
            else:
                key = (None, False)
            if key not in classes:
                classes.append(key)
            layer_cls.append(classes.index(key))
        self.classes = classes
        #: per-layer window span (None = full attention), for the
        #: runner's page-in plan clamping
        self.windows: List[Optional[int]] = [
            classes[c][0] for c in layer_cls]

        def embed(params, tokens):
            return llama._embed(params, m, tokens)

        self.embed = jax.jit(embed, out_shardings=rep)

        def make_qkv(local: bool):
            def qkv(params, l, x, positions, k_pool, v_pool, write_idx):
                flat_w = write_idx.reshape(-1)
                q, (k_pool, v_pool), _ = llama.layer_in(
                    x, params["layers"], l, m,
                    llama.rope_tables(m, positions, local=local),
                    (k_pool, v_pool), flat_w // page, flat_w % page)
                return q, k_pool, v_pool

            return jax.jit(qkv, donate_argnums=(4, 5),
                           out_shardings=(rep, kv, kv))

        def make_attn_hot(window: Optional[int]):
            def attn_hot(q, l, k_pool, v_pool, read_idx, read_pos,
                         read_valid, positions):
                rp, ro = read_idx // page, read_idx % page
                # [B, S, Hkv, Dh], each lane reading its own slots
                k_ctx = llama.kv_rows(k_pool, l, rp, ro)
                v_ctx = llama.kv_rows(v_pool, l, rp, ro)
                mask = (read_valid[:, None, :]
                        & (read_pos[:, None, :] <= positions[:, :, None]))
                if window is not None:
                    # dense-path sliding rule: keys strictly within the
                    # last `window` positions of each query
                    mask = mask & (read_pos[:, None, :]
                                   > positions[:, :, None] - window)
                return _partial_attend(m, q, k_ctx, v_ctx, mask)

            return jax.jit(attn_hot, out_shardings=(rep, rep, rep))

        def make_attn_cold(window: Optional[int]):
            def attn_cold(q, positions, kv_seg, meta, o, m_, d):
                # kv_seg: [2, B, n, Hkv, page, Dh] — one staged segment
                # PER LANE (k stacked over v so the whole slot is ONE
                # h2d transfer). meta: [B, 2] int32 = (valid blocks,
                # first token position) per lane; the validity and
                # position vectors are rebuilt on device from those two
                # scalars — cold segments are contiguous pinned-prefix
                # runs, so a prefix-block count and a start offset carry
                # everything the mask needs. Rows whose lane has no
                # segment at this step ride along with meta (0, 0):
                # all-invalid, an exact no-op under _merge. Cold
                # positions strictly precede every query, so only
                # validity (and, on sliding layers, each lane's own
                # window against the rebuilt positions) masks.
                k_seg, v_seg = kv_seg[0], kv_seg[1]
                B, n = k_seg.shape[0], k_seg.shape[1]
                k_ctx = jnp.transpose(k_seg, (0, 1, 3, 2, 4)).reshape(
                    B, n * page, k_seg.shape[2], k_seg.shape[4])
                v_ctx = jnp.transpose(v_seg, (0, 1, 3, 2, 4)).reshape(
                    B, n * page, v_seg.shape[2], v_seg.shape[4])
                iota = jnp.arange(n * page, dtype=jnp.int32)
                seg_valid = (iota // page)[None, :] < meta[:, 0:1]
                seg_pos = meta[:, 1:2] + iota[None, :]
                T = q.shape[1]
                mask = jnp.broadcast_to(seg_valid[:, None, :],
                                        (B, T, n * page))
                if window is not None:
                    # mirrors ops/attention.py's dense sliding rule
                    # `kp > qp - window` — keep the two in lockstep
                    mask = mask & (seg_pos[:, None, :]
                                   > positions[:, :, None] - window)
                o1, m1, d1 = _partial_attend(m, q, k_ctx, v_ctx, mask)
                return _merge(o, m_, d, o1, m1, d1)

            return jax.jit(attn_cold, donate_argnums=(4, 5, 6),
                           out_shardings=(rep, rep, rep))

        qkv_c = {loc: make_qkv(loc) for loc in {c[1] for c in classes}}
        hot_c = {w: make_attn_hot(w) for w in {c[0] for c in classes}}
        cold_c = {w: make_attn_cold(w) for w in {c[0] for c in classes}}
        #: per-layer (qkv, attn_hot, attn_cold, window) dispatch table —
        #: layers of the same class share the same compiled callables
        self.layer_programs = [
            (qkv_c[classes[c][1]], hot_c[classes[c][0]],
             cold_c[classes[c][0]], classes[c][0])
            for c in layer_cls]

        def layer_out(params, l, x, o, m_, d):
            B, Hkv, G, T, Dh = o.shape
            attn = o / jnp.where(d == 0.0, 1.0, d)[..., None]
            attn = jnp.transpose(attn, (0, 3, 1, 2, 4)).reshape(
                B, T, Hkv * G, Dh).astype(x.dtype)
            return llama.layer_out(x, attn, params["layers"], l, m)

        self.layer_out = jax.jit(layer_out, out_shardings=rep)

        def head(params, x, last_i, temp, top_p, top_k, key, counts,
                 freq_pen, pres_pen, active):
            from ...engine.sampling import apply_penalties, sample
            xs = jnp.take_along_axis(
                x, last_i[:, None, None].astype(jnp.int32), axis=1)
            logits = llama._lm_head(xs, params, m)[:, 0]       # [B, V]
            lg = apply_penalties(logits, counts, freq_pen, pres_pen)
            tok, logp, new_key = sample(lg, temp, top_p, top_k, key, active)
            B = tok.shape[0]
            # inactive rows (padded decode lanes) must not perturb the
            # lane-persistent sampling state: their penalty counts stay
            # put and their PRNG keys do not advance, so a lane's draws
            # are independent of which OTHER lanes shared its windows
            counts = counts.at[jnp.arange(B), tok].add(
                active.astype(jnp.int32))
            new_key = jnp.where(active, new_key, key)
            # token ids < 2^24 are exact in f32: one packed (token,
            # logprob) array = one host fetch per sampled window
            packed = jnp.stack([tok.astype(jnp.float32), logp], -1)
            return packed, new_key, counts

        self.head = jax.jit(head, donate_argnums=(7,),
                            out_shardings=(rep, rep, rep))

    # ------------------------------------------------------------------
    @staticmethod
    def validate(cfg) -> Optional[str]:
        """Why this engine config cannot run the paged path (None = ok).
        Sliding-window and dual-base-rope models compile per layer-class
        variants and ARE servable; what remains excluded is structure the
        segmented forward itself cannot express."""
        m = cfg.model
        if m.has_state:
            what = ("gated short-convolution" if m.has_conv
                    else "state-space")
            return f"models with {what} layers ({llama.NO_STATE})"
        if m.has_latent:
            return f"models with latent attention ({llama.NO_LATENT})"
        if m.per_kind:
            return (f"models whose window layers keep a cache of their own "
                    f"({llama.NO_SECOND_CACHE})")
        if m.num_experts:
            return "MoE models"
        if m.has_indexer:
            return f"models with an indexer ({llama.NO_INDEX_KEYS})"
        if m.vision is not None:
            return "VLM deployments (image spans need the dense path)"
        if cfg.pp > 1 or cfg.sp > 1:
            return "pp/sp parallel engines"
        return None
