"""Reference ``keye_vl2``: a float32 ``jax.numpy`` forward of the language
model of Keye-VL-2.0-30B-A3B, written from its published ``config.json``
(``https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json``)
and the two published mechanisms it names: GQA attention over a learned
top-k of the cache (a DeepSeek-Sparse-Attention indexer, ``sa_config``) and
routed experts (the Qwen3-MoE family's keys). No kernel, no cache, no
batching, ``jax.default_matmul_precision("highest")``. The contract of a
reference file (``build``, ``tail_logprobs``) is in ``harness/catalog.py``.

One layer, on a token's residual stream x (D wide), as written below:

1. ``h = RMSNorm(x; ln1)``; ``q = h Wq`` [Hq x Dh], ``k = h Wk``, ``v = h
   Wv`` [Hkv x Dh], no bias; q and k RMSNorm'ed per head (``ln_q``,
   ``ln_k``), then rotate-half rotary over all Dh dims.
2. Indexer: ``qI = h WIq`` [Hi x Di], ``kI = LayerNorm(h WIk)`` [Di] (ONE
   index key head), ``w = h WIw`` [Hi]; rotary (same theta) on qI and kI.
   ``I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])``; ``S_t`` = the
   ``min(topk, t+1)`` keys s <= t with the largest I (``lax.top_k``: a tie
   goes to the lower position).
3. ``o_t = sum_{s in S_t} softmax_s(q_t . k_s / sqrt(Dh)) v_s`` per head,
   query head h reading key/value head h // (Hq // Hkv); ``x += o Wo``.
4. ``h2 = RMSNorm(x; ln2)``; ``p = softmax(h2 Wr)`` over the E experts;
   ``T`` = top-K of p; ``g_e = p_e / sum_T p``; ``x += sum_{e in T} g_e
   Wd_e(silu(Wg_e h2) * Wu_e h2)``.
5. After the last layer: RMSNorm, untied head, float32 log-softmax.

Departures from the published description, each because the config does not
say (the configuration file lists them under ``assumed``): q/k RMSNorm per
head (the Qwen3-MoE convention, whose key names the config carries); the
indexer reads the layer's normed input h (DeepSeek's reads a query latent
that a GQA model does not have); LayerNorm (weight and bias, the model's
eps) on kI and rotary over all Di index dims; ``q_chunk_size`` /
``kv_chunk_size`` tile the indexer's computation and select nothing; no
scale on I (any positive scale selects the same set); M-RoPE sections are
ordinary rotary for text (the three position axes are equal); the vision
tower is left out.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls. The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]; lm_head [D,V]
    layers.* stacked on L: ln1, ln2 [L,D]; ln_q, ln_k [L,Dh]; wq
    [L,D,Hq,Dh]; wk, wv [L,D,Hkv,Dh]; wo [L,Hq,Dh,D]; wiq [L,D,Hi,Di]; wik
    [L,D,Di]; wiw [L,D,Hi]; ln_ik_w, ln_ik_b [L,Di]; wr [L,D,E]; wg, wu
    [L,E,D,F]; wd [L,E,F,D]

The weights stay in bfloat16 as the program made them and are upcast one
layer at a time inside the scan; attention, index scores and experts are
computed a block of ``BLOCK`` queries at a time (every expert on every
token of the block, gated: 16 times the routed work, and plain), so that a
14,400-token sequence fits beside 8.75 GB of weights.

Near-tied routing, scored under BOTH routings (step 4). Top-K routing is
discontinuous: where the K-th and the (K+1)-th expert of a token lie closer
in router logit than the served path's bfloat16 arithmetic resolves, served
and reference may choose different experts with nothing wrong, and the two
outputs differ by a whole expert. The router here is the model's own
(float32 softmax of ``h2 Wr``, top-K, renormalised). Where the float32
margin ``m`` between the K-th and the (K+1)-th logit of a (position, layer)
is under ``TIE_EPS`` router logits, the expert output of that position is
computed under both chosen sets (the K-th in, or the (K+1)-th in its place,
each renormalised over its own set) and the two are mixed ``(1 + m /
TIE_EPS) / 2`` to ``(1 - m / TIE_EPS) / 2``: half and half at an exact tie,
the model's own routing alone from ``TIE_EPS`` on, continuous between.
``TIE_EPS`` is ``2 ** -7`` logits: two units in the last place of a
bfloat16 number of size 1, the size of a router logit here (the served
path's normed input ``h2`` is bfloat16; its router logits and softmax are
float32). At the published widths a token's K-th and (K+1)-th logit of 128
lie 0.045 apart in the median, so 12 % of (position, layer) pairs are so
treated; where the served path and this file were seen to choose other
experts in a model's first layer, nine in ten margins were under 0.005
(PERF.md section 6, PR 28). A wider margin mixes more positions than part:
2 ** -5 (39 % of pairs) and 2 ** -6 read no better than none over five
pairs of runs (-25 % to +24 % on the root mean square of 32 positions).
Every call says on standard error how many (position, layer) pairs were so
treated, of how many. ``TIE_EPS`` is no tolerance of the comparison: it
widens no limit of ``harness/correct.py``, and a position that is no
near-tie is scored exactly as before.

Variants: ``full``; the probe's two (``dropped_layer``: the last layer
switched off; ``int8``: every weight matrix rounded to int8 per output
channel); and this model's own controls (``benchmarks/tests/
own_variants.py`` scores the served tokens under each through
``correct.compare``): ``no_selection`` (full causal attention), ``top7``
(one expert fewer), ``experts_int8`` (only the experts' three matrices
rounded to int8) and ``experts_zeroed`` (the experts' output left out).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "no_selection", "top7",
            "experts_int8", "experts_zeroed")
BLOCK = 128
TIE_EPS = 2.0 ** -7        # router logits; see "Near-tied routing" above


def hf_dims(hf: dict) -> dict:
    sa = hf["sa_config"]
    return {
        "L": hf["num_hidden_layers"], "D": hf["hidden_size"],
        "Hq": hf["num_attention_heads"], "Hkv": hf["num_key_value_heads"],
        "Dh": hf["head_dim"], "V": hf["vocab_size"],
        "E": hf["num_experts"], "K": hf["num_experts_per_tok"],
        "Hi": sa["indexer_num_heads"], "Di": sa["indexer_head_dim"],
        "topk": sa["topk"], "theta": float(hf["rope_theta"]),
        "eps": float(hf["rms_norm_eps"]),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jnp.reciprocal(jnp.sqrt(var + eps)) * w + b


def rotary(x, positions, theta):
    """x [T,H,d]; rotate-half: the first and second halves are the pairs."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fake_int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


def index_scores(qi, ki, w):
    """I[t,s] of step 2; qi [t,Hi,Di], ki [S,Di], w [t,Hi]."""
    import jax
    import jax.numpy as jnp

    dots = jnp.einsum("thd,sd->ths", qi, ki)
    score = jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)
    return jnp.where(score == 0.0, 0.0, score)      # -0.0 and 0.0 are one


def selected(score, causal, topk):
    """S_t as a mask [t,S]: the top ``min(topk, visible)`` of the visible."""
    import jax
    import jax.numpy as jnp

    k = min(topk, score.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), k)
    rows = jnp.arange(score.shape[0])[:, None]
    return causal & jnp.zeros(score.shape, bool).at[rows, idx].set(True)


def route(h2, wr, k, tie_eps=TIE_EPS):
    """-> (gates over all E experts [t,E], chosen expert ids [t,k], near
    [t] bool: the k-th and (k+1)-th logit within ``tie_eps``). The chosen
    ids are the model's own top-k; the gates of a near-tied token mix the
    two routings (the module's text)."""
    import jax
    import jax.numpy as jnp

    z = h2 @ wr
    p = jax.nn.softmax(z, axis=-1)
    vals, idx = jax.lax.top_k(p, k + 1)
    rows = jnp.arange(h2.shape[0])[:, None]

    def gates_of(v, i):
        return jnp.zeros_like(p).at[rows, i].set(
            v / jnp.sum(v, axis=-1, keepdims=True))

    own = gates_of(vals[:, :k], idx[:, :k])
    if not tie_eps:
        return own, idx[:, :k], jnp.zeros(h2.shape[0], bool)
    other = gates_of(jnp.concatenate([vals[:, :k - 1], vals[:, k:]], -1),
                     jnp.concatenate([idx[:, :k - 1], idx[:, k:]], -1))
    zk = jnp.take_along_axis(z, idx[:, k - 1:], axis=-1)
    margin = zk[:, 0] - zk[:, 1]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    return w * own + (1.0 - w) * other, idx[:, :k], near


# what a variant changes of the layer (``how`` below); ``full`` is HOW
HOW = {"int8": False, "experts_int8": False, "experts": 1.0, "select": True,
       "drop_experts": 0, "tie_eps": TIE_EPS}
HOW_OF = {
    "full": {}, "dropped_layer": {}, "int8": {"int8": True},
    "no_selection": {"select": False}, "top7": {"drop_experts": 1},
    "experts_int8": {"experts_int8": True},
    "experts_zeroed": {"experts": 0.0},
}


def layer(x, lp, dims, on, how, trace=False):
    """One block on x [T,D] float32 (T a multiple of ``BLOCK``, or any T as
    one block); ``lp`` is one layer's slice of the stacked weights, upcast
    here. ``on`` (0 or 1) switches the layer off for the probe; ``how`` is
    ``HOW`` with a variant's changes. -> (x, near-tied tokens [T] bool);
    with ``trace`` the second is (selected mask [T,T], chosen experts
    [T,K], index scores [T,T], router probabilities [T,E])."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    e8 = ((lambda w, ax: fake_int8(w, ax))
          if how["int8"] or how["experts_int8"] else (lambda w, ax: w))
    T = x.shape[0]
    Hq, Hkv, Dh = dims["Hq"], dims["Hkv"], dims["Dh"]
    eps, theta = dims["eps"], dims["theta"]
    select = how["select"]
    pos = jnp.arange(T)
    h = rms_norm(x, f32(lp["ln1"]), eps)
    q = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wq"]), (0,)))
    k = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wk"]), (0,)))
    v = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wv"]), (0,)))
    q = rotary(rms_norm(q, f32(lp["ln_q"]), eps), pos, theta)
    k = rotary(rms_norm(k, f32(lp["ln_k"]), eps), pos, theta)
    qi = jnp.einsum("td,dhk->thk", h, q8(f32(lp["wiq"]), (0,)))
    ki = layer_norm(h @ q8(f32(lp["wik"]), (0,)), f32(lp["ln_ik_w"]),
                    f32(lp["ln_ik_b"]), eps)
    w = h @ q8(f32(lp["wiw"]), (0,))
    qi = rotary(qi, pos, theta)
    ki = rotary(ki[:, None, :], pos, theta)[:, 0]

    nb = T // BLOCK if T % BLOCK == 0 else 1
    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])

    def attend(args):
        qb, qib, wb, pb = args                      # a block of queries
        mask = pb[:, None] >= pos[None, :]
        score = index_scores(qib, ki, wb) if select or trace else None
        if select:
            mask = selected(score, mask, dims["topk"])
        # query head h reads key/value head h // (Hq // Hkv)
        qg = qb.reshape(-1, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("tgqk,sgk->gqts", qg, k) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("gqts,sgk->tgqk", p, v).reshape(-1, Hq, Dh)
        return out, ((mask, score) if trace else ())

    a, sel = jax.lax.map(attend, (blocks(q), blocks(qi), blocks(w),
                                  blocks(pos)))
    a = a.reshape(T, Hq, Dh)
    x = x + on * jnp.einsum("thk,hkd->td", a, q8(f32(lp["wo"]), (0, 1)))

    h2 = rms_norm(x, f32(lp["ln2"]), eps)
    wr = q8(f32(lp["wr"]), (0,))
    wg, wu = e8(f32(lp["wg"]), (1,)), e8(f32(lp["wu"]), (1,))
    wd = e8(f32(lp["wd"]), (1,))
    k_experts = dims["K"] - how["drop_experts"]

    def experts(hb):
        # a trace is the model's own routing, nothing mixed
        gates, idx, near = route(hb, wr, k_experts,
                                 0.0 if trace else how["tie_eps"])
        act = (jax.nn.silu(jnp.einsum("td,edf->tef", hb, wg))
               * jnp.einsum("td,edf->tef", hb, wu))
        return jnp.einsum("tef,efd,te->td", act, wd, gates), idx, near

    y, chosen, near = jax.lax.map(experts, blocks(h2))
    x = x + on * how["experts"] * y.reshape(T, -1)
    if trace:
        return x, (sel[0].reshape(T, T), chosen.reshape(T, -1),
                   sel[1].reshape(T, T), jax.nn.softmax(h2 @ wr, axis=-1))
    return x, near.reshape(T)


def forward_tail(params, dims, tokens, first, n_tail, layers_on, how):
    """-> (log-softmax over the vocabulary at positions first ..
    first+n_tail-1 of one sequence ``tokens`` [T] (causal, so padding after
    them is inert), near-tied [L,T] bool: the (position, layer) pairs whose
    experts were scored under both routings)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)

        def body(x, xs):
            lp, on = xs
            return layer(x, lp, dims, on, how)

        x, near = jax.lax.scan(body, x, (params["layers"], layers_on))
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        x = rms_norm(x, params["final_norm"].astype(jnp.float32), dims["eps"])
        head = params["lm_head"].astype(jnp.float32)
        if how["int8"]:
            head = fake_int8(head, (0,))
        return jax.nn.log_softmax(x @ head, axis=-1), near


def trace(state: dict, tokens, detail: bool = False):
    """For the tests and the builder's on-chip comparison: what the model
    selects and routes on one sequence ``tokens`` [T] (T a multiple of
    ``BLOCK``, or any T as one block), the model's own routing with nothing
    mixed at a near-tie -> (selected [L,T,T] bool, chosen [L,T,K] int32,
    log-softmax [T,V]); with ``detail`` the third is (index scores [L,T,T],
    router probabilities [L,T,E]) in its place (the float32 margins a
    disagreement is judged by; no [T,V] at 151,936)."""
    import jax
    import jax.numpy as jnp

    dims = state["dims"]

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)

            def body(x, lp):
                return layer(x, lp, dims, 1.0, HOW, trace=True)

            x, (sel, chosen, scores, probs) = jax.lax.scan(
                body, x, params["layers"])
            if detail:
                return sel, chosen, (scores, probs)
            x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                         dims["eps"])
            return sel, chosen, jax.nn.log_softmax(
                x @ params["lm_head"].astype(jnp.float32), axis=-1)

    return jax.jit(run)(state["params"], jnp.asarray(tokens))


def build(config: dict, seed: int) -> dict:
    """The weights as the server's seeded random init makes them (bfloat16,
    upcast a layer at a time where they are used), and the dimensions.
    ``config`` is the published ``config.json`` (the configuration file
    without its ``benchmark`` group)."""
    import jax

    cfg = program.LlamaConfig.from_hf_config(config)
    params = jax.block_until_ready(
        program.init_params(cfg, jax.random.PRNGKey(int(seed))))
    return {"params": params, "dims": hf_dims(config)}


def tail_logprobs(state: dict, tokens, first: int, n_tail: int,
                  variant: str = "full"):
    """-> [n_tail, V] float32 log-softmax at positions first .. of the one
    padded sequence ``tokens`` [T]. One whole-sequence program per
    (n_tail, variant's changes), compiled on first use and kept in the
    state. Says on standard error how many (position, layer) pairs were
    near-tied and scored under both routings (the module's text)."""
    import sys

    import jax
    import jax.numpy as jnp
    import numpy as np

    if variant not in VARIANTS:
        raise ValueError(f"no variant {variant!r} ({', '.join(VARIANTS)})")
    dims = state["dims"]
    how = {**HOW, **HOW_OF[variant]}
    key = (n_tail, *sorted(how.items()))
    fn = state.setdefault("programs", {}).get(key)
    if fn is None:
        fn = state["programs"][key] = jax.jit(partial(
            forward_tail, dims=dims, n_tail=n_tail, how=how))
    on = np.ones(dims["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    logp, near = fn(state["params"], tokens=jnp.asarray(tokens), first=first,
                    layers_on=jnp.asarray(on))
    near = np.asarray(near)[:, : first + n_tail]
    print(f"keye_vl2 {variant}: {int(near.sum())} of {near.size} (position, "
          f"layer) pairs up to the last scored position, "
          f"{int(near[:, first:].sum())} of {near[:, first:].size} at the "
          f"scored positions, lie within {how['tie_eps']:g} router logits "
          f"of a tie and were scored under both routings",
          file=sys.stderr, flush=True)
    return logp
