"""Reference ``command_a_plus``: a float32 ``jax.numpy`` forward of the
language model of Command A+ (``model_type cohere2_moe``), written from its
published ``config.json``
(``https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json``)
and the Cohere2 family's public modelling code. No kernel, no cache, no
batching, ``jax.default_matmul_precision("highest")``. The contract of a
reference file (``build``, ``tail_logprobs``) is in ``harness/catalog.py``.

Layer ``l`` on a token's residual stream x (D wide), kind ``layer_types[l]``
(``sliding_attention`` W, ``full_attention`` F); ``LN(x; w) = (x - mean(x))
rsqrt(var(x) + layer_norm_eps) w``, no bias:

1. ``h = LN(x; w_ln)``: the layer's ONE norm.
2. ``q = h Wq`` [Hq x Dh]; ``k = h Wk`` [Hkv x Dh]; ``v = h Wv`` [Hkv x Dh];
   no bias, no q / k norm.
3. W layer: INTERLEAVED rotary (``position_embedding_type rope_gptj``) over
   all Dh dims of every q and k head: dims (2i, 2i + 1) are a pair, angle
   ``p x rope_theta^(-2i / Dh)``. F layer: none, q and k as projected.
4. ``s_ij = q_i . k_j / sqrt(Dh)``; visible keys ``j <= i`` (F) or ``i -
   sliding_window < j <= i`` (W); ``p = softmax_j(s)``; ``a = (sum_j p_ij
   v_j) Wo`` (query head h reads K/V head h // (Hq // Hkv)).
5. ``s = sigmoid(h Wr)`` over the R routed experts; the K with the largest
   ``s_e`` are chosen among all; ``g_e = s_e / sum_chosen s``
   (``norm_topk_prob``); ``r = sum_{e chosen AND held} g_e SwiGLU_e(h)``.
6. ``m = (1 / n) sum_{j < n} SwiGLU_sh_j(h)``: the ``num_shared_experts``
   shared experts, AVERAGED (``shared_expert_combination_strategy``).
7. ``x <- x + a + r + m``: a parallel block, one residual add.
8. After the last layer: ``LN(x; w_final)``, the tied head (the embedding),
   x ``logit_scale``, float32 log-softmax.

A chip's share (``expert_shard``): the router is R = ``router_experts`` wide
and chooses among all R; the weights hold experts ``first_expert ..
first_expert + num_experts - 1``; the gates are normalised over all K
chosen, held or not; what the absent experts would add is left out, here as
in the program; the shared experts are whole on every chip.

Departures, each because the config does not say (the configuration file's
``assumed``): the shared experts' ``average`` is the MEAN of their outputs;
``sliding_window`` counts the query's own key; ``intermediate_size`` is the
width of one expert, routed or shared; the vision tower is left out.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls. The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]
    stacks.full / stacks.window (a layer at its index among its kind):
      ln1 [n,D]; wq [n,D,Hq,Dh]; wk, wv [n,D,Hkv,Dh]; wo [n,Hq,Dh,D]
    stacks.routed (layer l at l): wr [n,D,R]; wg, wu [n,E,D,F]; wd
      [n,E,F,D]; ws_g, ws_u [n,D,S x F]; ws_d [n,S x F,D]: the S shared
      experts side by side, expert j the columns / rows j F .. (j + 1) F

The weights stay in bfloat16 as the program made them and are upcast where
they are used: attention a block of ``BLOCK`` queries at a time (q is
projected, the scores taken and the out-projection made inside the block: a
[T, Hq, Dh] float32 array is 1.6 GB at 24,832 positions), the experts ONE
EXPERT at a time over all tokens (sixteen held experts upcast together are
3.2 GB), each shared expert on its own and the four added up and divided by
four, as written above, so that 24,832 positions fit beside 9.5 GB of
weights.

Near-tied routing is scored under both routings, as ``mimo_v2_flash`` does
and for its reason: where the K-th and the (K+1)-th score of a (position,
layer) lie within ``TIE_EPS`` the routed sum is computed under both chosen
sets and mixed, half and half at an exact tie.

Variants: ``full``; the probe's two (``dropped_layer``, ``int8``); and this
model's own broken controls, each ONE departure from the text above
(tests/test_command_a_plus.py scores the served path against each):
``sequential_block`` (the feed-forward reads LN(x + a)), ``second_norm``
(it reads LN(h)), ``rms_norm``, ``rope_full_too``, ``no_rope``,
``rotate_half``, ``shared_summed``, ``softmax_routing``, ``gates_raw``,
``window_minus`` / ``window_plus`` (a key fewer / more).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "sequential_block",
            "second_norm", "rms_norm", "rope_full_too", "no_rope",
            "rotate_half", "shared_summed", "softmax_routing", "gates_raw",
            "window_minus", "window_plus")
BLOCK = 32
TIE_EPS = 2.0 ** -9        # selection score; mimo_v2_flash.py says why


def hf_dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    shard = hf.get("expert_shard") or {}
    rp = hf.get("rope_parameters") or {}
    return {
        "L": L, "D": hf["hidden_size"], "Hq": hf["num_attention_heads"],
        "Hkv": hf["num_key_value_heads"], "Dh": hf["head_dim"],
        "V": hf["vocab_size"], "F": hf["intermediate_size"],
        "E": hf["num_experts"],
        "R": shard.get("router_experts", hf["num_experts"]),
        "first": shard.get("first_expert", 0),
        "K": hf["num_experts_per_tok"], "S": hf["num_shared_experts"],
        "W": hf["sliding_window"],
        "theta": float(hf.get("rope_theta", rp.get("rope_theta"))),
        "eps": float(hf["layer_norm_eps"]),
        "logit_scale": float(hf.get("logit_scale", 1)),
        "window": tuple(t == "sliding_attention"
                        for t in hf["layer_types"][:L]),
    }


def layer_norm(x, w, eps):
    import jax.numpy as jnp

    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jnp.reciprocal(jnp.sqrt(
        jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)) * w


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rotary(x, positions, theta, interleaved=True):
    """x [T,H,d]: rotary over all d dims, dims (2i, 2i + 1) a pair as
    published (``interleaved``), or (i, i + d / 2), the broken control."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fake_int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


# what a variant changes of the layer; ``full`` is HOW
HOW = {"int8": False, "ffn_reads": "h", "norm": "layer", "rope": "window",
       "interleaved": True, "shared": "mean", "law": "sigmoid",
       "renorm": True, "window_by": 0, "tie_eps": TIE_EPS}
HOW_OF = {
    "full": {}, "dropped_layer": {}, "int8": {"int8": True},
    "sequential_block": {"ffn_reads": "x+a"},
    "second_norm": {"ffn_reads": "LN(h)"},
    "rms_norm": {"norm": "rms"}, "rope_full_too": {"rope": "all"},
    "no_rope": {"rope": "none"}, "rotate_half": {"interleaved": False},
    "shared_summed": {"shared": "sum"},
    "softmax_routing": {"law": "softmax"}, "gates_raw": {"renorm": False},
    "window_minus": {"window_by": -1}, "window_plus": {"window_by": 1},
}


def route(h, wr, k, law, tie_eps, renorm=True):
    """-> (gates over all R experts [t,R], chosen ids [t,k], near [t] bool:
    the k-th and (k+1)-th score within ``tie_eps``). The gates of a
    near-tied token mix the two routings (the module's text)."""
    import jax
    import jax.numpy as jnp

    z = h @ wr
    score = (jax.nn.softmax(z, axis=-1) if law == "softmax"
             else jax.nn.sigmoid(z))
    _, idx = jax.lax.top_k(score, k + 1)
    rows = jnp.arange(h.shape[0])[:, None]

    def gates_of(i):
        v = jnp.take_along_axis(score, i, axis=-1)
        if renorm:
            v = v / jnp.sum(v, axis=-1, keepdims=True)
        return jnp.zeros_like(score).at[rows, i].set(v)

    own = gates_of(idx[:, :k])
    if not tie_eps:
        return own, idx[:, :k], jnp.zeros(h.shape[0], bool)
    other = gates_of(jnp.concatenate([idx[:, :k - 1], idx[:, k:]], -1))
    pk = jnp.take_along_axis(score, idx[:, k - 1:], axis=-1)
    margin = pk[:, 0] - pk[:, 1]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    return w * own + (1.0 - w) * other, idx[:, :k], near


def swiglu(h, wg, wu, wd):
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def layer(x, at, ff, window, dims, on, how, trace=False):
    """One parallel block on x [T,D] float32 (T a multiple of ``BLOCK``, or
    any T as one block). ``at`` / ``ff``: this layer's slices of its
    attention and routed stacks (bfloat16, upcast here); ``window``: its
    kind. -> (x, near-tied tokens [T] bool); with ``trace`` the second is
    the chosen experts [T,K]."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    T = x.shape[0]
    Hq, Hkv, Dh, F = dims["Hq"], dims["Hkv"], dims["Dh"], dims["F"]
    norm = partial(layer_norm if how["norm"] == "layer" else rms_norm,
                   w=f32(at["ln1"]), eps=dims["eps"])
    rotate = how["rope"] == "all" or (how["rope"] == "window" and window)
    W = dims["W"] + how["window_by"]
    pos = jnp.arange(T)
    h = norm(x)
    wq, wo = q8(f32(at["wq"]), (0,)), q8(f32(at["wo"]), (0, 1))
    k = jnp.einsum("td,dhk->thk", h, q8(f32(at["wk"]), (0,)))
    v = jnp.einsum("td,dhk->thk", h, q8(f32(at["wv"]), (0,)))
    if rotate:
        k = rotary(k, pos, dims["theta"], how["interleaved"])

    nb = T // BLOCK if T % BLOCK == 0 else 1
    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])

    def attend(args):
        hb, pb = args                               # a block of queries
        qb = jnp.einsum("td,dhk->thk", hb, wq)
        if rotate:
            qb = rotary(qb, pb, dims["theta"], how["interleaved"])
        mask = pb[:, None] >= pos[None, :]
        if window:
            mask = mask & (pos[None, :] > pb[:, None] - W)
        qg = qb.reshape(-1, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("tgqk,sgk->gqts", qg, k) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gqts,sgk->tgqk", p, v).reshape(-1, Hq, Dh)
        return jnp.einsum("thk,hkd->td", o, wo)

    a = jax.lax.map(attend, (blocks(h), blocks(pos))).reshape(T, -1)

    # what the feed-forward reads: the SAME normed stream (the model), or
    # one of the two sequential readings (the broken controls)
    hf = {"h": h, "x+a": norm(x + a), "LN(h)": norm(h)}[how["ffn_reads"]]
    gates, idx, near = route(hf, q8(f32(ff["wr"]), (0,)), dims["K"],
                             how["law"], 0.0 if trace else how["tie_eps"],
                             how["renorm"])
    held = gates[:, dims["first"]:dims["first"] + dims["E"]]

    def expert(r, e):
        # ONE held expert on every token, gated (zero where not chosen)
        w3 = [q8(f32(ff[n][e]), (0,)) for n in ("wg", "wu", "wd")]
        return r + held[:, e, None] * swiglu(hf, *w3), None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(dims["E"]))
    # the shared experts: four of them, each on its own, summed and divided
    # by four (``shared_expert_combination_strategy average``)
    m = jnp.zeros_like(x)
    for j in range(dims["S"]):
        sl = slice(j * F, (j + 1) * F)
        m = m + swiglu(hf, q8(f32(ff["ws_g"][:, sl]), (0,)),
                       q8(f32(ff["ws_u"][:, sl]), (0,)),
                       q8(f32(ff["ws_d"][sl]), (0,)))
    if how["shared"] == "mean":
        m = m / dims["S"]
    x = x + on * (a + r + m)
    return x, (idx if trace else near)


def _layers(params, dims):
    """-> per layer (attention stack, index in it, index in the routed
    stack, window?): a layer lies at its index among its kind."""
    st = params["stacks"]
    seen = {"full": 0, "window": 0}
    out = []
    for l, window in enumerate(dims["window"]):
        a = "window" if window else "full"
        out.append((st[a], seen[a], l, bool(window)))
        seen[a] += 1
    return out


def _at(stack, i):
    return {n: w[i] for n, w in stack.items()}


def _layer_step(x, at, ia, ff, jf, on, *, window, dims, how):
    import jax

    # the layer's slices are taken INSIDE the program (a traced index: one
    # program a kind of layer), so no copy of them is made beside the stack
    with jax.default_matmul_precision("highest"):
        return layer(x, _at(at, ia), _at(ff, jf), window, dims, on, how)


def _head(x, params, dims, how):
    import jax
    import jax.numpy as jnp

    norm = layer_norm if how["norm"] == "layer" else rms_norm
    x = norm(x, params["final_norm"].astype(jnp.float32), dims["eps"])
    head = params["embed"].astype(jnp.float32).T      # tied
    if how["int8"]:
        head = fake_int8(head, (0,))
    return jax.nn.log_softmax((x @ head) * dims["logit_scale"], axis=-1)


def _head_step(x, params, first, *, n_tail, dims, how):
    import jax

    with jax.default_matmul_precision("highest"):
        return _head(jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0),
                     params, dims, how)


def forward_tail(programs, params, dims, tokens, first, n_tail, layers_on,
                 how):
    """-> (log-softmax over the vocabulary at positions first ..
    first+n_tail-1 of one sequence ``tokens`` [T] (causal, so padding after
    them is inert), near-tied [L,T] bool). One program a kind of layer and
    one for the head, run a layer at a time from here (``programs`` keeps
    them)."""
    import jax
    import jax.numpy as jnp

    def program_of(name, fn, **static):
        key = (name, *sorted(static.items()), *sorted(how.items()))
        if key not in programs:
            programs[key] = jax.jit(partial(fn, dims=dims, how=how,
                                            **static))
        return programs[key]

    x = params["embed"][tokens].astype(jnp.float32)
    routed = params["stacks"]["routed"]
    nears = []
    for l, (at, ia, jf, window) in enumerate(_layers(params, dims)):
        x, near = program_of("layer", _layer_step, window=window)(
            x, at, ia, routed, jf, layers_on[l])
        nears.append(near)
    head = {"final_norm": params["final_norm"], "embed": params["embed"]}
    logp = program_of("head", _head_step, n_tail=n_tail)(x, head, first)
    return logp, jnp.stack(nears)


def trace(state: dict, tokens, variant: str = "full"):
    """For the tests: the model's own routing with nothing mixed at a
    near-tie, on one sequence ``tokens`` [T] -> (chosen experts of the
    layers [L,T,K] int32, log-softmax [T,V])."""
    import jax
    import jax.numpy as jnp

    dims = state["dims"]
    how = {**HOW, **HOW_OF[variant]}

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            chosen = []
            layers = _layers(params, dims)
            for l, (at, ia, jf, window) in enumerate(layers):
                on = 0.0 if (variant == "dropped_layer"
                             and l == len(layers) - 1) else 1.0
                x, ch = layer(x, _at(at, ia),
                              _at(params["stacks"]["routed"], jf), window,
                              dims, on, how, trace=True)
                chosen.append(ch)
            return jnp.stack(chosen), _head(x, params, dims, how)

    return jax.jit(run)(state["params"], jnp.asarray(tokens))


def build(config: dict, seed: int) -> dict:
    """The weights as the server's seeded random init makes them (bfloat16,
    upcast where they are used), and the dimensions. ``config`` is the
    configuration file without its ``benchmark`` group."""
    import jax

    cfg = program.LlamaConfig.from_hf_config(config)
    params = jax.block_until_ready(
        program.init_params(cfg, jax.random.PRNGKey(int(seed))))
    return {"params": params, "dims": hf_dims(config)}


def tail_logprobs(state: dict, tokens, first: int, n_tail: int,
                  variant: str = "full"):
    """-> [n_tail, V] float32 log-softmax at positions first .. of the one
    padded sequence ``tokens`` [T]. The programs (:func:`forward_tail`) are
    compiled on first use and kept in the state. Says on standard error how
    many (position, layer) pairs were near-tied and scored under both
    routings (the module's text)."""
    import sys

    import jax.numpy as jnp
    import numpy as np

    if variant not in VARIANTS:
        raise ValueError(f"no variant {variant!r} ({', '.join(VARIANTS)})")
    dims = state["dims"]
    how = {**HOW, **HOW_OF[variant]}
    on = np.ones(dims["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    logp, near = forward_tail(state.setdefault("programs", {}),
                              state["params"], dims, jnp.asarray(tokens),
                              first, n_tail, on, how)
    near = np.asarray(near)[:, : first + n_tail]
    print(f"command_a_plus {variant}: {int(near.sum())} of {near.size} "
          f"(position, layer) pairs up to the last scored position, "
          f"{int(near[:, first:].sum())} of {near[:, first:].size} at the "
          f"scored positions, lie within {how['tie_eps']:g} of a tie in "
          f"score and were scored under both routings",
          file=sys.stderr, flush=True)
    return logp
