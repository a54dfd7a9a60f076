"""Reference ``mimo_v2_flash``: a float32 ``jax.numpy`` forward of the
language model of MiMo-V2-Flash, written from its published ``config.json``
(``https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json``).
No kernel, no cache, no batching, ``jax.default_matmul_precision("highest")``.
The contract of a reference file (``build``, ``tail_logprobs``) is in
``harness/catalog.py``.

Layer ``l`` on a token's residual stream x (D wide), kind ``a =
hybrid_layer_pattern[l]`` (0 full, 1 window), all norms RMSNorm with
``layernorm_epsilon``, no bias, no q/k norm:

1. ``h = norm(x)``; ``q = h Wq`` [Hq x Dh]; ``k = h Wk`` [Hkv x Dh]; ``v =
   h Wv`` [Hkv x Dv]; Hkv = ``num_key_value_heads`` in a full layer,
   ``swa_num_key_value_heads`` in a window layer.
2. Rotate-half rotary over the first ``rot = 2 floor(partial_rotary_factor
   Dh / 2)`` dims of every q and k head, the rest pass; base ``rope_theta``
   in a full layer, ``swa_rope_theta`` in a window layer.
3. ``v <- attention_value_scale v``.
4. ``s_ij = q_i . k_j / sqrt(Dh)``; visible keys ``j <= i`` (full) or ``i -
   sliding_window < j <= i`` (window).
5. full: ``p = softmax_j(s)``. window (``add_swa_attention_sink_bias``):
   ``p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij))`` with one learned
   scalar ``b_h`` a query head (the sink takes weight and gives no value).
6. ``x += (sum_j p_ij v_j) Wo`` (query head h reads K/V head h // (Hq //
   Hkv)).
7. ``h2 = norm(x)``. ``moe_layer_freq[l] = 0``: SwiGLU of width
   ``intermediate_size``. Else ``s = sigmoid(h2 Wr)`` over the R routed
   experts; the K with the largest ``s_e + c_e`` are chosen (``c``: the
   ``noaux_tc`` selection bias; ``n_group = topk_group = 1``); ``g_e = s_e
   / sum_chosen s``; ``y = sum_{e chosen AND held} g_e SwiGLU_e(h2)``; ``x
   += y``.
8. After the last layer: norm, untied head, float32 log-softmax.

A chip's share (``expert_shard``): the router is R = ``router_experts``
wide and chooses among all R; the weights hold experts ``first_expert ..
first_expert + n_routed_experts - 1``; the gates are normalised over all K
chosen, held or not; what the absent experts would add is left out, here as
in the program, and that partial sum goes on to the next layer.

Departures, each because the config does not say (the configuration file's
``assumed``): ``attention_chunk_size`` (= the window) tiles the window
layers' computation and masks nothing; ``sliding_window`` counts the
query's own key; the three MTP layers of the model card are not in the
config and are left out.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls. The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]; lm_head [D,V]
    stacks.full / stacks.window (a layer at its index among its kind):
      ln1 [n,D]; wq [n,D,Hq,Dh]; wk [n,D,Hkv,Dh]; wv [n,D,Hkv,Dv]; wo
      [n,Hq,Dv,D]; sink [n,Hq] (a kind with a sink)
    stacks.dense: ln2 [n,D]; wg, wu [n,D,F]; wd [n,F,D]
    stacks.routed: ln2 [n,D]; wr [n,D,R]; rbias [n,R]; wg, wu [n,E,D,Fe];
      wd [n,E,Fe,D]

The weights stay in bfloat16 as the program made them and are upcast a
layer at a time; attention and experts are computed a block of ``BLOCK``
queries at a time (every HELD expert on every token of the block, gated),
so that a 14,400-token sequence fits beside 6.9 GB of weights.

Near-tied routing is scored under both routings, as ``keye_vl2`` does and
for its reason (top-K routing is discontinuous; the served path's normed
input is bfloat16): where the K-th and the (K+1)-th selection score ``s +
c`` of a (position, layer) lie within ``TIE_EPS`` the expert output is
computed under both chosen sets and mixed, half and half at an exact tie,
the model's own routing alone from ``TIE_EPS`` on. ``TIE_EPS`` = 2 ** -9 in
score: a sigmoid's slope is at most a quarter, so this is 2 ** -7 in router
logit, two units in the last place of a bfloat16 number of size 1.

Variants: ``full``; the probe's two (``dropped_layer``, ``int8``); and
this model's own broken controls, each one departure from the text above
(tests/test_mimo_v2_flash.py scores the served path against each):
``no_sink``, ``sink_full_too``, ``window_off``, ``one_rope_base``,
``rope_all_dims``, ``v_unscaled``, ``softmax_routing``, ``no_select_bias``,
``bias_as_weight``, ``top7`` (one expert fewer), ``experts_int8``.
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "no_sink", "sink_full_too",
            "window_off", "one_rope_base", "rope_all_dims", "v_unscaled",
            "softmax_routing", "no_select_bias", "bias_as_weight", "top7",
            "experts_int8")
BLOCK = 128
TIE_EPS = 2.0 ** -9        # selection score; see "Near-tied routing" above


def hf_dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    Dh = hf["head_dim"]
    rot = int(hf.get("partial_rotary_factor", 1.0) * Dh)
    shard = hf.get("expert_shard") or {}
    return {
        "L": L, "D": hf["hidden_size"], "Hq": hf["num_attention_heads"],
        "Hkv": (hf["num_key_value_heads"], hf["swa_num_key_value_heads"]),
        "Dh": Dh, "Dv": hf.get("v_head_dim", Dh), "V": hf["vocab_size"],
        "E": hf["n_routed_experts"],
        "R": shard.get("router_experts", hf["n_routed_experts"]),
        "first": shard.get("first_expert", 0),
        "K": hf["num_experts_per_tok"], "W": hf["sliding_window"],
        "rot": rot - rot % 2,
        "theta": (float(hf["rope_theta"]), float(hf["swa_rope_theta"])),
        "eps": float(hf["layernorm_epsilon"]),
        "vscale": float(hf.get("attention_value_scale") or 1.0),
        "kinds": tuple(hf["hybrid_layer_pattern"][:L]),
        "routed": tuple(hf["moe_layer_freq"][:L]),
        "sink": (bool(hf.get("add_full_attention_sink_bias")),
                 bool(hf.get("add_swa_attention_sink_bias"))),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rotary(x, positions, theta, rot):
    """x [T,H,d]: rotate-half over the first ``rot`` dims (their first and
    second halves are the pairs), the other d - rot pass through."""
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def fake_int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


# what a variant changes of the layer; ``full`` is HOW
HOW = {"int8": False, "experts_int8": False, "sink": "config",
       "window": True, "one_base": False, "rot_all": False, "vscale": True,
       "law": "sigmoid_bias", "drop_experts": 0, "tie_eps": TIE_EPS}
HOW_OF = {
    "full": {}, "dropped_layer": {}, "int8": {"int8": True},
    "no_sink": {"sink": "none"}, "sink_full_too": {"sink": "both"},
    "window_off": {"window": False}, "one_rope_base": {"one_base": True},
    "rope_all_dims": {"rot_all": True}, "v_unscaled": {"vscale": False},
    "softmax_routing": {"law": "softmax"},
    "no_select_bias": {"law": "sigmoid"},
    "bias_as_weight": {"law": "sigmoid_bias_weighs"},
    "top7": {"drop_experts": 1}, "experts_int8": {"experts_int8": True},
}


def route(h2, wr, c, k, law, tie_eps):
    """-> (gates over all R experts [t,R], chosen ids [t,k], near [t] bool:
    the k-th and (k+1)-th selection score within ``tie_eps``). The gates of
    a near-tied token mix the two routings (the module's text)."""
    import jax
    import jax.numpy as jnp

    z = h2 @ wr
    if law == "softmax":
        weigh = pick = jax.nn.softmax(z, axis=-1)
    else:
        weigh = jax.nn.sigmoid(z)
        pick = weigh if law == "sigmoid" else weigh + c
        if law == "sigmoid_bias_weighs":
            weigh = pick
    _, idx = jax.lax.top_k(pick, k + 1)
    rows = jnp.arange(h2.shape[0])[:, None]

    def gates_of(i):
        v = jnp.take_along_axis(weigh, i, axis=-1)
        return jnp.zeros_like(weigh).at[rows, i].set(
            v / jnp.sum(v, axis=-1, keepdims=True))

    own = gates_of(idx[:, :k])
    if not tie_eps:
        return own, idx[:, :k], jnp.zeros(h2.shape[0], bool)
    other = gates_of(jnp.concatenate([idx[:, :k - 1], idx[:, k:]], -1))
    pk = jnp.take_along_axis(pick, idx[:, k - 1:], axis=-1)
    margin = pk[:, 0] - pk[:, 1]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    return w * own + (1.0 - w) * other, idx[:, :k], near


def layer(x, at, ff, window, dims, on, how, trace=False):
    """One block on x [T,D] float32 (T a multiple of ``BLOCK``, or any T as
    one block). ``at`` / ``ff``: this layer's slices of its attention and
    feed-forward stacks, upcast here; ``window``: its kind. -> (x, near-tied
    tokens [T] bool); with ``trace`` the second is the chosen experts [T,K]
    (None for a dense layer)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    e8 = ((lambda w, ax: fake_int8(w, ax))
          if how["int8"] or how["experts_int8"] else (lambda w, ax: w))
    T = x.shape[0]
    Hq, Dh, Dv = dims["Hq"], dims["Dh"], dims["Dv"]
    Hkv = dims["Hkv"][window]
    eps = dims["eps"]
    theta = dims["theta"][0 if how["one_base"] else window]
    rot = Dh if how["rot_all"] else dims["rot"]
    pos = jnp.arange(T)
    h = rms_norm(x, f32(at["ln1"]), eps)
    q = jnp.einsum("td,dhk->thk", h, q8(f32(at["wq"]), (0,)))
    k = jnp.einsum("td,dhk->thk", h, q8(f32(at["wk"]), (0,)))
    v = jnp.einsum("td,dhk->thk", h, q8(f32(at["wv"]), (0,)))
    q, k = rotary(q, pos, theta, rot), rotary(k, pos, theta, rot)
    if how["vscale"]:
        v = v * dims["vscale"]
    sink = None
    if how["sink"] != "none" and "sink" in at:
        sink = f32(at["sink"])
    if how["sink"] == "both" and sink is None:
        sink = jnp.full((Hq,), 4.0, jnp.float32)   # the window layers' mean

    nb = T // BLOCK if T % BLOCK == 0 else 1
    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])

    def attend(args):
        qb, pb = args                               # a block of queries
        mask = pb[:, None] >= pos[None, :]
        if window and how["window"]:
            mask = mask & (pos[None, :] > pb[:, None] - dims["W"])
        qg = qb.reshape(-1, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("tgqk,sgk->gqts", qg, k) / math.sqrt(Dh)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        if sink is not None:
            col = jnp.broadcast_to(
                sink.reshape(Hkv, Hq // Hkv, 1, 1), (*s.shape[:-1], 1))
            p = jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)
            p = p[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqts,sgk->tgqk", p, v).reshape(-1, Hq, Dv)

    a = jax.lax.map(attend, (blocks(q), blocks(pos))).reshape(T, Hq, Dv)
    x = x + on * jnp.einsum("thk,hkd->td", a, q8(f32(at["wo"]), (0, 1)))

    h2 = rms_norm(x, f32(ff["ln2"]), eps)
    if "wr" not in ff:
        wg, wu = q8(f32(ff["wg"]), (0,)), q8(f32(ff["wu"]), (0,))
        y = (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ q8(f32(ff["wd"]), (0,))
        return x + on * y, (None if trace else jnp.zeros(T, bool))
    wr = q8(f32(ff["wr"]), (0,))
    c = f32(ff["rbias"])
    wg, wu = e8(f32(ff["wg"]), (1,)), e8(f32(ff["wu"]), (1,))
    wd = e8(f32(ff["wd"]), (1,))
    k_experts = dims["K"] - how["drop_experts"]
    first, E = dims["first"], dims["E"]

    def experts(hb):
        gates, idx, near = route(hb, wr, c, k_experts, how["law"],
                                 0.0 if trace else how["tie_eps"])
        held = gates[:, first:first + E]            # the absent: left out
        act = (jax.nn.silu(jnp.einsum("td,edf->tef", hb, wg))
               * jnp.einsum("td,edf->tef", hb, wu))
        return jnp.einsum("tef,efd,te->td", act, wd, held), idx, near

    y, chosen, near = jax.lax.map(experts, blocks(h2))
    x = x + on * y.reshape(T, -1)
    return x, (chosen.reshape(T, -1) if trace else near.reshape(T))


def _layers(params, dims):
    """-> per layer (attention stack, index in it, feed-forward stack, index
    in it, window?): a layer lies at its index among its kind."""
    st = params["stacks"]
    seen = {"full": 0, "window": 0, "dense": 0, "routed": 0}
    out = []
    for kind, routed in zip(dims["kinds"], dims["routed"]):
        a, f = ("window" if kind else "full"), ("routed" if routed
                                                else "dense")
        out.append((st[a], seen[a], st[f], seen[f], int(kind)))
        seen[a] += 1
        seen[f] += 1
    return out


def _at(stack, i):
    return {n: w[i] for n, w in stack.items()}


def _layer_step(x, at, ia, ff, jf, on, *, window, dims, how):
    import jax

    # the layer's slices are taken INSIDE the program (a traced index: one
    # program a kind of layer), so no copy of them is made beside the stack
    with jax.default_matmul_precision("highest"):
        return layer(x, _at(at, ia), _at(ff, jf), window, dims, on, how)


def _head_step(x, norm, head, first, *, n_tail, dims, how):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        x = rms_norm(x, norm.astype(jnp.float32), dims["eps"])
        head = head.astype(jnp.float32)
        if how["int8"]:
            head = fake_int8(head, (0,))
        return jax.nn.log_softmax(x @ head, axis=-1)


def forward_tail(programs, params, dims, tokens, first, n_tail, layers_on,
                 how):
    """-> (log-softmax over the vocabulary at positions first ..
    first+n_tail-1 of one sequence ``tokens`` [T] (causal, so padding after
    them is inert), near-tied [L,T] bool). One program a kind of layer and
    one for the head, run a layer at a time from here (``programs`` keeps
    them): inside ONE program XLA hoists every layer's upcast out of the
    block loops and holds them all at once, 13.7 GB at this size."""
    import jax
    import jax.numpy as jnp

    def program_of(name, fn, **static):
        key = (name, *sorted(static.items()), *sorted(how.items()))
        if key not in programs:
            programs[key] = jax.jit(partial(fn, dims=dims, how=how,
                                            **static))
        return programs[key]

    x = params["embed"][tokens].astype(jnp.float32)
    nears = []
    for l, (at, ia, ff, jf, window) in enumerate(_layers(params, dims)):
        x, near = program_of("layer", _layer_step, window=window)(
            x, at, ia, ff, jf, layers_on[l])
        nears.append(near)
    logp = program_of("head", _head_step, n_tail=n_tail)(
        x, params["final_norm"], params["lm_head"], first)
    return logp, jnp.stack(nears)


def trace(state: dict, tokens, variant: str = "full"):
    """For the tests: the model's own routing with nothing mixed at a
    near-tie, on one sequence ``tokens`` [T] -> (chosen experts of the
    routed layers [Lr,T,K] int32, log-softmax [T,V])."""
    import jax
    import jax.numpy as jnp

    dims = state["dims"]
    how = {**HOW, **HOW_OF[variant]}

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            chosen = []
            layers = _layers(params, dims)
            for l, (at, ia, ff, jf, window) in enumerate(layers):
                on = 0.0 if (variant == "dropped_layer"
                             and l == len(layers) - 1) else 1.0
                x, ch = layer(x, _at(at, ia), _at(ff, jf), window, dims, on,
                              how, trace=True)
                if ch is not None:
                    chosen.append(ch)
            x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                         dims["eps"])
            head = params["lm_head"].astype(jnp.float32)
            if how["int8"]:
                head = fake_int8(head, (0,))
            return jnp.stack(chosen), jax.nn.log_softmax(x @ head, axis=-1)

    return jax.jit(run)(state["params"], jnp.asarray(tokens))


def build(config: dict, seed: int) -> dict:
    """The weights as the server's seeded random init makes them (bfloat16,
    upcast a layer at a time where they are used), and the dimensions.
    ``config`` is the configuration file without its ``benchmark`` group."""
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

    import jax
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
    print(f"mimo_v2_flash {variant}: {int(near.sum())} of {near.size} "
          f"(position, layer) pairs up to the last scored position, "
          f"{int(near[:, first:].sum())} of {near[:, first:].size} at the "
          f"scored positions, lie within {how['tie_eps']:g} of a tie in "
          f"selection score and were scored under both routings",
          file=sys.stderr, flush=True)
    return logp
