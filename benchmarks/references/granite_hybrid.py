"""Reference ``granite_hybrid``: a float32 ``jax.numpy`` forward of
Granite-4.0-H (``model_type: granitemoehybrid`` without routed experts),
written from the published ``config.json``
(``https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json``).
No kernel, no cache, no batching, no chunked form: the recurrence runs token
by token. ``jax.default_matmul_precision("highest")``. The contract of a
reference file (``build``, ``tail_logprobs``) is in ``harness/catalog.py``.

With x the residual stream (embedding row x ``embedding_multiplier``), every
norm an RMSNorm with ``rms_norm_eps``, no bias anywhere but the convolution's,
r = ``residual_multiplier``:

1. every layer: ``x <- x + r Mix(norm(x))``, then ``x <- x + r W_down(silu(g)
   * u)`` with ``[g, u] = W_up norm(x)``, ``shared_intermediate_size`` each.
2. ``Mix`` of a ``mamba`` layer, for the normed input v_t of token t: ``[z_t,
   c_t, d_t] = W_in v_t`` of widths I | I + 2 N | H (I = ``mamba_n_heads`` x
   ``mamba_d_head``, N = ``mamba_d_state``, H = ``mamba_n_heads``). ``c'_t =
   silu(b + sum_{k<K} w[k] * c_{t-K+1+k})`` (K = ``mamba_d_conv``; zeros before
   the sequence). ``c'_t = [X_t (H x P) | B_t (N) | C_t (N)]``
   (``mamba_n_groups`` 1: every head shares B and C). Per head h: ``dt =
   softplus(d_t[h] + dt_bias[h])``; ``a = exp(-dt exp(A_log[h]))``; ``S_t[h] =
   a S_{t-1}[h] + dt X_t[h] (x) B_t`` (P x N, ``S_0 = 0``); ``y_t[h] = S_t[h]
   C_t + D[h] X_t[h]``. ``Mix = W_out (w * rmsnorm(y_t * silu(z_t)))`` over
   all I channels.
3. ``Mix`` of an ``attention`` layer: q (``num_attention_heads``), k, v
   (``num_key_value_heads``) of width hidden / heads, no bias, NO rotary
   (``position_embedding_type`` "nope"), causal, scores x
   ``attention_multiplier`` (not 1 / sqrt(width)), softmax, output projection.
4. logits: ``norm(x) E^T / logits_scaling`` with the embedding table E
   (``tie_word_embeddings``); float32 log-softmax.

Departures from the published file: none in the mathematics. The weights are
seeded (the configuration file's ``assumed`` says how ``A_log``, ``dt_bias``,
``D`` and the convolution are drawn); ``time_step_limit`` is (0, inf): dt is
not clamped; ``mamba_chunk_size`` tiles a computation and appears nowhere.

From the program it takes the weights as DATA and nothing else
(``llama.init_params(cfg, PRNGKey(seed))``: what the server's random init
calls). The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]
    stacks.full (the attention layers, each at its index among them):
      ln1 [n,D]; wq [n,D,Hq,Dh]; wk, wv [n,D,Hkv,Dh]; wo [n,Hq,Dh,D]
    stacks.mamba (the mamba layers likewise): ln1 [n,D]; W_in as two
      matrices, w_in [n,D,I+I+2N] (columns z | X B C) and w_dt [n,D,H] (its
      last H columns, dt); conv_w [n,K,I+2N]; conv_b [n,I+2N]; A_log, D,
      dt_bias [n,H]; norm [n,I]; w_out [n,I,D]
    stacks.dense (every layer): ln2 [n,D]; wg, wu [n,D,F]; wd [n,F,D]

The weights stay in bfloat16 as the program made them and are upcast a layer
at a time (one program a kind of layer, run from Python: 3.2 G parameters in
float32 do not fit beside themselves); attention is computed a block of
``BLOCK`` queries at a time.

Variants: ``full``; the probe's two (``dropped_layer``: the last layer's two
branches switched off; ``int8``: every weight matrix rounded to 127 levels
per output channel); and this model's own controls, each ONE departure from
the text above: ``carry_dropped`` (state S and the convolution's memory zeroed
at every position that is a multiple of 256: what a prefill that lost the
carry between chunks computes), ``rotary_on`` (rotate-half rotary at
``rope_theta`` on q and k), ``scale_sqrt`` (scores x 1 / sqrt(width)), and
``state_bf16`` (S rounded to bfloat16 after every token: what a cache that
kept the state in the model's dtype would hold; NOT a fault of the program,
a measurement for whoever wants to halve the state).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "carry_dropped", "rotary_on",
            "scale_sqrt", "state_bf16")
BLOCK = 128
CARRY_EVERY = 256


def hf_dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    return {
        "L": L, "D": hf["hidden_size"], "Hq": hf["num_attention_heads"],
        "Hkv": hf["num_key_value_heads"],
        "Dh": hf["hidden_size"] // hf["num_attention_heads"],
        "V": hf["vocab_size"], "H": H, "P": P, "N": N,
        "K": hf["mamba_d_conv"], "I": H * P,
        "eps": float(hf["rms_norm_eps"]),
        "scale": float(hf["attention_multiplier"]),
        "embed": float(hf["embedding_multiplier"]),
        "res": float(hf["residual_multiplier"]),
        "logits": float(hf["logits_scaling"]),
        "theta": float(hf.get("rope_theta", 10000.0)),
        "kinds": tuple(hf["layer_types"][:L]),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rotary(x, positions, theta):
    """x [T,H,d]: rotate-half over all d dims."""
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


# what a variant changes; ``full`` is HOW
HOW = {"int8": False, "carry": True, "rotary": False, "sqrt": False,
       "state_bf16": False}
HOW_OF = {"full": {}, "dropped_layer": {}, "int8": {"int8": True},
          "carry_dropped": {"carry": False}, "rotary_on": {"rotary": True},
          "scale_sqrt": {"sqrt": True}, "state_bf16": {"state_bf16": True}}


def mamba_mix(h, mp, dims, how):
    """``Mix`` of a mamba layer on the normed inputs h [T,D], token by
    token. ``mp``: the layer's tensors, float32."""
    import jax
    import jax.numpy as jnp

    H, P, N, K, I = dims["H"], dims["P"], dims["N"], dims["K"], dims["I"]
    zc, d = h @ mp["w_in"], h @ mp["w_dt"]
    z, c = zc[:, :I], zc[:, I:]
    A = -jnp.exp(mp["A_log"])
    bias = mp.get("conv_b", 0.0)

    def token(carry, inp):
        S, past = carry                     # [H,P,N], [K-1, I+2N]
        t, c_t, d_t = inp
        if not how["carry"]:
            lost = (t % CARRY_EVERY) == 0
            S = jnp.where(lost, 0.0, S)
            past = jnp.where(lost, 0.0, past)
        win = jnp.concatenate([past, c_t[None]], 0)             # [K, .]
        cc = jax.nn.silu(bias + jnp.sum(mp["conv_w"] * win, axis=0))
        X, B, C = cc[:I].reshape(H, P), cc[I:I + N], cc[I + N:]
        dt = jax.nn.softplus(d_t + mp["dt_bias"])               # [H]
        a = jnp.exp(dt * A)
        S = a[:, None, None] * S + (dt[:, None] * X)[..., None] * B
        if how["state_bf16"]:
            # (reduce_precision, not a cast there and back: XLA may elide
            # such a pair, and on the chip it did: the variant read the
            # full model's number to every digit)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        y = S @ C + mp["D"][:, None] * X                        # [H,P]
        return (S, win[1:]), y.reshape(I)

    T = h.shape[0]
    init = (jnp.zeros((H, P, N), jnp.float32),
            jnp.zeros((K - 1, I + 2 * N), jnp.float32))
    _, y = jax.lax.scan(token, init, (jnp.arange(T), c, d))
    g = rms_norm(y * jax.nn.silu(z), mp["norm"], dims["eps"])
    return g @ mp["w_out"]


def attention_mix(h, at, dims, how):
    """``Mix`` of an attention layer on the normed inputs h [T,D]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    Hq, Hkv, Dh = dims["Hq"], dims["Hkv"], dims["Dh"]
    pos = jnp.arange(T)
    q = jnp.einsum("td,dhk->thk", h, at["wq"])
    k = jnp.einsum("td,dhk->thk", h, at["wk"])
    v = jnp.einsum("td,dhk->thk", h, at["wv"])
    if how["rotary"]:
        q, k = rotary(q, pos, dims["theta"]), rotary(k, pos, dims["theta"])
    scale = 1.0 / math.sqrt(Dh) if how["sqrt"] else dims["scale"]
    nb = T // BLOCK if T % BLOCK == 0 else 1

    def attend(args):
        qb, pb = args                               # a block of queries
        qg = qb.reshape(-1, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("tgqk,sgk->gqts", qg, k) * scale
        s = jnp.where((pb[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqts,sgk->tgqk", p, v).reshape(-1, Hq, Dh)

    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])
    a = jax.lax.map(attend, (blocks(q), blocks(pos))).reshape(T, Hq, Dh)
    return jnp.einsum("thk,hkd->td", a, at["wo"])


# the matrices of a stack and the axes their inputs lie on (int8 rounds per
# OUTPUT channel); every other tensor of a stack is a vector or the
# convolution and stays as it is
MATRICES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1), "w_in": (0,),
            "w_dt": (0,), "w_out": (0,), "wg": (0,), "wu": (0,), "wd": (0,)}


def _tensors(stack, i, how):
    """Layer ``i``'s slice of a stack, upcast (and rounded under int8)."""
    import jax.numpy as jnp

    out = {}
    for name, w in stack.items():
        w = w[i].astype(jnp.float32)
        if how["int8"] and name in MATRICES:
            w = fake_int8(w, MATRICES[name])
        out[name] = w
    return out


def layer(x, mix, ia, ff, jf, on, *, kind, dims, how):
    """One block on x [T,D] float32. ``mix`` / ``ff``: the layer's mixer and
    feed-forward stacks, sliced at TRACED indices inside the program (one
    program a kind of layer, no copy of a layer beside its stack)."""
    import jax

    with jax.default_matmul_precision("highest"):
        mp, fp = _tensors(mix, ia, how), _tensors(ff, jf, how)
        h = rms_norm(x, mp["ln1"], dims["eps"])
        branch = (mamba_mix if kind == "mamba" else attention_mix)(
            h, mp, dims, how)
        x = x + on * dims["res"] * branch
        h2 = rms_norm(x, fp["ln2"], dims["eps"])
        y = (jax.nn.silu(h2 @ fp["wg"]) * (h2 @ fp["wu"])) @ fp["wd"]
        return x + on * dims["res"] * y


def head(x, norm, embed, first, *, n_tail, dims, how):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        x = rms_norm(x, norm.astype(jnp.float32), dims["eps"])
        E = embed.astype(jnp.float32)
        if how["int8"]:
            E = fake_int8(E, (1,))
        return jax.nn.log_softmax((x @ E.T) / dims["logits"], axis=-1)


def forward_tail(programs, params, dims, tokens, first, n_tail, layers_on,
                 how):
    """-> log-softmax over the vocabulary at positions first ..
    first+n_tail-1 of one sequence ``tokens`` [T] (causal, so padding after
    them is inert). One program a kind of layer and one for the head, run a
    layer at a time from here (``programs`` keeps them)."""
    import jax
    import jax.numpy as jnp

    def program_of(name, fn, **static):
        key = (name, *sorted(static.items()), *sorted(how.items()))
        if key not in programs:
            programs[key] = jax.jit(partial(fn, dims=dims, how=how,
                                            **static))
        return programs[key]

    st = params["stacks"]
    E = params["embed"]
    x = E[tokens].astype(jnp.float32)
    if how["int8"]:
        # the table is one matrix, embedding and head: rounded per row (a
        # row is a token's output channel)
        x = fake_int8(E.astype(jnp.float32), (1,))[tokens]
    x = x * dims["embed"]
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(dims["kinds"]):
        x = program_of("layer", layer, kind=kind)(
            x, st["mamba" if kind == "mamba" else "full"], seen[kind],
            st["dense"], l, layers_on[l])
        seen[kind] += 1
    return program_of("head", head, n_tail=n_tail)(
        x, params["final_norm"], E, first)


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
    compiled on first use and kept in the state."""
    import jax.numpy as jnp
    import numpy as np

    if variant not in VARIANTS:
        raise ValueError(f"no variant {variant!r} ({', '.join(VARIANTS)})")
    dims = state["dims"]
    on = np.ones(dims["L"], np.float32)
    if variant == "dropped_layer":
        on[-1] = 0.0
    return forward_tail(state.setdefault("programs", {}), state["params"],
                        dims, jnp.asarray(tokens), first, n_tail, on,
                        {**HOW, **HOW_OF[variant]})
