"""Reference ``lfm2_moe``: a float32 ``jax.numpy`` forward of LFM2-MoE
(``model_type: lfm2_moe``), written from the published ``config.json``
(``https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json``) and
the family's modeling file (``modeling_lfm2_moe.py``). No kernel, no cache,
no tail, no chunk, no batching: the whole sequence at once, the convolution
a plain causal three-tap sum. ``jax.default_matmul_precision("highest")``.
The contract of a reference file (``build``, ``tail_logprobs``) is in
``harness/catalog.py``.

With x the residual stream (the embedding row), every norm ``w * x /
rms(x)`` with ``norm_eps``, no bias anywhere (``conv_bias: false``):

1. every layer: ``x <- x + Op(norm_op(x))``, then ``x <- x + Ffn(norm_ffn(x))``.
2. ``Op`` of a ``conv`` layer, for the normed input h_t: ``[B_t | C_t | z_t]
   = W_in h_t`` (D -> 3 D, split in that order); ``u_t = B_t * z_t``; ``c_t =
   sum_{k<K} w[k] * u_{t-K+1+k}`` (depthwise, causal, K = ``conv_L_cache``
   taps, one weight a channel and tap, u before the sequence = 0); ``y_t =
   C_t * c_t``; ``Op = W_out y_t``. No activation: both gates are products.
3. ``Op`` of a ``full_attention`` layer: q (``num_attention_heads``), k, v
   (``num_key_value_heads``) of width hidden / heads; RMSNorm over each
   head's width of q and of k (one weight vector each, shared by the heads);
   rotary, rotate-half over the whole width, ``rope_theta``, unscaled; causal
   softmax of ``q . k / sqrt(width)``, grouped queries; ``W_o``.
4. ``Ffn`` of layers < ``num_dense_layers``: ``W_2(silu(W_1 h) * W_3 h)`` at
   ``intermediate_size``.
5. ``Ffn`` of the others: ``s = sigmoid(W_r h)`` over ``num_experts``; the
   ``num_experts_per_tok`` experts with the largest ``s + b`` (b:
   ``expert_bias``); gates ``g = s_chosen / (sum s_chosen + 1e-6) x
   routed_scaling_factor``: the bias chooses and never weighs; ``sum_e g_e
   SwiGLU_e(h)`` at ``moe_intermediate_size``. No shared expert.
6. after the last layer one RMSNorm (the source's ``embedding_norm``), the
   head TIED to the embedding, float32 log-softmax.

Departures from the published file: none in the mathematics. ``assumed`` of
the configuration file lists what no config says (the tied head, the seeded
init).

From the program it takes the weights as DATA and nothing else
(``llama.init_params(cfg, PRNGKey(seed))``: what the server's random init
calls). The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]
    stacks.full (the attention layers, each at its index among them):
      ln1 [n,D]; wq [n,D,Hq,Dh]; wk, wv [n,D,Hkv,Dh]; wo [n,Hq,Dh,D];
      ln_q, ln_k [n,Dh]
    stacks.conv (the conv layers likewise): ln1 [n,D]; w_in [n,D,3D]
      (columns B | C | z); conv_w [n,K,D] (tap K-1 meets the token itself);
      w_out [n,D,D]
    stacks.dense (the leading layers): ln2 [n,D]; wg, wu [n,D,F]; wd [n,F,D]
    stacks.routed (the others): ln2 [n,D]; wr [n,D,E]; rbias [n,E]; wg, wu
      [n,E,D,Fe]; wd [n,E,Fe,D]

The weights stay in bfloat16 as the program made them and are upcast a layer
at a time; attention and experts are computed a block of ``BLOCK`` positions
at a time (each token through its chosen experts by a plain gather of their
matrices' rows of the product: every expert on the block, gated by a one-hot
of the chosen ids, independent of ``models/moe.py``), so that 8,448 tokens
fit beside 8 GB of weights.

Near-tied routing is scored under both routings, as the keye, mimo and
deepseek references do and for their reason (top-k routing is discontinuous;
the served path's normed input is bfloat16): where the K-th and the (K+1)-th
selection score ``s + b`` of a (position, layer) lie within ``TIE_EPS`` the
expert output is computed under both chosen sets and mixed, half and half at
an exact tie, the model's own routing alone from ``TIE_EPS`` on. ``TIE_EPS``
= 2 ** -9 in score: a sigmoid's slope is at most a quarter, so this is 2 **
-7 in router logit, two units in the last place of a bfloat16 number of
size 1.

Variants: ``full``; the probe's two (``dropped_layer``: the last layer's two
branches switched off; ``int8``: every weight matrix rounded to 127 levels
per output channel); and this model's own controls, each ONE departure from
the text above: ``tail_dropped`` (u before every position that is a multiple
of ``TAIL_EVERY`` = 256 taken as 0: what a chunk that started from a zero
tail computes), ``gate_c_off`` (y = c), ``gate_b_off`` (u = z),
``taps_reversed`` (w[k] meets u_{t-k}), ``bias_off`` (plain top-k of s),
``renorm_off`` (gates = the chosen scores), ``qk_norm_off``,
``dense_as_routed`` (layers < ``num_dense_layers`` given the FIRST routed
layer's experts in place of their dense feed-forward). On the chip, through
the comparison that decides ``correct`` at the configuration's limit 0.16
(``benchmarks/tests/own_variants.py``, my chip run, PR 44; sound 0.1115 on
that seed): ``gate_c_off`` 4.46, ``gate_b_off`` 4.54, ``taps_reversed`` 4.11,
``dense_as_routed`` 3.27, ``renorm_off`` 1.99, ``bias_off`` 0.316,
``qk_norm_off`` 0.280: NOT correct. ``tail_dropped`` reads 0.1132 and IS
WHAT THE LIMIT DOES NOT CATCH: a lost tail damages the two positions behind
the boundary it was lost at (and what attends to them, faintly), and the
sample scores the 64 tokens behind prompts of 128 / 647 / 1,365 / 6,208,
none of which lies within two positions of a multiple of 256. The tier-1
tests hold the tail instead, where every position is compared
(``tests/test_lfm2_moe.py``: chunks of 1, 2, 3, 5, 16 and 32 tokens then
decode, a lane's tail through idle dispatches, ``conv_mix`` against a loop).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "tail_dropped", "gate_c_off",
            "gate_b_off", "taps_reversed", "bias_off", "renorm_off",
            "qk_norm_off", "dense_as_routed")
BLOCK = 128
TAIL_EVERY = 256
TIE_EPS = 2.0 ** -9        # selection score; see "Near-tied routing" above


def hf_dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    rope = hf.get("rope_parameters") or {}
    return {
        "L": L, "D": hf["hidden_size"], "Hq": hf["num_attention_heads"],
        "Hkv": hf["num_key_value_heads"],
        "Dh": hf["hidden_size"] // hf["num_attention_heads"],
        "V": hf["vocab_size"], "K": int(hf.get("conv_L_cache", 3)),
        "E": hf["num_experts"], "k": hf["num_experts_per_tok"],
        "eps": float(hf.get("norm_eps", 1e-5)),
        "theta": float(rope.get("rope_theta", hf.get("rope_theta"))),
        "scaling": float(hf.get("routed_scaling_factor", 1.0)),
        "kinds": tuple(hf["layer_types"][:L]),
        "dense": int(hf.get("num_dense_layers", 0)),
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
HOW = {"int8": False, "tail": True, "gate_c": True, "gate_b": True,
       "reversed": False, "bias": True, "renorm": True, "qk_norm": True,
       "dense_as_routed": False, "tie_eps": TIE_EPS}
HOW_OF = {"full": {}, "dropped_layer": {}, "int8": {"int8": True},
          "tail_dropped": {"tail": False}, "gate_c_off": {"gate_c": False},
          "gate_b_off": {"gate_b": False},
          "taps_reversed": {"reversed": True}, "bias_off": {"bias": False},
          "renorm_off": {"renorm": False}, "qk_norm_off": {"qk_norm": False},
          "dense_as_routed": {"dense_as_routed": True}}


def conv_mix(h, mp, dims, how):
    """``Op`` of a conv layer on the normed inputs h [T,D]: the plain causal
    sum over the whole sequence."""
    import jax.numpy as jnp

    T, D, K = h.shape[0], dims["D"], dims["K"]
    bcz = h @ mp["w_in"]
    Bg, Cg, z = bcz[:, :D], bcz[:, D:2 * D], bcz[:, 2 * D:]
    u = Bg * z if how["gate_b"] else z
    w = mp["conv_w"][::-1] if how["reversed"] else mp["conv_w"]
    t = jnp.arange(T)
    c = jnp.zeros_like(u)
    for k in range(K):
        back = K - 1 - k                    # tap k meets u_{t - back}
        past = jnp.concatenate([jnp.zeros((back, D), u.dtype),
                                u[:T - back]], 0) if back else u
        if not how["tail"]:
            # a chunk that starts at a multiple of TAIL_EVERY sees no u
            # from before it
            past = jnp.where(((t % TAIL_EVERY) >= back)[:, None], past, 0.0)
        c = c + w[k] * past
    y = Cg * c if how["gate_c"] else c
    return y @ mp["w_out"]


def attention_mix(h, at, dims, how):
    """``Op`` of an attention layer on the normed inputs h [T,D]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    Hq, Hkv, Dh = dims["Hq"], dims["Hkv"], dims["Dh"]
    pos = jnp.arange(T)
    q = jnp.einsum("td,dhk->thk", h, at["wq"])
    k = jnp.einsum("td,dhk->thk", h, at["wk"])
    v = jnp.einsum("td,dhk->thk", h, at["wv"])
    if how["qk_norm"]:
        q = rms_norm(q, at["ln_q"], dims["eps"])
        k = rms_norm(k, at["ln_k"], dims["eps"])
    q, k = rotary(q, pos, dims["theta"]), rotary(k, pos, dims["theta"])
    nb = T // BLOCK if T % BLOCK == 0 else 1

    def attend(args):
        qb, pb = args                               # a block of queries
        qg = qb.reshape(-1, Hkv, Hq // Hkv, Dh)
        s = jnp.einsum("tgqk,sgk->gqts", qg, k) / math.sqrt(Dh)
        s = jnp.where((pb[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqts,sgk->tgqk", p, v).reshape(-1, Hq, Dh)

    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])
    a = jax.lax.map(attend, (blocks(q), blocks(pos))).reshape(T, Hq, Dh)
    return jnp.einsum("thk,hkd->td", a, at["wo"])


def route(h2, wr, b, dims, how, tie_eps):
    """-> (gates over all E experts [t,E], chosen ids [t,k], near [t] bool:
    the k-th and (k+1)-th selection score within ``tie_eps``). The gates of
    a near-tied token mix the two routings (the module's text)."""
    import jax
    import jax.numpy as jnp

    k = dims["k"]
    s = jax.nn.sigmoid(h2 @ wr)
    pick = s + b if how["bias"] else s
    _, idx = jax.lax.top_k(pick, k + 1)
    rows = jnp.arange(h2.shape[0])[:, None]

    def gates_of(i):
        v = jnp.take_along_axis(s, i, axis=-1)
        if how["renorm"]:
            v = v / (jnp.sum(v, axis=-1, keepdims=True) + 1e-6)
        return jnp.zeros_like(s).at[rows, i].set(v * dims["scaling"])

    own = gates_of(idx[:, :k])
    if not tie_eps:
        return own, idx[:, :k], jnp.zeros(h2.shape[0], bool)
    other = gates_of(jnp.concatenate([idx[:, :k - 1], idx[:, k:]], -1))
    pk = jnp.take_along_axis(pick, idx[:, k - 1:], axis=-1)
    margin = pk[:, 0] - pk[:, 1]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    return w * own + (1.0 - w) * other, idx[:, :k], near


def routed_ffn(h2, fp, dims, how, trace=False):
    """``Ffn`` of a routed layer on h2 [T,D], a block of positions at a
    time. -> (y [T,D], near-tied [T] bool, or the chosen ids [T,k] with
    ``trace``)."""
    import jax
    import jax.numpy as jnp

    T = h2.shape[0]
    nb = T // BLOCK if T % BLOCK == 0 else 1

    def experts(hb):
        gates, idx, near = route(hb, fp["wr"], fp["rbias"], dims, how,
                                 0.0 if trace else how["tie_eps"])
        act = (jax.nn.silu(jnp.einsum("td,edf->tef", hb, fp["wg"]))
               * jnp.einsum("td,edf->tef", hb, fp["wu"]))
        return jnp.einsum("tef,efd,te->td", act, fp["wd"], gates), idx, near

    y, chosen, near = jax.lax.map(experts, h2.reshape(nb, T // nb, -1))
    return y.reshape(T, -1), (chosen.reshape(T, -1) if trace
                              else near.reshape(T))


# the matrices of a stack and the axes their inputs lie on (int8 rounds per
# OUTPUT channel; an expert's matrices each for itself); every other tensor
# of a stack is a vector or the taps and stays as it is
MATRICES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1), "w_in": (0,),
            "w_out": (0,), "wr": (0,), "wg": (-2,), "wu": (-2,),
            "wd": (-2,)}


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


def layer(x, mix, ia, ff, jf, on, *, kind, routed, dims, how, trace=False):
    """One block on x [T,D] float32. ``mix`` / ``ff``: the layer's operator
    and feed-forward stacks, sliced at TRACED indices inside the program (one
    program a kind of layer, no copy of a layer beside its stack).
    -> (x, near-tied tokens [T] bool; with ``trace`` the chosen experts
    [T,k], None for a dense layer)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        mp, fp = _tensors(mix, ia, how), _tensors(ff, jf, how)
        h = rms_norm(x, mp["ln1"], dims["eps"])
        branch = (conv_mix if kind == "conv" else attention_mix)(
            h, mp, dims, how)
        x = x + on * branch
        h2 = rms_norm(x, fp["ln2"], dims["eps"])
        if not routed:
            y = (jax.nn.silu(h2 @ fp["wg"]) * (h2 @ fp["wu"])) @ fp["wd"]
            return x + on * y, (None if trace
                                else jnp.zeros(x.shape[0], bool))
        y, seen = routed_ffn(h2, fp, dims, how, trace)
        return x + on * y, seen


def head(x, norm, embed, first, *, n_tail, dims, how):
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, first, n_tail, axis=0)
        x = rms_norm(x, norm.astype(jnp.float32), dims["eps"])
        E = embed.astype(jnp.float32)
        if how["int8"]:
            E = fake_int8(E, (1,))
        return jax.nn.log_softmax(x @ E.T, axis=-1)


def _layers(params, dims, how):
    """-> per layer (operator stack, index in it, feed-forward stack, index
    in it, kind, routed?): a layer lies at its index among its kind."""
    st = params["stacks"]
    seen = {"full": 0, "conv": 0, "dense": 0, "routed": 0}
    out = []
    for l, kind in enumerate(dims["kinds"]):
        a = "conv" if kind == "conv" else "full"
        routed = l >= dims["dense"]
        f, jf = ("routed" if routed else "dense"), None
        if not routed and how["dense_as_routed"]:
            f, jf, routed = "routed", 0, True
        out.append((st[a], seen[a], st[f], seen[f] if jf is None else jf,
                    kind, routed))
        seen[a] += 1
        seen[f] += jf is None
    return out


def _embed(params, tokens, how):
    import jax.numpy as jnp

    E = params["embed"]
    if how["int8"]:
        # the table is one matrix, embedding and head: rounded per row (a
        # row is a token's output channel)
        return fake_int8(E.astype(jnp.float32), (1,))[tokens]
    return E[tokens].astype(jnp.float32)


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

    x = _embed(params, tokens, how)
    nears = []
    for l, (mix, ia, ff, jf, kind, routed) in enumerate(
            _layers(params, dims, how)):
        x, near = program_of("layer", layer, kind=kind, routed=routed)(
            x, mix, ia, ff, jf, layers_on[l])
        nears.append(near)
    logp = program_of("head", head, n_tail=n_tail)(
        x, params["final_norm"], params["embed"], first)
    return logp, jnp.stack(nears)


def trace(state: dict, tokens, variant: str = "full"):
    """The routing of the whole forward WITHOUT the mixing at a near-tie, on
    one sequence ``tokens`` [T] -> the chosen experts of each routed layer
    [routed layers, T, k] (the tests compare them with the program's own)."""
    import jax.numpy as jnp
    import numpy as np

    dims, how = state["dims"], {**HOW, **HOW_OF[variant]}
    x = _embed(state["params"], jnp.asarray(tokens), how)
    chosen = []
    for mix, ia, ff, jf, kind, routed in _layers(state["params"], dims, how):
        x, seen = layer(x, mix, ia, ff, jf, 1.0, kind=kind, routed=routed,
                        dims=dims, how=how, trace=True)
        if seen is not None:
            chosen.append(np.asarray(seen))
    return np.stack(chosen)


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
    compiled on first use and kept in the state. Prints how many (position,
    layer) pairs were near-tied and scored under both routings."""
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
    print(f"lfm2_moe {variant}: {int(near.sum())} of {near.size} (position, "
          f"layer) pairs, {int(near[:, first:].sum())} of "
          f"{near[:, first:].size} at the scored positions, lie within "
          f"{how['tie_eps']:g} of a tie in the routing and were scored under "
          f"both", flush=True)
    return logp
