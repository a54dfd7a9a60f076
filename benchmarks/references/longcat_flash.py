"""Reference ``longcat_flash``: a float32 ``jax.numpy`` forward of the
language model of LongCat-Flash-Omni (the LongCat-Flash family's decoder),
written from its published ``config.json``
(``https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json``)
and the family's public modelling code. No kernel, no cache, no batching,
``jax.default_matmul_precision("highest")``. The contract of a reference
file (``build``, ``tail_logprobs``) is in ``harness/catalog.py``.

One PUBLISHED layer on a token's residual stream x (D wide), ``N`` RMSNorm
with ``rms_norm_eps``, no bias anywhere, two sublayers i = 0, 1 each with an
attention and a dense feed-forward of their own, and ONE routed branch:

    for i in (0, 1):
        a = x + MLA_i(N(x; ln1[i]))
        h = N(a; ln2[i])
        if i == 0:  s = MoE(h)          # the shortcut branch leaves here
        x = a + SwiGLU_i(h)             # dense, ``ffn_hidden_size`` wide
        if i == 1:  x = x + s           # ... and lands here

``MLA(h)``: ``q = W_uq (N(W_dq h) x sqrt(D / q_lora_rank))`` (-> Hq x (nope +
rope); the WHOLE q carries the scale); ``[c, k_r] = W_dkv h``; ``c~ = N(c) x
sqrt(D / kv_lora_rank)``, scaled BEFORE the expansion, so it reaches k_nope
and v and NOT the shared rotary key; ``[k_nope, v] = W_ukv c~`` PER HEAD, as
published (the served path never forms them: it is checked against this
form); rotate-half rotary (``rope_theta``, no scaling) over the rope dims of
every q head and of the ONE k_r every head shares; ``s_ij = [q_nope, q_r]_i .
[k_nope, k_r]_j x (nope + rope)^-1/2``, causal softmax, ``W_o [p v]``.

``MoE(h)``: ``p = softmax(h W_r)`` in float32 over the router's R + Z outputs
(R = the deployment's routed experts, Z = ``zero_expert_num`` identity experts
behind them); the ``moe_topk`` largest of ``p + b`` are chosen (b the router's
correction bias: it chooses and never weighs); ``g_e = routed_scaling_factor x
p_e`` for the chosen, NOT renormalised; ``out = sum_{chosen e < R, held}
g_e SwiGLU_e(h) + (sum_{chosen e >= R} g_e) x h``. No shared expert, no
leading dense layer. After the last layer: norm, untied head, float32
log-softmax.

A chip's share (``expert_shard``): the router keeps its R + Z outputs and
chooses among all; the weights hold routed experts ``first_expert ..
first_expert + n_routed_experts - 1`` of R; what the absent routed experts
would add is left out, here as in the program; the identity part is computed
WHOLE (no chip holds it; every chip computes it for its own tokens, and it
counts once when the shares are added up).

Departures (the configuration file's ``assumed``): rotate-half over the rope
dims (the checkpoint's interleaved layout is a column permutation of ``W_uq``
/ ``W_dkv`` a loader would apply, and none is loaded); ``hidden_act`` silu,
``tie_word_embeddings`` false, ``router_bias`` false, ``norm_topk_prob``
false, where the published file is silent.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls. The layout of that tree is the only thing this file knows of it (n = 2
x ``num_layers`` sublayers, sublayer i of published layer l at 2 l + i; m =
``num_layers`` branches):

    embed [V,D]; final_norm [D]; lm_head [D,V]
    stacks.full: ln1 [n,D]; w_dq [n,D,Rq]; ln_dq [n,Rq]; W_uq as its two
      column sets, each stored TRANSPOSED: w_uq [n,Hq x nope,Rq] and w_uqr
      [n,Hq x rope,Rq]; w_dkv [n,D,Rkv + rope]; ln_kv [n,Rkv]; w_uk
      [n,Hq,nope,Rkv]; w_uv [n,Hq,Rkv,v]; wo [n,Hq,v,D]
    stacks.dense: ln2 [n,D]; wg, wu [n,D,F]; wd [n,F,D]
    stacks.routed: wr [m,D,R+Z]; rbias [m,R+Z]; wg, wu [m,E,D,Fe];
      wd [m,E,Fe,D]

The weights stay in bfloat16 as the program made them and are upcast where
they are used: attention a group of ``HEADS`` heads at a time (a scan whose
body expands that group's K and V over the whole context and runs a block of
``BLOCK`` queries at a time), the held experts ``EXPERTS`` at a time, so that
an 8,000-token sequence fits beside 10.35 GB of weights.

Near-tied routing is scored under both routings, as ``deepseek_v2`` does and
for its reason (top-K routing is discontinuous; the served path's normed
input is bfloat16): where the K-th and the (K+1)-th selection score ``p + b``
of a (position, layer) lie within ``TIE_EPS`` x the K-th score (a router
LOGIT difference of ``TIE_EPS``, to first order) the gates are computed under
both choices and mixed, half and half at an exact tie, the model's own
routing alone from ``TIE_EPS`` on. ``TIE_EPS`` = 2 ** -7: two units in the
last place of a bfloat16 number of size 1.

Variants: ``full``; the probe's two (``dropped_layer``: the last PUBLISHED
layer, both sublayers and its branch; ``int8``); and this model's own broken
controls, each one departure from the text above (``tests/test_longcat_flash
.py`` holds the served path to fail each): ``no_branch`` (the routed branch
left out), ``no_identity`` (the identity experts' part left out),
``renormalised`` (gates over their sum), ``scaling_1``
(``routed_scaling_factor`` 1), ``bias_weighs`` (gates from ``p + b``),
``branch_after_first`` (the branch added after the FIRST sublayer's
feed-forward), ``no_q_scale`` / ``no_kv_scale`` (a latent scale left out),
``q_scale_nope_only`` (the rotary half of q not scaled), ``kv_scale_on_key``
(the shared rotary key scaled too).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "no_branch", "no_identity",
            "renormalised", "scaling_1", "bias_weighs", "branch_after_first",
            "no_q_scale", "no_kv_scale", "q_scale_nope_only",
            "kv_scale_on_key")
BLOCK = 128
HEADS = 16
EXPERTS = 8
TIE_EPS = 2.0 ** -7        # see "Near-tied routing" above


def hf_dims(hf: dict) -> dict:
    shard = hf.get("expert_shard") or {}
    E, D = hf["n_routed_experts"], hf["hidden_size"]
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    return {
        "L": hf["num_layers"], "D": D, "Hq": hf["num_attention_heads"],
        "nope": nope, "rope": rope, "Dv": hf["v_head_dim"],
        "Rq": hf["q_lora_rank"], "Rkv": hf["kv_lora_rank"],
        "V": hf["vocab_size"], "E": E,
        "R": shard.get("router_experts", E),
        "Z": hf.get("zero_expert_num", 0),
        "first": shard.get("first_expert", 0), "K": hf["moe_topk"],
        "scaling": float(hf.get("routed_scaling_factor", 1)),
        "theta": float(hf["rope_theta"]),
        "scale": 1.0 / math.sqrt(nope + rope),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "q_scale": (math.sqrt(D / hf["q_lora_rank"])
                    if hf.get("mla_scale_q_lora") else 1.0),
        "kv_scale": (math.sqrt(D / hf["kv_lora_rank"])
                     if hf.get("mla_scale_kv_lora") else 1.0),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def rotary(x, positions, inv):
    """x [T,H,d]: rotate-half over all d dims (first and second halves are
    the pairs)."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def fake_int8(w, in_axes):
    """Round to 127 levels per output channel (max over the input axes)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale) * scale


# what a variant changes of the layer; ``full`` is HOW
HOW = {"int8": False, "branch": True, "identity": True, "renorm": False,
       "scaling": True, "bias_weighs": False, "lands": 1, "q_scale": "whole",
       "kv_scale": "latent", "tie_eps": TIE_EPS}
HOW_OF = {
    "full": {}, "dropped_layer": {}, "int8": {"int8": True},
    "no_branch": {"branch": False}, "no_identity": {"identity": False},
    "renormalised": {"renorm": True}, "scaling_1": {"scaling": False},
    "bias_weighs": {"bias_weighs": True}, "branch_after_first": {"lands": 0},
    "no_q_scale": {"q_scale": "none"}, "no_kv_scale": {"kv_scale": "none"},
    "q_scale_nope_only": {"q_scale": "nope"},
    "kv_scale_on_key": {"kv_scale": "latent_and_key"},
}


def route(h, wr, bias, dims, how, tie_eps, forced=None):
    """-> (gates over all R + Z outputs [t,R+Z], chosen ids [t,K], near [t]
    bool: a near-tie of the K-th choice). The gates of a near-tied token mix
    the two routings (the module's text). ``forced`` [t,K]: the outputs
    another run chose; the gates are this run's own scores of THOSE."""
    import jax
    import jax.numpy as jnp

    K = dims["K"]
    p = jax.nn.softmax(h @ wr, axis=-1)                     # [t,R+Z] float32
    sel = p + bias
    t = p.shape[0]
    rows = jnp.arange(t)[:, None]
    factor = dims["scaling"] if how["scaling"] else 1.0

    def gates_of(idx):
        v = jnp.take_along_axis(sel if how["bias_weighs"] else p, idx,
                                axis=-1)
        if how["renorm"]:
            v = v / jnp.sum(v, axis=-1, keepdims=True)
        return jnp.zeros_like(p).at[rows, idx].set(v * factor)

    if forced is not None:
        return gates_of(forced), forced, jnp.zeros(t, bool)
    sv, idx = jax.lax.top_k(sel, K + 1)
    own = gates_of(idx[:, :K])
    if not tie_eps:
        return own, idx[:, :K], jnp.zeros(t, bool)
    # the K-th against the (K+1)-th, as a share of the K-th's own score
    margin = (sv[:, K - 1] - sv[:, K]) / jnp.take_along_axis(
        p, idx[:, K - 1:K], axis=-1)[:, 0]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    other = gates_of(jnp.concatenate([idx[:, :K - 1], idx[:, K:]], -1))
    return w * own + (1.0 - w) * other, idx[:, :K], near


def attention(x, at, dims, how):
    """``MLA(N(x; ln1))`` of one sublayer on x [T,D] float32 (T a multiple of
    ``BLOCK``, or any T as one block): K and V expanded PER HEAD."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    T = x.shape[0]
    Hq, Dv, Rkv, rope = dims["Hq"], dims["Dv"], dims["Rkv"], dims["rope"]
    eps = dims["eps"]
    pos = jnp.arange(T)
    inv = jnp.asarray(1.0 / dims["theta"] ** (
        jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    sq = {"whole": (dims["q_scale"],) * 2, "nope": (dims["q_scale"], 1.0),
          "none": (1.0, 1.0)}[how["q_scale"]]
    skv = {"latent": (dims["kv_scale"], 1.0), "none": (1.0, 1.0),
           "latent_and_key": (dims["kv_scale"],) * 2}[how["kv_scale"]]

    h = rms_norm(x, f32(at["ln1"]), eps)
    cq = rms_norm(h @ q8(f32(at["w_dq"]), (0,)), f32(at["ln_dq"]), eps)
    ckv = h @ q8(f32(at["w_dkv"]), (0,))
    c = rms_norm(ckv[:, :Rkv], f32(at["ln_kv"]), eps) * skv[0]
    k_r = rotary(ckv[:, None, Rkv:] * skv[1], pos, inv)[:, 0]   # [T,rope]

    nb = T // BLOCK if T % BLOCK == 0 else 1
    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])
    hg = HEADS if Hq % HEADS == 0 else Hq

    def heads(ws):
        """A group of heads: their q, their K and V over the whole context
        (expanded per head), causal softmax, their part of W_o's sum."""
        w_uq, w_uqr, w_uk, w_uv, wo = ws
        q_nope = ((cq * sq[0]) @ q8(f32(w_uq), (1,)).T).reshape(T, hg, -1)
        q_r = rotary(((cq * sq[1]) @ q8(f32(w_uqr), (1,)).T).reshape(
            T, hg, -1), pos, inv)
        k_nope = jnp.einsum("tr,hnr->thn", c, q8(f32(w_uk), (2,)))
        v = jnp.einsum("tr,hrv->thv", c, q8(f32(w_uv), (1,)))

        def attend(args):
            qn, qr, pb = args                       # a block of queries
            s = (jnp.einsum("thn,shn->hts", qn, k_nope)
                 + jnp.einsum("thr,sr->hts", qr, k_r))
            s = jnp.where((pb[:, None] >= pos[None, :])[None],
                          s * dims["scale"], -jnp.inf)
            return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v)

        a = jax.lax.map(attend, (blocks(q_nope), blocks(q_r), blocks(pos)))
        return jnp.einsum("thv,hvd->td", a.reshape(T, hg, Dv),
                          q8(f32(wo), (0, 1)))

    by_group = lambda w: w.reshape(Hq // hg, hg, *w.shape[1:])
    # [Hq x ., Rq] -> [groups, a group's rows, Rq]
    cols = lambda w: w.reshape(Hq // hg, -1, w.shape[-1])
    return jnp.sum(jax.lax.map(heads, (cols(at["w_uq"]), cols(at["w_uqr"]),
                                       by_group(at["w_uk"]),
                                       by_group(at["w_uv"]),
                                       by_group(at["wo"]))), axis=0)


def moe(h, br, dims, how, tie_eps, forced=None):
    """``MoE(h)`` on h [T,D]: the held routed experts' part and the identity
    experts' part. -> (out [T,D], chosen ids [T,K], near [T] bool)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    T = h.shape[0]
    gates, idx, near = route(h, q8(f32(br["wr"]), (0,)), f32(br["rbias"]),
                             dims, how, tie_eps, forced)
    first, E, R = dims["first"], dims["E"], dims["R"]
    held = gates[:, first:first + E]                # the absent: left out
    eg = EXPERTS if E % EXPERTS == 0 else E

    def experts(ws):
        """A group of held experts, every token through each, gated."""
        wg, wu, wd, g = ws
        wg, wu = q8(f32(wg), (1,)), q8(f32(wu), (1,))
        act = (jax.nn.silu(jnp.einsum("td,edf->tef", h, wg))
               * jnp.einsum("td,edf->tef", h, wu))
        return jnp.einsum("tef,efd,te->td", act, q8(f32(wd), (1,)), g)

    grouped = lambda w: w.reshape(E // eg, eg, *w.shape[1:])
    y = jnp.sum(jax.lax.map(experts, (
        grouped(br["wg"]), grouped(br["wu"]), grouped(br["wd"]),
        jnp.moveaxis(held.reshape(T, E // eg, eg), 1, 0))), axis=0)
    if how["identity"]:
        # an identity expert's part is gate x input: one scalar a token
        y = y + jnp.sum(gates[:, R:], axis=-1, keepdims=True) * h
    return y, idx, near


def layer(x, ats, ffs, br, dims, on, how, trace=False, forced=None):
    """One PUBLISHED layer on x [T,D] float32. ``ats`` / ``ffs``: the two
    sublayers' slices of the attention and dense stacks, ``br`` the branch's
    (bfloat16 as the program made them), upcast where used. -> (x, near-tied
    tokens [T] bool); with ``trace`` the second is the chosen outputs [T,K].
    ``forced``: as :func:`route`'s."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    s = seen = None
    for i, (at, ff) in enumerate(zip(ats, ffs)):
        a = x + on * attention(x, at, dims, how)
        h = rms_norm(a, f32(ff["ln2"]), dims["eps"])
        if i == 0:
            s, idx, near = moe(h, br, dims, how,
                               0.0 if trace else how["tie_eps"], forced)
            seen = idx if trace else near
        wg, wu = q8(f32(ff["wg"]), (0,)), q8(f32(ff["wu"]), (0,))
        x = a + on * ((jax.nn.silu(h @ wg) * (h @ wu))
                      @ q8(f32(ff["wd"]), (0,)))
        if i == how["lands"] and how["branch"]:
            x = x + on * s
    return x, seen


def _at(stack, i):
    return {n: w[i] for n, w in stack.items()}


def _published(params, l):
    """-> (the two sublayers' attention slices, their dense slices, the
    branch's slices) of published layer ``l`` (a traced or Python index)."""
    st = params["stacks"]
    return ([_at(st["full"], 2 * l + i) for i in (0, 1)],
            [_at(st["dense"], 2 * l + i) for i in (0, 1)],
            _at(st["routed"], l))


def _layer_step(x, stacks, l, on, *, dims, how):
    import jax

    # the layer's slices are taken INSIDE the program (a traced index: one
    # program for every layer), so no copy of them is made beside the stack
    with jax.default_matmul_precision("highest"):
        return layer(x, *_published({"stacks": stacks}, l), dims, on, how)


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
    them is inert), near-tied [L,T] bool). One program for the layers and one
    for the head, run a layer at a time from here (``programs`` keeps
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
    nears = []
    for l in range(dims["L"]):
        x, near = program_of("layer", _layer_step)(
            x, params["stacks"], l, layers_on[l])
        nears.append(near)
    logp = program_of("head", _head_step, n_tail=n_tail)(
        x, params["final_norm"], params["lm_head"], first)
    return logp, jnp.stack(nears)


def trace(state: dict, tokens, variant: str = "full"):
    """For the tests: the model's own routing with nothing mixed at a
    near-tie, on one sequence ``tokens`` [T] -> (chosen router outputs of
    the published layers [L,T,K] int32, log-softmax [T,V])."""
    import jax
    import jax.numpy as jnp

    dims = state["dims"]
    how = {**HOW, **HOW_OF[variant]}

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            chosen = []
            for l in range(dims["L"]):
                on = 0.0 if (variant == "dropped_layer"
                             and l == dims["L"] - 1) else 1.0
                x, ch = layer(x, *_published(params, l), dims, on, how,
                              trace=True)
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
    print(f"longcat_flash {variant}: {int(near.sum())} of {near.size} "
          f"(position, layer) pairs up to the last scored position, "
          f"{int(near[:, first:].sum())} of {near[:, first:].size} at the "
          f"scored positions, lie within {how['tie_eps']:g} of a tie in "
          f"router score and were scored under both routings",
          file=sys.stderr, flush=True)
    return logp
