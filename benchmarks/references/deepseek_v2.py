"""Reference ``deepseek_v2``: a float32 ``jax.numpy`` forward of
DeepSeek-V2, written from its published ``config.json`` and
``modeling_deepseek.py``
(``https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json``).
No kernel, no cache, no batching, ``jax.default_matmul_precision("highest")``.
The contract of a reference file (``build``, ``tail_logprobs``) is in
``harness/catalog.py``.

Layer ``l`` on a token's residual stream x (D wide), all norms RMSNorm with
``rms_norm_eps``, no bias anywhere:

1. ``h = norm(x)``; ``q = W_uq norm(W_dq h)`` (D -> ``q_lora_rank`` -> Hq x
   (nope + rope)); a head's q = [q_nope | q_pe].
2. ``[c, k_pe] = W_dkv h`` (D -> ``kv_lora_rank`` + rope); ``c~ =
   norm(c)``; ``[k_nope, v] = W_ukv c~`` (-> Hq x (nope + v)): K and V PER
   HEAD, as published (the served path never forms them in decode: it is
   checked against this form).
3. Rotate-half rotary over the rope dims of every q_pe and of the ONE k_pe,
   which every head shares. YaRN frequencies: ``inv = f_inter (1 - m) +
   f_extra m``, ``f_extra = theta^(-2i / rope)``, ``f_inter = f_extra /
   factor``, ``m = 1 - clip((i - low) / (high - low), 0, 1)``, ``low, high``
   the correction range of ``beta_fast``, ``beta_slow`` over
   ``original_max_position_embeddings``; the tables' factor ``mscale /
   mscale_all_dim`` is 1.
4. ``s_ij = [q_nope, q_pe]_i . [k_nope, k_pe]_j x (nope + rope)^-1/2 x (0.1
   mscale_all_dim ln factor + 1)^2``, causal softmax, ``x += W_o [p v]``.
5. ``h2 = norm(x)``. Layers before ``first_k_dense_replace``: SwiGLU of
   width ``intermediate_size``. Else ``s = softmax(h2 W_r)`` in float32 over
   the R routed experts; a group (``n_group`` equal groups) scores as its
   best expert; the ``topk_group`` best groups stay; the K best ``s`` inside
   them are chosen; ``g_e = routed_scaling_factor x s_e`` (NOT
   renormalised); ``y = sum_{e chosen AND held} g_e SwiGLU_e(h2) +
   SwiGLU_shared(h2)`` (the shared expert ``n_shared_experts x
   moe_intermediate_size`` wide); ``x += y``.
6. After the last layer: norm, untied head, float32 log-softmax.

A chip's share (``expert_shard``): the router is R = ``router_experts``
wide and chooses among all R, in all its groups; the weights hold experts
``first_expert .. first_expert + n_routed_experts - 1``; what the absent
experts would add is left out, here as in the program, and that partial sum
(with the WHOLE shared expert) goes on to the next layer.

Departure (the configuration file's ``assumed``): rotate-half over the rope
dims; the checkpoint's interleaved layout is a column permutation of
``W_uq`` / ``W_dkv`` a loader would apply, and none is loaded.

From the program it takes the weights as DATA and nothing else:
``llama.init_params(cfg, PRNGKey(seed))`` is what the server's random init
calls. The layout of that tree is the only thing this file knows of it:

    embed [V,D]; final_norm [D]; lm_head [D,V]
    stacks.full: ln1 [n,D]; w_dq [n,D,Rq]; ln_dq [n,Rq]; W_uq as its two
      column sets, each stored TRANSPOSED: w_uq [n,Hq x nope,Rq] and w_uqr
      [n,Hq x rope,Rq] (a head's rows side by side); w_dkv [n,D,Rkv + rope]; ln_kv [n,Rkv];
      w_uk [n,Hq,nope,Rkv]; w_uv [n,Hq,Rkv,v]; wo [n,Hq,v,D]
    stacks.dense: ln2 [n,D]; wg, wu [n,D,F]; wd [n,F,D]
    stacks.routed: ln2 [n,D]; wr [n,D,R]; wg, wu [n,E,D,Fe]; wd [n,E,Fe,D];
      ws_g, ws_u [n,D,Fs]; ws_d [n,Fs,D]

The weights stay in bfloat16 as the program made them and are upcast where
they are used: attention a group of ``HEADS`` heads at a time (a scan whose
body expands that group's K and V over the whole context and runs a block
of ``BLOCK`` queries at a time), the held experts ``EXPERTS`` at a time (a
scan over groups of experts, every token through each), so that a
13,100-token sequence fits beside 10.3 GB of weights.

Near-tied routing is scored under both routings, as ``keye_vl2`` and
``mimo_v2_flash`` do and for their reason (top-K routing is discontinuous;
the served path's normed input is bfloat16): where the K-th and the
(K+1)-th chosen router LOGIT of a (position, layer), or the ``topk_group``-th
and the next GROUP's best logit, lie within ``TIE_EPS`` the gates are
computed under both choices and mixed, half and half at an exact tie, the
model's own routing alone from ``TIE_EPS`` on. ``TIE_EPS`` = 2 ** -7 in
router logit (softmax scores order as their logits do): two units in the
last place of a bfloat16 number of size 1.

Variants: ``full``; the probe's two (``dropped_layer``, ``int8``); and this
model's own broken controls, each one departure from the text above
(``benchmarks/tests/own_variants.py`` scores the served path against each):
``no_shared`` (no shared expert), ``plain_top6`` (top-K among all experts,
no groups), ``renormalised`` (gates over their sum), ``scaling_1``
(``routed_scaling_factor`` 1), ``no_pe_term`` (scores without ``q_pe .
k_pe``), ``plain_rotary`` (rotary without YaRN's frequencies).
"""

from __future__ import annotations

import math
from functools import partial

from dynamo_tpu.models import llama as program

VARIANTS = ("full", "dropped_layer", "int8", "no_shared", "plain_top6",
            "renormalised", "scaling_1", "no_pe_term", "plain_rotary")
BLOCK = 128
HEADS = 32
EXPERTS = 8
TIE_EPS = 2.0 ** -7        # router logit; see "Near-tied routing" above


def hf_dims(hf: dict) -> dict:
    L = hf["num_hidden_layers"]
    shard = hf.get("expert_shard") or {}
    first_dense, freq = hf.get("first_k_dense_replace", 0), hf.get(
        "moe_layer_freq", 1)
    rs = hf.get("rope_scaling") or {}
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    scale = 1.0 / math.sqrt(nope + rope)
    if rs:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    E = hf["n_routed_experts"]
    return {
        "L": L, "D": hf["hidden_size"], "Hq": hf["num_attention_heads"],
        "nope": nope, "rope": rope, "Dv": hf["v_head_dim"],
        "Rkv": hf["kv_lora_rank"], "V": hf["vocab_size"], "E": E,
        "R": shard.get("router_experts", E),
        "first": shard.get("first_expert", 0),
        "K": hf["num_experts_per_tok"], "groups": hf["n_group"],
        "topk_group": hf["topk_group"],
        "scaling": float(hf["routed_scaling_factor"]),
        "theta": float(hf["rope_theta"]), "yarn": dict(rs),
        "scale": scale, "eps": float(hf["rms_norm_eps"]),
        "routed": tuple(int(l >= first_dense and l % freq == 0)
                        for l in range(L)),
    }


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def yarn_inv_freq(dims, plain: bool = False):
    """The rotary frequencies [rope / 2] (numpy float64 -> float32)."""
    import numpy as np

    d, theta, rs = dims["rope"], dims["theta"], dims["yarn"]
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if plain or not rs:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def correction(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    m = 1.0 - ramp
    return (extra / rs["factor"] * (1.0 - m) + extra * m).astype(np.float32)


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
HOW = {"int8": False, "shared": True, "groups": True, "renorm": False,
       "scaling": True, "pe_term": True, "yarn": True, "tie_eps": TIE_EPS}
HOW_OF = {
    "full": {}, "dropped_layer": {}, "int8": {"int8": True},
    "no_shared": {"shared": False}, "plain_top6": {"groups": False},
    "renormalised": {"renorm": True}, "scaling_1": {"scaling": False},
    "no_pe_term": {"pe_term": False}, "plain_rotary": {"yarn": False},
}


def route(h2, wr, dims, how, tie_eps, forced=None):
    """-> (gates over all R experts [t,R], chosen ids [t,K], near [t] bool:
    a near-tie of the K-th expert or of the last group that stays). The
    gates of a near-tied token mix the two routings (the module's text).
    ``forced`` [t,K]: the experts another run chose; the gates are this
    run's own scores of THOSE experts (``benchmarks/tests/served_routing.py``:
    what is left of the distance once routing flips are taken out)."""
    import jax
    import jax.numpy as jnp

    K, G, Gk = dims["K"], dims["groups"], dims["topk_group"]
    z = h2 @ wr                                              # [t,R] float32
    s = jax.nn.softmax(z, axis=-1)
    t, R = z.shape
    rows = jnp.arange(t)[:, None]
    factor = dims["scaling"] if how["scaling"] else 1.0

    def gates_of(idx):
        v = jnp.take_along_axis(s, idx, axis=-1)
        if how["renorm"]:
            v = v / jnp.sum(v, axis=-1, keepdims=True)
        return jnp.zeros_like(s).at[rows, idx].set(v * factor)

    if forced is not None:
        return gates_of(forced), forced, jnp.zeros(t, bool)

    def choose(stay):
        """Top K + 1 logits among the experts of the groups that stay."""
        zz = jnp.where(jnp.repeat(stay, R // G, axis=-1), z, -jnp.inf)
        return jax.lax.top_k(zz, K + 1)

    if how["groups"]:
        best = z.reshape(t, G, R // G).max(axis=-1)          # [t,G]
        gv, gi = jax.lax.top_k(best, min(Gk + 1, G))
        stay = jnp.zeros((t, G), bool).at[rows, gi[:, :Gk]].set(True)
    else:
        stay = jnp.ones((t, G), bool)
    zv, idx = choose(stay)
    own = gates_of(idx[:, :K])
    if not tie_eps:
        return own, idx[:, :K], jnp.zeros(t, bool)
    # the K-th against the (K+1)-th expert inside the groups that stay
    margin = zv[:, K - 1] - zv[:, K]
    near = margin < tie_eps
    w = jnp.where(near, 0.5 + 0.5 * margin / tie_eps, 1.0)[:, None]
    other = gates_of(jnp.concatenate([idx[:, :K - 1], idx[:, K:]], -1))
    mixed = w * own + (1.0 - w) * other
    if how["groups"] and Gk < G:
        # the last group that stays against the best one that does not
        gm = gv[:, Gk - 1] - gv[:, Gk]
        gnear = gm < tie_eps
        wg = jnp.where(gnear, 0.5 + 0.5 * gm / tie_eps, 1.0)[:, None]
        swapped = jnp.zeros((t, G), bool).at[
            rows, jnp.concatenate([gi[:, :Gk - 1], gi[:, Gk:]], -1)
        ].set(True)
        _, idx2 = choose(swapped)
        mixed = wg * mixed + (1.0 - wg) * gates_of(idx2[:, :K])
        near = near | gnear
    return mixed, idx[:, :K], near


def layer(x, at, ff, dims, on, how, trace=False, forced=None):
    """One block on x [T,D] float32 (T a multiple of ``BLOCK``, or any T as
    one block). ``at`` / ``ff``: this layer's slices of its attention and
    feed-forward stacks (bfloat16 as the program made them), upcast where
    used. -> (x, near-tied tokens [T] bool); with ``trace`` the second is
    the chosen experts [T,K] (None for a dense layer). ``forced``: as
    :func:`route`'s."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    q8 = ((lambda w, ax: fake_int8(w, ax)) if how["int8"]
          else (lambda w, ax: w))
    T = x.shape[0]
    Hq, nope, Dv, Rkv = dims["Hq"], dims["nope"], dims["Dv"], dims["Rkv"]
    eps = dims["eps"]
    pos = jnp.arange(T)
    inv = jnp.asarray(yarn_inv_freq(dims, plain=not how["yarn"]))

    h = rms_norm(x, f32(at["ln1"]), eps)
    cq = rms_norm(h @ q8(f32(at["w_dq"]), (0,)), f32(at["ln_dq"]), eps)
    ckv = h @ q8(f32(at["w_dkv"]), (0,))
    c = rms_norm(ckv[:, :Rkv], f32(at["ln_kv"]), eps)
    k_pe = rotary(ckv[:, None, Rkv:], pos, inv)[:, 0]           # [T,rope]

    nb = T // BLOCK if T % BLOCK == 0 else 1
    blocks = lambda a: a.reshape(nb, T // nb, *a.shape[1:])
    hg = HEADS if Hq % HEADS == 0 else Hq

    def heads(ws):
        """A group of heads: their q, their K and V over the whole context
        (expanded per head), causal softmax, their part of W_o's sum."""
        w_uq, w_uqr, w_uk, w_uv, wo = ws
        q_nope = (cq @ q8(f32(w_uq), (1,)).T).reshape(T, hg, -1)
        q_pe = rotary((cq @ q8(f32(w_uqr), (1,)).T).reshape(T, hg, -1), pos,
                      inv)
        k_nope = jnp.einsum("tr,hnr->thn", c, q8(f32(w_uk), (2,)))
        v = jnp.einsum("tr,hrv->thv", c, q8(f32(w_uv), (1,)))

        def attend(args):
            qn, qp, pb = args                       # a block of queries
            s = jnp.einsum("thn,shn->hts", qn, k_nope)
            if how["pe_term"]:
                s = s + jnp.einsum("thr,sr->hts", qp, k_pe)
            s = jnp.where((pb[:, None] >= pos[None, :])[None],
                          s * dims["scale"], -jnp.inf)
            return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, axis=-1), v)

        a = jax.lax.map(attend, (blocks(q_nope), blocks(q_pe), blocks(pos)))
        return jnp.einsum("thv,hvd->td", a.reshape(T, hg, Dv),
                          q8(f32(wo), (0, 1)))

    by_group = lambda w: w.reshape(Hq // hg, hg, *w.shape[1:])
    # [Hq x ., Rq] -> [groups, a group's rows, Rq]
    cols = lambda w: w.reshape(Hq // hg, -1, w.shape[-1])
    o = jnp.sum(jax.lax.map(heads, (cols(at["w_uq"]), cols(at["w_uqr"]),
                                    by_group(at["w_uk"]),
                                    by_group(at["w_uv"]),
                                    by_group(at["wo"]))), axis=0)
    x = x + on * o

    h2 = rms_norm(x, f32(ff["ln2"]), eps)

    def swiglu(wg, wu, wd):
        wg, wu = q8(f32(wg), (0,)), q8(f32(wu), (0,))
        return (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ q8(f32(wd), (0,))

    if "wr" not in ff:
        y = swiglu(ff["wg"], ff["wu"], ff["wd"])
        return x + on * y, (None if trace else jnp.zeros(T, bool))
    gates, idx, near = route(h2, q8(f32(ff["wr"]), (0,)), dims, how,
                             0.0 if trace else how["tie_eps"], forced)
    first, E = dims["first"], dims["E"]
    held = gates[:, first:first + E]                # the absent: left out
    eg = EXPERTS if E % EXPERTS == 0 else E

    def experts(ws):
        """A group of held experts, every token through each, gated."""
        wg, wu, wd, g = ws
        wg, wu = q8(f32(wg), (1,)), q8(f32(wu), (1,))
        act = (jax.nn.silu(jnp.einsum("td,edf->tef", h2, wg))
               * jnp.einsum("td,edf->tef", h2, wu))
        return jnp.einsum("tef,efd,te->td", act, q8(f32(wd), (1,)), g)

    grouped = lambda w: w.reshape(E // eg, eg, *w.shape[1:])
    y = jnp.sum(jax.lax.map(experts, (
        grouped(ff["wg"]), grouped(ff["wu"]), grouped(ff["wd"]),
        jnp.moveaxis(held.reshape(T, E // eg, eg), 1, 0))), axis=0)
    if how["shared"]:
        y = y + swiglu(ff["ws_g"], ff["ws_u"], ff["ws_d"])
    return x + on * y, (idx if trace else near)


def _layers(params, dims):
    """-> per layer (attention stack, index in it, feed-forward stack, index
    in it): a layer lies at its index among its kind."""
    st = params["stacks"]
    seen = {"dense": 0, "routed": 0}
    out = []
    for l, routed in enumerate(dims["routed"]):
        f = "routed" if routed else "dense"
        out.append((st["full"], l, st[f], seen[f]))
        seen[f] += 1
    return out


def _at(stack, i):
    return {n: w[i] for n, w in stack.items()}


def _layer_step(x, at, ia, ff, jf, on, *, dims, how):
    import jax

    # the layer's slices are taken INSIDE the program (a traced index: one
    # program a kind of layer), so no copy of them is made beside the stack
    with jax.default_matmul_precision("highest"):
        return layer(x, _at(at, ia), _at(ff, jf), dims, on, how)


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
    them is inert), near-tied [L,T] bool). One program a kind of
    feed-forward and one for the head, run a layer at a time from here
    (``programs`` keeps them)."""
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
    for l, (at, ia, ff, jf) in enumerate(_layers(params, dims)):
        x, near = program_of("layer", _layer_step)(
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
            for l, (at, ia, ff, jf) in enumerate(layers):
                on = 0.0 if (variant == "dropped_layer"
                             and l == len(layers) - 1) else 1.0
                x, ch = layer(x, _at(at, ia), _at(ff, jf), dims, on, how,
                              trace=True)
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
    print(f"deepseek_v2 {variant}: {int(near.sum())} of {near.size} "
          f"(position, layer) pairs up to the last scored position, "
          f"{int(near[:, first:].sum())} of {near[:, first:].size} at the "
          f"scored positions, lie within {how['tie_eps']:g} of a tie in "
          f"router logit and were scored under both routings",
          file=sys.stderr, flush=True)
    return logp
