"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` mesh axis.

Mixtral-style block: a router picks ``top_k`` of ``E`` experts per token;
each expert is a SwiGLU FFN; outputs combine weighted by renormalized
router probabilities. Under expert parallelism the expert dimension of the
weights is sharded over ``ep`` — each shard computes only its local
experts' contribution for the full token batch and a ``psum`` over the ep
axis combines them (gate weights for non-local experts are zero on each
shard, so the sum is exact).

Two dispatch formulations, both exact (no capacity limit, no dropped
tokens): SORTED dispatch (stable-sort assignments by expert +
``lax.ragged_dot`` segment matmuls — only the experts hit are read) and
DENSE dispatch (every local expert sees every token — one einsum a matrix,
combines across shards with one psum). An unsharded mesh chooses by
:func:`sorted_wins` (measured on the chip for six geometries, the old rule
outside them); ep/tp-sharded meshes are dense.

Reference capability: the reference inherits MoE/EP from its engines
(SURVEY §2.5 — vllm patch touches deepseek_v2.py); on TPU the in-tree
engine owns it, so this module IS the capability.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_EP, AXIS_TP


def sorted_wins(rows: int, top_k: int, n_experts: int,
                share: float = 1.0) -> bool:
    """The dispatch rule of an unsharded mesh: SORTED (``lax.ragged_dot``
    over the assignments, only the experts hit are read) or DENSE (every
    expert sees every row).

    Measured on a v5e at 128 experts of 2048 x 768, 8 a token, milliseconds
    a layer, dense / sorted (my chip run, PR 28,
    ``benchmarks/tests/moe_dispatch.py``): 12 rows 1.63 / 1.38 (72 of 128
    experts hit); 64 rows 1.64 / 4.37; 256 rows 1.77 / 4.74; 512 rows 3.30 /
    5.06. Dense streams all the weights at 740 GB/s and turns compute-bound
    near 512 rows (187 TFLOP/s); the grouped matmul pays for 16-row groups.
    So INSIDE what was measured (many small experts, ``n_experts >= 16 x
    top_k``, up to 512 rows a call) sorted wins only where a call has fewer
    assignments than experts, so that some experts are certainly idle.
    OUTSIDE it (few large experts as Mixtral's 8 with 2 a token, where dense
    costs 4 x the multiply-adds of a compute-bound chunk, or more than 512
    rows) nothing is measured, and the rule is the one those models always
    had: sorted from 16 rows on.

    A chip's SHARE of the experts (``n_experts`` held of ``n_experts /
    share`` routed over; assignments to absent experts are dropped before
    dispatch) is the same rule in assignments to held experts, ``rows x
    top_k x share`` expected a call. Measured for 16 held of 256, 4096 x
    2048, 8 a token (my chip run, PR 34,
    ``benchmarks/tests/moe_dispatch_share.py``): dense reads all 16 whatever
    the rows, 1.13-1.25 ms a layer; sorted 0.51 ms at 8 assignments (5
    experts hit), 0.82 at 17 (7 hit), 1.48 at 31, 2.2 at 131: sorted pays
    0.1-0.15 ms an expert hit, so it wins while clearly fewer experts are
    hit than held, which an even router ends at as many assignments as
    experts (16 of them hit 10 of 16). And for 40 held of 160, 5120 x 1536,
    6 a token, group-limited, beside a shared expert (my chip run, PR 42; ms
    a layer dense / sorted): 12 rows (21
    assignments, 18 experts hit) 2.67 / 1.36; 16 rows (27, 21) 2.68 / 1.61;
    32 rows (50, 28) 2.68 / 2.13; 64 rows (96, 36) 2.69 / 3.16; 256 rows
    (387, all 40) 2.92 / 6.92: sorted pays 0.07 ms an expert hit, the rule
    (sorted under 40 expected assignments: 26 rows) is right at every row
    count that cell's programs have but the 32-row chunk bucket, where it
    says dense and sorted is a fifth faster. And for 64 experts of 2048 x
    1536, 4 a token, no share: ``n_experts >= 16 x top_k`` holds with
    equality, so the rule says sorted under 16 rows, dense from 16 to 512,
    sorted past 512 (my chip run, PR 44; ms a layer dense / sorted): 8 rows
    (24.6 experts hit) 1.62 / 0.72; 16 rows (40.6) 1.62 / 1.20; 32 rows
    (57.0) 1.62 / 1.83; 256 rows 1.75 / 4.01; 512 rows 3.27 / 4.24; 1,024
    rows 6.50 / 4.82. Sorted pays 0.03 ms an expert hit; dense streams the
    1.2 GB of a layer's experts in 1.62 ms (745 GB/s) and turns
    compute-bound between 256 and 512 rows (1.24 TFLOP a layer at 512: 190
    TFLOP/s). The rule is on the measured side at every row count but 16
    itself, where it says dense and sorted is a quarter faster: a decode
    program of 32 rows is dense, chunks of 32 to 512 rows dense, a chunk of
    1,024 sorted.

    And for 16 held of a router 768 wide (512 routed + 256 identity
    outputs), 6144 x 2048 (75.5 MB an expert, sixteen times the first
    geometry's), 12 a token, so a row gives the held experts 0.25
    assignments and the rule says sorted under 64 rows (my chip run, PR 48,
    ``benchmarks/tests/program_memory_scmoe.py``; ms a layer dense / sorted,
    held experts hit a layer): a chunk of 32 rows (7.0 hit) 2.08 / 1.33; 64
    rows (7.5) 2.01 / 1.63; 128 rows (14.3) 1.99 / 4.09; 256 rows (15.8) 2.15
    / 4.97; 512 rows (15.8) 3.79 / 6.41; the decode program's 32 rows of
    which b are busy and the rest absent: b = 4 (1.5 hit) 2.06 / 0.60; 12
    (4.0) 2.05 / 0.94; 32 (7.0) 2.05 / 1.34. Dense streams the 1.21 GB of a
    layer's held experts in 2.0 ms whatever the rows (600 GB/s) and turns
    compute-bound between 256 and 512 rows (every row through all 16: 0.62
    TFLOP a layer at 512); sorted pays 0.4 ms + 0.135 ms an expert hit (an
    expert at 560 GB/s) while the groups are a few rows, and three times
    that once they are 8 rows and more. The rule is on the measured side at
    every row count this configuration's programs have but the 64-row chunk
    bucket, where it says dense and sorted is a fifth faster; it is left as
    it is.

    And for 16 held of 128, 4096 x 4096 (100.7 MB an expert, the largest
    measured), 8 a token, so a row gives the held experts ONE assignment,
    beside four shared experts that are computed outside this call (my chip
    run, PR 51, ``benchmarks/tests/program_memory_parblock.py``; ms a layer
    dense / sorted, held experts hit a layer): a chunk of 32 rows (14.0 hit)
    2.56 / 3.21; 64 rows (15.3) 2.57 / 5.34; 128 rows (16) 2.48 / 5.58; 256
    rows 2.66 / 5.82; 512 rows 4.77 / 6.43; the decode program's 12 rows of
    which b are busy: b = 3 (2.75 hit) 2.45 / 0.67; 6 (5.5) 2.44 / 1.10; 12
    (8.25) 2.40 / 1.49. Dense streams the 1.61 GB of a layer's held experts
    in 2.45 ms whatever the rows (657 GB/s) and turns compute-bound between
    256 and 512 rows (every row through all 16: 0.82 TFLOP a layer at 512,
    173 TFLOP/s); sorted pays 0.26 ms + 0.15 ms an expert hit at decode rows
    and 0.35 ms an expert once its group is four rows and more. The rule is
    on the measured side at EVERY row count this configuration's programs
    have (the 12-row decode program sorted: 12 expected assignments for 16
    held; every chunk bucket dense) and is left as it is. What it costs: a
    512-row chunk's dense call spends 4.8 ms a layer, 19 of a 43 ms chunk,
    on routed work of 0.05 TFLOP (PERF.md section 7, After PR 51).

    A DECODE step knows which of its rows are busy (``moe_ffn(active=)``):
    an idle row's assignments are absent ones, and where this rule says
    dense with every expert held the call holds both forms and takes the
    sorted one while its busy rows hit fewer than
    :data:`SORTED_UNDER_HIT_SHARE` of the experts (``dispatch_form``
    ``by_hit``). Measured at the decode program's own shape: 32 rows of
    which b are busy and the rest absent, 64 experts of 2048 x 1536, 4 a
    token, the stacked ``layer=`` form inside one ``lax.scan`` (my chip
    run, PR 45, ``scripts/moe_by_hit.py``; ms a layer sorted, experts hit
    a layer; dense 1.617 at every b): b = 1 0.179 (4.0 hit); 2 0.285 (7.5);
    4 0.472 (13.7); 6 0.649 (19.5); 8 0.786 (24.0); 10 0.952 (29.5); 12
    1.084 (33.8); 16 1.301 (41.0); 20 1.489 (47.2); 24 1.631 (51.8); 28
    1.741 (55.5); 32 1.808 (57.7). Sorted costs 0.06 ms + 0.030 ms an
    expert hit, whatever rows are absent behind them (8 busy of 32 rows
    0.786 where an 8-row program read 0.72), and crosses dense at 51 experts
    hit; the choice is taken at 48 (three quarters: 6 % under dense there, 1
    % over it at 52), and the ``lax.cond`` itself costs 0.005 ms a layer
    (by_hit 0.791 at b = 8, 1.620 at b = 32). A chip's SHARE that this rule
    sends dense keeps its program as it is (its crossing is another one,
    0.10-0.15 ms an expert hit above: ROADMAP ``held-experts-hit``)."""
    if n_experts >= 16 * top_k * share and rows <= 512:
        return rows * top_k * share < n_experts
    return rows >= 16


# Where a ``by_hit`` call crosses from sorted to dense, as a share of the
# experts (the readings: :func:`sorted_wins`).
SORTED_UNDER_HIT_SHARE = 0.75


def dispatch_form(rows: int, top_k: int, n_experts: int, share: float = 1.0,
                  mesh=None, width: int = 0, masked: bool = False) -> str:
    """``sorted``, ``dense`` or ``by_hit``: the form :func:`moe_ffn` gives a
    call of ``rows`` rows (experts ``width`` wide) on ``mesh``:
    :func:`sorted_wins` on an unsharded mesh, dense where the experts or
    their width are sharded. ``masked``: the call knows which of its rows
    are busy (a decode step); where the rule would send such a call dense
    with every expert held, it holds BOTH forms and chooses on the device
    from the experts its busy rows hit (``by_hit``: sorted under
    :func:`sorted_under`). The engine reports it for its programs
    (``dyn_engine_info{moe_dispatch}``)."""
    tp = _tp_size(mesh)
    if _ep_size(mesh) > 1 or (tp > 1 and width % tp == 0):
        return "dense"
    if sorted_wins(rows, top_k, n_experts, share):
        return "sorted"
    return "by_hit" if masked and share == 1.0 else "dense"


def heeds_active(form: str, share: float) -> bool:
    """Whether a decode call of ``form`` drops its idle rows' assignments:
    every call but a chip's share dispatched dense (:func:`moe_ffn`)."""
    return not (share < 1.0 and form == "dense")


def sorted_under(n_experts: int) -> int:
    """The experts-hit count under which a ``by_hit`` call goes sorted
    (:data:`SORTED_UNDER_HIT_SHARE` of the experts, at least 1: a call whose
    rows are all idle hits none and reads none)."""
    return max(1, int(n_experts * SORTED_UNDER_HIT_SHARE))


def _ep_size(mesh) -> int:
    if mesh is None or AXIS_EP not in mesh.axis_names:
        return 1
    return mesh.shape[AXIS_EP]


def _tp_size(mesh) -> int:
    if mesh is None or AXIS_TP not in mesh.axis_names:
        return 1
    return mesh.shape[AXIS_TP]


def _sorted_dispatch(x: jax.Array,            # [B, T, D]
                     wg: jax.Array, wu: jax.Array, wd: jax.Array,
                     vals: jax.Array,          # [B, T, K] renormalized gates
                     idx: jax.Array,           # [B, T, K] expert ids
                     layer: Optional[int] = None,
                     absent: bool = False) -> jax.Array:
    """Exact sorted MoE dispatch: flatten (token, k) assignments, stable-sort
    by expert, run each expert's contiguous group through `lax.ragged_dot`,
    scatter-add the weighted outputs back. No capacity limit, no dropped
    tokens — same math as the dense formulation (summation order aside) —
    at K-per-token FFN cost
    instead of E-per-token. TPU lowers ragged_dot onto the MXU with
    group-size prefetch.

    With ``layer`` the weights are the STACKED [L, E, ...] tensors, seen as
    L * E groups of which only this layer's hold rows: ``ragged_dot`` takes
    its operand as a buffer of its own, so one layer's slice of a stacked
    tensor is copied whole at every call (compiled for a v5e, PR 28: 2.4 GB
    of temporaries for six layers of 128 x 2048 x 768, 13.6 MB this way)."""
    B, T, D = x.shape
    E = wg.shape[-3]
    K = idx.shape[-1]
    N = B * T
    xf = x.reshape(N, D)
    flat_e = idx.reshape(N * K)
    flat_g = vals.reshape(N * K)
    order = jnp.argsort(flat_e, stable=True)           # [N*K]
    tok = order // K                                   # source token per slot
    xs = xf[tok]                                       # [N*K, D]
    # ``absent``: an assignment to an expert this chip does not hold
    # carries id E (moe_ffn): it sorts behind every group, belongs to none
    # and adds nothing
    counts = jnp.zeros((E + absent,), jnp.int32).at[flat_e].add(1)
    if absent:
        counts = counts[:E]
    if layer is not None:
        L = wg.shape[0]
        counts = jnp.zeros((L, E), jnp.int32).at[layer].set(counts).reshape(-1)
        wg, wu, wd = (w.reshape(L * E, *w.shape[2:]) for w in (wg, wu, wd))
    g = jax.lax.ragged_dot(xs, wg, counts)             # [N*K, F]
    u = jax.lax.ragged_dot(xs, wu, counts)
    a = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(x.dtype)
    y = jax.lax.ragged_dot(a, wd, counts)              # [N*K, D]
    y = y.astype(jnp.float32) * flat_g[order][:, None]
    if absent:
        y = jnp.where((flat_e[order] < E)[:, None], y, 0.0)
    out = jnp.zeros((N, D), jnp.float32).at[tok].add(y)
    return out.reshape(B, T, D).astype(x.dtype)


def route_topk(x: jax.Array, wr: jax.Array, top_k: int,
               router: str = "softmax", bias: Optional[jax.Array] = None,
               groups: Optional[Tuple[int, int]] = None,
               scaling: float = 1.0, norm_eps: float = 0.0):
    """Router: top-k gate values + expert ids ([B,T,K] each).
    Shared by every dispatch formulation (incl. forward_pp's in-stage MoE)
    so the gating policy has exactly one implementation. Four laws:
    ``softmax`` (top-k of the softmax over all experts, renormalised),
    ``sigmoid_bias`` (sigmoid scores; the k largest of score + ``bias`` [E],
    the learned selection bias, are chosen; the gates are the chosen SCORES
    over their sum + ``norm_eps`` (LFM2's 1e-6), x ``scaling``: the bias
    chooses and never weighs; ``sigmoid`` is the same law of a model that
    has no bias: the k largest scores among all) and ``softmax_group``
    (softmax scores; the experts lie in ``groups[0]`` equal groups, a group
    scores as its best expert, the ``groups[1]`` best groups stay and the
    top-k is taken among their experts; the gates are the chosen scores x
    ``scaling`` and are NOT renormalised) and ``softmax_bias`` (softmax
    scores over every output of the router, identity experts among them; the
    k largest of score + ``bias`` are chosen; the gates are the chosen
    SCORES x ``scaling``, NOT renormalised: the bias chooses and never
    weighs)."""
    # float32 logits, not only a float32 softmax: bfloat16 resolves a
    # router logit of 32-64 to 0.25, i.e. a gate ratio to 25 %
    logits = jnp.einsum("btd,de->bte", x, wr.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if router in ("sigmoid", "sigmoid_bias"):
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores if bias is None else scores + bias,
                               top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    elif router == "softmax_group":
        probs = jax.nn.softmax(logits, axis=-1)
        G, Gk = groups
        best = probs.reshape(*probs.shape[:-1], G, -1).max(axis=-1)
        _, gi = jax.lax.top_k(best, Gk)                   # [B,T,Gk]
        stay = jnp.sum(jax.nn.one_hot(gi, G, dtype=jnp.int32), axis=-2) > 0
        stay = jnp.repeat(stay, probs.shape[-1] // G, axis=-1)
        # (a softmax score is positive: 0 never beats an expert that stays)
        vals, idx = jax.lax.top_k(jnp.where(stay, probs, 0.0), top_k)
        return vals * scaling, idx
    elif router == "softmax_bias":
        probs = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(probs if bias is None else probs + bias,
                               top_k)
        return jnp.take_along_axis(probs, idx, axis=-1) * scaling, idx
    elif router == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, top_k)           # [B,T,K]
    else:
        raise ValueError(f"no router law {router!r}")
    total = jnp.sum(vals, axis=-1, keepdims=True)
    if norm_eps:
        total = total + norm_eps
    vals = vals / total
    return (vals if scaling == 1.0 else vals * scaling), idx


def dense_gates(vals: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """One-hot gate matrix [B,T,E] for dense dispatch."""
    return jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)
                   * vals[..., None], axis=-2)


def expert_ffn(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
               gates: jax.Array) -> jax.Array:
    """Dense-dispatch expert compute for ONE shard's local experts.
    Shapes per shard: wg/wu [El, D, F], wd [El, F, D], gates [B,T,El].
    Pure per-shard math — safe inside any enclosing shard_map (forward_pp's
    pp x ep stage body psums the result over ep/tp itself)."""
    g = jnp.einsum("btd,edf->btef", x, wg)
    u = jnp.einsum("btd,edf->btef", x, wu)
    a = jax.nn.silu(g) * u
    return jnp.einsum("btef,efd,bte->btd", a, wd, gates.astype(x.dtype))


def moe_ffn(x: jax.Array,           # [B, T, D]
            wr: jax.Array,          # [D, E] router
            wg: jax.Array,          # [E, D, F] expert gate projections
            wu: jax.Array,          # [E, D, F] expert up projections
            wd: jax.Array,          # [E, F, D] expert down projections
            top_k: int,
            mesh=None,
            layer: Optional[int] = None,
            router: str = "softmax",
            bias: Optional[jax.Array] = None,
            first: Optional[int] = None,
            groups: Optional[Tuple[int, int]] = None,
            scaling: float = 1.0,
            shared: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
            norm_eps: float = 0.0,
            active: Optional[jax.Array] = None,
            stats: Optional[Dict[str, Any]] = None,
            zero: int = 0):
    """Routed MoE feed-forward (expert width F is the weights' own: a model
    whose experts are not ``intermediate_size`` wide needs nothing here).
    With ``layer``, ``wg`` / ``wu`` / ``wd`` are the stacked [L, E, ...]
    tensors and ``layer`` picks this call's (see :func:`_sorted_dispatch`).
    Returns ([B, T, D] in x.dtype, experts hit: int32 scalar, the experts
    that at least one row of this call was routed to, and the chosen expert
    ids [B, T, K]).

    A chip's SHARE of the experts (``first`` given): ``wr`` is as wide as
    the deployment's router, ``wg`` / ``wu`` / ``wd`` hold experts ``first
    .. first + E - 1`` of it. The router chooses among all and the gates
    are normalised over all ``top_k`` chosen, held here or not; an
    assignment to an absent expert is dropped before dispatch, and the
    result is the held experts' part of the layer's output: what the
    absent ones would add is left out, not stood in for. The second result
    is then (experts hit among the HELD, assignments to held experts), and
    the chosen ids stay the router's own.

    ``shared`` = (wg [D, Fs], wu [D, Fs], wd [Fs, D]): an expert EVERY token
    passes through, added to the routed sum whole (under a chip's share
    too: every chip of the deployment computes it alike, and it counts once
    when the shares are added up).

    ``active`` [B] bool (a DECODE step: which rows are lanes the dispatch
    serves): an idle row's assignments become absent ones (expert id E, gate
    0) before dispatch, so a sorted call reads the busy rows' experts alone,
    and experts hit and held assignments count the busy rows alone; the busy
    rows' results are what they were, an idle row's is the shared expert's
    part at most and is never read. A call that holds both forms
    (:func:`dispatch_form` ``by_hit``) chooses by that count on the device.
    A chip's share that the rule sends dense takes no notice of ``active``
    (its crossing is another one; ROADMAP ``held-experts-hit``). With
    ``stats`` such a call adds 1 to ``stats["sorted"]`` if it was dispatched
    sorted.

    ``zero``: the LAST ``zero`` outputs of the router are IDENTITY experts
    (ids >= ``wr.shape[1] - zero``): experts without weights, whose part of
    the result is gate x input. Such an assignment is never dispatched: its
    gate goes into one scalar a token and scalar x ``x`` is added to the
    routed sum, WHOLE under a chip's share (no chip holds them, every chip
    of the deployment computes them for its own tokens, and they count once
    when the shares are added up) and masked by ``active`` like every other
    assignment of an idle row, whatever the dispatch form. ``first`` then
    counts among the routed experts alone (0 where none is given) and the
    second result is the share's pair. With ``stats`` the call adds its
    identity assignments (busy rows') to ``stats["zero"]``."""
    with jax.named_scope("dynamo.moe_ffn"):
        vals, idx = route_topk(x, wr, top_k, router, bias, groups, scaling,
                               norm_eps)
        E = wg.shape[-3]
        if zero and first is None:
            first = 0
        share = 1.0 if first is None else E / wr.shape[1]
        form = dispatch_form(x.shape[0] * x.shape[1], top_k, E, share, mesh,
                             wg.shape[-1], masked=active is not None)
        masked = active is not None and heeds_active(form, share)
        busy = active[:, None, None] if masked else None
        if first is not None:
            held = (idx >= first) & (idx < first + E)
            if masked:
                held = held & busy
            n_held = jnp.sum(held.astype(jnp.int32))
            keep = held
        else:
            keep = busy
        # an assignment that is not kept (held elsewhere, an idle row's) is
        # an ABSENT one: expert id E, gate 0
        absent = keep is not None
        local = idx if not absent else jnp.where(
            keep, idx if first is None else idx - first, E)
        n_hit = jnp.zeros((E + absent,), jnp.int32).at[
            local.reshape(-1)].max(1)
        n_hit = jnp.sum(n_hit[:E] if absent else n_hit)
        hit = n_hit if first is None else (n_hit, n_held)
        gates = vals if not absent else jnp.where(keep, vals, 0.0)
        out, took_sorted = _dispatch(x, wg, wu, wd, gates, local, mesh, layer,
                                     form, absent, n_hit)
        if active is not None and stats is not None:
            stats["sorted"] = stats.get("sorted", 0) + took_sorted
        if shared is not None:
            out = out + shared_ffn(x, *shared)
        if zero:
            ident = idx >= wr.shape[1] - zero
            if active is not None:
                ident = ident & active[:, None, None]
            gate = jnp.sum(jnp.where(ident, vals, 0.0), axis=-1, keepdims=True)
            out = (out.astype(jnp.float32)
                   + gate * x.astype(jnp.float32)).astype(x.dtype)
            if stats is not None:
                stats["zero"] = stats.get("zero", 0) + jnp.sum(
                    ident.astype(jnp.int32))
        return out, hit, idx


def shared_ffn(x: jax.Array, sg: jax.Array, su: jax.Array, sd: jax.Array,
               scale: Optional[float] = None) -> jax.Array:
    """The experts EVERY token passes through, as one SwiGLU of their joint
    width: ``sg`` / ``su`` [D, Fs], ``sd`` [Fs, D]. ``scale`` multiplies the
    result: 1 / n for a model that AVERAGES its n shared experts (the sum of
    the n experts' outputs is what the joint down-projection gives)."""
    a = jax.nn.silu(jnp.einsum("btd,df->btf", x, sg)) * jnp.einsum(
        "btd,df->btf", x, su)
    y = jnp.einsum("btf,fd->btd", a, sd)
    return y if scale is None else y * jnp.asarray(scale, y.dtype)


def moe_ffn_in_stage(x: jax.Array, wr: jax.Array, wg: jax.Array,
                     wu: jax.Array, wd: jax.Array, top_k: int,
                     ep: int = 1, psum_axes=()) -> jax.Array:
    """The same routing and expert mathematics for a caller ALREADY inside
    manual SPMD (``forward_pp``'s pp x tp x ep stage body; shard_maps do not
    nest): ``wg`` / ``wu`` / ``wd`` are this shard's local experts, dense
    dispatch, the gates of non-local experts are zero on each shard, and one
    ``psum`` over ``psum_axes`` combines them exactly."""
    E = wr.shape[1]
    vals, idx = route_topk(x, wr, top_k)
    gates = dense_gates(vals, idx, E)                     # [B, T, E]
    if ep > 1:
        El = E // ep
        gates = jax.lax.dynamic_slice_in_dim(
            gates, jax.lax.axis_index(AXIS_EP) * El, El, axis=2)
    y = expert_ffn(x, wg, wu, wd, gates)
    return jax.lax.psum(y, psum_axes) if psum_axes else y


def _dispatch(x, wg, wu, wd, vals, idx, mesh, layer, form, absent, n_hit):
    """-> (the routed sum, 1 if the call was dispatched sorted else 0).
    ``form``: :func:`dispatch_form` of the call; ``absent``: ``idx`` may
    hold E, an assignment that belongs to no expert here (a chip's share,
    an idle row); ``n_hit``: the experts the call's assignments hit, what a
    ``by_hit`` call chooses by."""
    if form == "sorted":
        return _sorted_dispatch(x, wg, wu, wd, vals, idx, layer, absent), 1
    if form == "by_hit":
        under = n_hit < sorted_under(wg.shape[-3])
        return jax.lax.cond(
            under,
            lambda: _sorted_dispatch(x, wg, wu, wd, vals, idx, layer, absent),
            lambda: _dense_dispatch(x, wg, wu, wd, vals, idx, mesh, layer)
        ), under.astype(jnp.int32)
    return _dense_dispatch(x, wg, wu, wd, vals, idx, mesh, layer), 0


def _dense_dispatch(x, wg, wu, wd, vals, idx, mesh, layer=None):
    """Every local expert sees every row (an assignment to expert id E, an
    absent one, has no column in the gates and adds nothing)."""
    E = wg.shape[-3]

    ep = _ep_size(mesh)
    tp = _tp_size(mesh)
    F = wg.shape[-1]
    tp_ffn = tp if tp > 1 and F % tp == 0 else 1
    if layer is not None:
        # a layer's slice of the stacked tensor is free for an einsum
        wg, wu, wd = wg[layer], wu[layer], wd[layer]

    # dense dispatch consumes the one-hot gates tensor; only built where used
    gates = dense_gates(vals, idx, E)                     # [B,T,E]
    experts = expert_ffn

    if ep <= 1 and tp_ffn <= 1:
        return experts(x, wg, wu, wd, gates)

    # expert dim shards over ep; the FFN intermediate dim additionally
    # shards over tp (each shard computes an F/tp slice of its local
    # experts — the down-projection contraction leaves partial sums, so
    # the combine is one psum over BOTH axes)
    axes = tuple(a for a, n in ((AXIS_EP, ep), (AXIS_TP, tp_ffn)) if n > 1)

    def local(x, wg, wu, wd, gates):
        y = experts(x, wg, wu, wd, gates)
        return jax.lax.psum(y, axes)

    ftp = AXIS_TP if tp_ffn > 1 else None
    eax = AXIS_EP if ep > 1 else None
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None),
                  P(eax, None, ftp), P(eax, None, ftp), P(eax, ftp, None),
                  P(None, None, eax)),
        out_specs=P(None, None, None),
        check_vma=False,
    )(x, wg, wu, wd, gates)
