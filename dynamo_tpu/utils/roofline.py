"""Roofline accounting: how close is each dispatch to the hardware?

Three pieces, consumed by the engine's goodput telemetry
(``dyn_mfu`` / ``dyn_mbu`` / ``dyn_hbm_gbps``):

1. **Peaks** — per-platform peak dense bf16 FLOP/s and HBM bandwidth.
   TPU generations come from a static table; off-chip (CPU) the peaks are
   *calibrated once* with a short matmul / memcpy measurement so MFU/MBU
   stay meaningful rather than reading 0.0001 against an imaginary chip.
   ``DYN_PEAK_FLOPS`` / ``DYN_PEAK_GBPS`` override everything (deployments
   that know their part better than the table).

2. **Analytic cost model** — FLOPs and HBM bytes of one engine dispatch,
   computed from the model config and the dispatch's actual lane lengths.
   Matmul FLOPs count dense projections + MLP (active experts only for
   MoE) + the LM head where the program really computes it; attention
   score/value FLOPs and KV reads are **window-clamped** on sliding-window
   layers (a Gemma-style 5:1 sliding stack reads a fraction of the KV a
   full-attention stack would). Bytes = weights streamed once per
   sequential step + KV read/written. Activations and padding lanes are
   deliberately excluded: the numbers are *useful* work, so bucket padding
   shows up as lost MFU instead of being flattered away.

3. :class:`GoodputMeter` — accumulates (flops, bytes, busy-time) per
   dispatch and answers with windowed MFU / MBU / achieved-GB/s rates plus
   lifetime totals.

The model is an estimate, not a profiler: it exists so "are we 4% or 40%
of the chip" is answerable from /metrics on every deployment, and so the
bench artifacts can never again ship ``mfu: null``.
"""

from __future__ import annotations

import collections
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

# device_kind substring -> (peak dense bf16 FLOP/s, peak HBM bytes/s) per
# chip — THE peak table; bandwidth
# from the public chip datasheets (v5e 819 GB/s, v5p 2765, v6e 1640,
# v4 1228).
PEAKS_BY_DEVICE_KIND: Tuple[Tuple[str, float, float], ...] = (
    ("v6", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v5lite", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
)


@dataclass(frozen=True)
class Peaks:
    """What the attached hardware could theoretically sustain."""

    flops: float          # dense bf16 FLOP/s
    hbm_bytes: float      # main-memory bytes/s
    source: str           # "table:<kind>" | "calibrated-cpu" | "env"


def _env_peaks() -> Optional[Peaks]:
    f = os.environ.get("DYN_PEAK_FLOPS")
    b = os.environ.get("DYN_PEAK_GBPS")
    if not (f and b):
        return None
    try:
        return Peaks(float(f), float(b) * 1e9, "env")
    except ValueError:
        return None


def _calibrate_cpu() -> Peaks:
    """Measure this host once: matmul FLOP/s (BLAS) and memcpy bandwidth.

    Deliberately short (~tens of ms): the point is a denominator within
    ~2x of the truth, so CPU MFU/MBU read as real percentages instead of
    noise against a TPU peak. Best-of-N to shave scheduler jitter."""
    import numpy as np

    n = 384
    a = np.random.default_rng(0).standard_normal((n, n), dtype=np.float32)
    b = np.ascontiguousarray(a.T)
    a @ b                                    # warm the BLAS threads
    flops = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        a @ b
        dt = time.perf_counter() - t0
        flops = max(flops, 2.0 * n * n * n / max(dt, 1e-9))
    src = np.zeros(32 << 20, dtype=np.uint8)  # 32 MiB: past typical LLC
    dst = np.empty_like(src)
    bw = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        dt = time.perf_counter() - t0
        # a copy moves 2x the buffer (read + write)
        bw = max(bw, 2.0 * src.nbytes / max(dt, 1e-9))
    return Peaks(flops, bw, "calibrated-cpu")


_CAL_CACHE: Dict[str, Peaks] = {}


def detect_peaks(device_kind: Optional[str] = None,
                 platform: Optional[str] = None) -> Peaks:
    """Peaks for the attached accelerator. ``device_kind``/``platform``
    default to jax's first device; passing them explicitly keeps this
    importable (and testable) without touching a backend.

    An accelerator whose ``device_kind`` is not in
    :data:`PEAKS_BY_DEVICE_KIND` raises ``ValueError`` — a device missing
    from the table is an error, never a default (set ``DYN_PEAK_FLOPS`` /
    ``DYN_PEAK_GBPS`` or add the row). Only ``platform == "cpu"`` takes the
    calibrated host measurement."""
    env = _env_peaks()
    if env is not None:
        return env
    if device_kind is None or platform is None:
        import jax

        d = jax.devices()[0]
        device_kind, platform = d.device_kind, d.platform
    if platform != "cpu":
        k = device_kind.lower()
        for sub, pf, pb in PEAKS_BY_DEVICE_KIND:
            if sub in k:
                return Peaks(pf, pb, f"table:{sub}")
        raise ValueError(
            f"no peak FLOP/s / bandwidth known for device_kind "
            f"{device_kind!r} (platform {platform!r}): add it to "
            f"roofline.PEAKS_BY_DEVICE_KIND or set DYN_PEAK_FLOPS and "
            f"DYN_PEAK_GBPS")
    if "cpu" not in _CAL_CACHE:
        _CAL_CACHE["cpu"] = _calibrate_cpu()
    return _CAL_CACHE["cpu"]


# ---------------------------------------------------------------------------
# analytic dispatch cost model
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelCosts:
    """Per-config constants the dispatch cost functions combine.

    ``window_groups`` collapses the layer stack into ``(window, count)``
    groups — ``None`` = full attention — so the per-token clamped-length
    sum is O(distinct windows), not O(layers), on the engine hot path.
    All FLOP counts use 2 FLOPs per MAC."""

    mat_flops_per_token: float   # dense projections + (active-expert) MLP
    lm_head_flops: float         # 2 * D * V, charged where the head runs
    attn_flops_coef: float       # 4 * Hq * Dh: score+value FLOPs per kv pos
    kv_bytes_per_tok_layer: float  # 2 (k+v) * Hkv * Dh * esize
    num_layers: int
    window_groups: Tuple[Tuple[Optional[int], int], ...]
    weight_bytes: float          # total param bytes streamed per step
    # learned top-k attention (0 = none): a query attends to min(visible,
    # topk) keys, and scores EVERY visible key first (2 * Hi * Di FLOPs and
    # one index key of Di * esize bytes per visible position and layer)
    index_topk: int = 0
    index_flops_coef: float = 0.0
    index_bytes_per_tok_layer: float = 0.0
    # a per-kind model (window and full layers with head counts and caches
    # of their own): K/V bytes a token a layer of each of ``window_groups``,
    # in its order (empty: ``kv_bytes_per_tok_layer`` for every layer)
    group_kv_bytes: Tuple[float, ...] = ()
    # state-space layers: the bytes a LANE holds in them (recurrent state
    # and convolution tail, all such layers), read and written once by a
    # dispatch that serves the lane, and the recurrence's FLOPs a token
    state_bytes_per_lane: float = 0.0
    state_flops_per_token: float = 0.0

    def kv_bytes_of(self, i: int) -> float:
        return (self.group_kv_bytes[i] if self.group_kv_bytes
                else self.kv_bytes_per_tok_layer)

    @property
    def kv_write_bytes_per_token(self) -> float:
        """K/V bytes one new token writes, all layers."""
        return float(sum(n * self.kv_bytes_of(i)
                         for i, (_, n) in enumerate(self.window_groups)))


def dtype_size(dtype: Any) -> int:
    import numpy as np

    try:
        import jax.numpy as jnp

        return int(np.dtype(jnp.zeros((), dtype).dtype).itemsize)
    # dynalint: ok(swallowed-exception) jax-dtype probe falling back to
    # the numpy interpretation IS the handling; both paths return a size
    except Exception:
        return int(np.dtype(dtype).itemsize)


def model_costs(m: Any, weight_bytes: Optional[float] = None) -> ModelCosts:
    """Build :class:`ModelCosts` from a ``LlamaConfig``-shaped object.
    ``weight_bytes`` overrides the analytic parameter count with the exact
    loaded size when the caller has it (the engine does)."""
    D, V = m.hidden_size, m.vocab_size
    Hq, Hkv, Dh = m.num_heads, m.num_kv_heads, m.head_dim
    L, I = m.num_layers, m.intermediate_size
    esize = dtype_size(m.dtype)
    if getattr(m, "has_state", False):
        return _state_costs(m, weight_bytes, esize)
    if getattr(m, "has_latent", False):
        return _latent_costs(m, weight_bytes, esize)
    if getattr(m, "per_kind", False):
        return _per_kind_costs(m, weight_bytes, esize)
    Dv = getattr(m, "v_dim", Dh)
    attn_proj = D * Hq * Dh + D * Hkv * (Dh + Dv) + Hq * Dv * D
    topk = getattr(m, "index_topk", 0)
    Hi, Di = getattr(m, "index_heads", 0), getattr(m, "index_head_dim", 0)
    if topk:
        attn_proj += D * (Hi * Di + Di + Hi)
    if getattr(m, "num_experts", 0):
        # the ACTIVE experts at their own width, and the router
        I = getattr(m, "expert_width", I)
        mlp_active = m.experts_per_token * 3 * D * I + D * m.num_experts
        mlp_weights = m.num_experts * (3 * D * I + D)
    else:
        mlp_active = mlp_weights = 3 * D * I
    if weight_bytes is None:
        n_params = V * D + L * (attn_proj + mlp_weights)
        if not getattr(m, "tie_embeddings", False):
            n_params += D * V
        weight_bytes = float(n_params) * esize
    groups: Dict[Optional[int], int] = {}
    for layer in range(L):
        w = m.sliding_window if m.layer_sliding(layer) else None
        groups[w] = groups.get(w, 0) + 1
    return ModelCosts(
        mat_flops_per_token=2.0 * L * (attn_proj + mlp_active),
        lm_head_flops=2.0 * D * V,
        attn_flops_coef=2.0 * Hq * (Dh + Dv),
        kv_bytes_per_tok_layer=float(Hkv * (Dh + Dv) * esize),
        num_layers=L,
        window_groups=tuple(sorted(groups.items(),
                                   key=lambda kv: (kv[0] is None, kv[0]))),
        weight_bytes=float(weight_bytes),
        index_topk=int(topk),
        index_flops_coef=2.0 * Hi * Di,
        index_bytes_per_tok_layer=float(Di * esize),
    )


def _per_kind_costs(m: Any, weight_bytes: Optional[float],
                    esize: int) -> ModelCosts:
    """:class:`ModelCosts` of a model described layer by layer: attention
    projections and cache bytes by attention kind, the feed-forward by its
    kind; of routed experts the part a token's assignments compute HERE
    (``experts_per_token`` x the chip's share of the router's experts)."""
    D, V, Hq, Dh, Dv = (m.hidden_size, m.vocab_size, m.num_heads,
                        m.head_dim, m.v_dim)
    Fe = m.expert_width
    R = m.router_experts or m.num_experts
    share = m.num_experts / R
    from ..engine.cache import cache_kinds

    mat = n_params = 0.0
    groups, kv = [], []
    # window layers first, as the one-law model's groups sort
    for kind in sorted(cache_kinds(m), key=lambda k: k.window is None):
        proj = (D * Hq * Dh + D * kind.kv_heads * (Dh + Dv) + Hq * Dv * D)
        mat += kind.layers * proj
        n_params += kind.layers * proj
        groups.append((kind.window, kind.layers))
        kv.append(float(kind.token_bytes(esize) // kind.layers))
    for l in range(m.num_layers):
        if m.layer_routed(l):
            mat += m.experts_per_token * share * 3 * D * Fe + D * R
            n_params += m.num_experts * 3 * D * Fe + D * R
        else:
            mat += 3 * D * m.intermediate_size
            n_params += 3 * D * m.intermediate_size
    if weight_bytes is None:
        n_params += V * D * (1 if m.tie_embeddings else 2)
        weight_bytes = n_params * esize
    return ModelCosts(
        mat_flops_per_token=2.0 * mat, lm_head_flops=2.0 * D * V,
        attn_flops_coef=2.0 * Hq * (Dh + Dv),
        kv_bytes_per_tok_layer=kv[1], num_layers=m.num_layers,
        window_groups=tuple(groups), weight_bytes=float(weight_bytes),
        group_kv_bytes=tuple(kv))


def _latent_costs(m: Any, weight_bytes: Optional[float],
                  esize: int) -> ModelCosts:
    """:class:`ModelCosts` of a model with latent attention, priced as it is
    served: the low-rank projections (q through ``q_lora_rank``, the
    compressed vector and the shared rotary key, both halves of the
    expansion: ``w_uk`` absorbs q, ``w_uv`` expands the heads' sums, each a
    query token's work) and ``wo``; a (query, key) pair in the absorbed
    form, 2 x Hq x (Rkv + rope + Rkv); the ONE row a token a layer the cache
    holds; of the feed-forward the dense layers, and for a routed layer the
    router, the shared expert (whole) and the chip's share of a token's
    assignments; a routed branch BESIDE a dense feed-forward
    (``layer_branch``) likewise, on top of the dense one, its identity
    experts' share of the assignments at D multiply-adds each and no
    weights."""
    from ..engine.cache import cache_kinds

    D, V, Hq = m.hidden_size, m.vocab_size, m.num_heads
    Rq, Rkv, Dn, Dr, Dv = (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_dim,
                           m.qk_rope_dim, m.v_dim)
    kind, = cache_kinds(m)
    Fe = m.expert_width
    R = m.router_width
    share, zero = (m.num_experts / R, m.zero_experts / R) if R else (0.0, 0.0)
    proj = (D * Rq + Rq * Hq * (Dn + Dr) + D * (Rkv + Dr)
            + Rkv * Hq * (Dn + Dv) + Hq * Dv * D)
    mat = n_params = float(m.num_layers * proj)
    for l in range(m.num_layers):
        beside = m.layer_branch(l)
        if m.layer_routed(l) or beside:
            every = D * R + 3 * D * m.shared_experts * Fe
            mat += (m.experts_per_token * (share * 3 * D * Fe + zero * D)
                    + every)
            n_params += m.num_experts * 3 * D * Fe + every
        if beside or not m.layer_routed(l):
            mat += 3 * D * m.intermediate_size
            n_params += 3 * D * m.intermediate_size
    if weight_bytes is None:
        n_params += V * D * (1 if m.tie_embeddings else 2)
        weight_bytes = n_params * esize
    return ModelCosts(
        mat_flops_per_token=2.0 * mat, lm_head_flops=2.0 * D * V,
        attn_flops_coef=2.0 * Hq * (2 * Rkv + Dr),
        kv_bytes_per_tok_layer=float(kind.token_bytes(esize) // kind.layers),
        num_layers=m.num_layers, window_groups=((None, m.num_layers),),
        weight_bytes=float(weight_bytes))


def _state_costs(m: Any, weight_bytes: Optional[float],
                 esize: int) -> ModelCosts:
    """:class:`ModelCosts` of a model with state-space or gated
    short-convolution layers: K/V and attention FLOPs of its attention
    layers alone; the mixers' two projections and every layer's
    feed-forward (dense, or a token's routed experts and the router) among
    the matrix FLOPs; the recurrence (a state-space mixer: state update and
    read-out, 4 FLOPs a state element a token; a short convolution: two
    gates and the taps, 8 a channel at 3 taps) and the per-lane state bytes
    by ``CacheKind``'s description (a tail alone for the latter)."""
    from ..engine.cache import cache_kinds

    D, V, Hq, Dh = m.hidden_size, m.vocab_size, m.num_heads, m.head_dim
    glob, state = cache_kinds(m)
    attn = D * Hq * Dh + 2 * D * glob.kv_heads * Dh + Hq * Dh * D
    if getattr(m, "has_conv", False):
        # a gated short convolution: [B | C | z] in, W_out back, the taps;
        # the recurrence is two gates and ``conv_cache`` multiply-adds a
        # channel a token, and a lane holds its tail alone
        mix = D * 3 * D + D * D + m.conv_cache * D
        rec = (2.0 + 2.0 * m.conv_cache) * state.layers * D
    else:
        I, Cd = m.ssm_inner, m.ssm_conv_dim
        mix = D * (I + Cd + m.ssm_heads) + I * D
        rec = 4.0 * state.layers * I * m.ssm_state
    # the feed-forward by its kind: a token computes its assignments'
    # experts and the router; the weights hold every expert
    dense = 3 * D * m.intermediate_size
    routed = m.routed_layers
    Fe = m.expert_width
    ffn_active = ((m.num_layers - routed) * dense + routed * (
        m.experts_per_token * 3 * D * Fe + D * m.num_experts))
    ffn_held = ((m.num_layers - routed) * dense + routed * m.num_experts * (
        3 * D * Fe + D))
    mat = glob.layers * attn + state.layers * mix
    if weight_bytes is None:
        weight_bytes = (mat + ffn_held
                        + V * D * (1 if m.tie_embeddings else 2)) * esize
    return ModelCosts(
        mat_flops_per_token=2.0 * (mat + ffn_active),
        lm_head_flops=2.0 * D * V,
        attn_flops_coef=4.0 * Hq * Dh,
        kv_bytes_per_tok_layer=float(glob.token_bytes(esize) // glob.layers),
        num_layers=glob.layers, window_groups=((None, glob.layers),),
        weight_bytes=float(weight_bytes),
        state_bytes_per_lane=float(state.lane_bytes(esize)),
        state_flops_per_token=rec)


def _clamped_len_sum(groups: Sequence[Tuple[Optional[int], int]],
                     s: int, topk: int = 0) -> float:
    """sum over layers of min(s, window): the kv positions one query token
    at kv-length ``s`` actually touches across the layer stack (``topk``:
    a model with an indexer attends to its selected keys only)."""
    if topk:
        s = min(s, topk)
    return float(sum((min(s, w) if w is not None else s) * n
                     for w, n in groups))


def _attn_cost(c: ModelCosts, s: int) -> Tuple[float, float]:
    """(flops, kv bytes read) of ONE query at kv-length ``s`` across the
    layer stack: attention over the keys it attends to, plus — for a model
    with an indexer — the index scores of every visible key."""
    touched = _clamped_len_sum(c.window_groups, s, c.index_topk)
    flops = c.attn_flops_coef * touched
    if c.group_kv_bytes:
        read = float(sum((min(s, w) if w is not None else s) * n * b
                         for (w, n), b in zip(c.window_groups,
                                              c.group_kv_bytes)))
    else:
        read = touched * c.kv_bytes_per_tok_layer
    if c.index_topk:
        flops += c.index_flops_coef * s * c.num_layers
        read += c.index_bytes_per_tok_layer * s * c.num_layers
    return flops, read


def decode_cost(c: ModelCosts, lengths: Iterable[int], steps: int
                ) -> Tuple[float, float, int]:
    """(flops, bytes, tokens) of a multi-step decode dispatch: ``steps``
    scan iterations over the given per-lane kv lengths (active lanes only).
    Weights stream once per scan step; every token computes the LM head."""
    flops = 0.0
    kv_read = 0.0
    lanes = 0
    for s0 in lengths:
        lanes += 1
        for j in range(steps):
            fl, rd = _attn_cost(c, s0 + j)
            flops += (c.mat_flops_per_token + c.lm_head_flops + fl
                      + c.state_flops_per_token)
            kv_read += rd
    tokens = lanes * steps
    bytes_ = (steps * c.weight_bytes + kv_read
              + tokens * c.kv_write_bytes_per_token
              + 2 * lanes * c.state_bytes_per_lane)
    return flops, bytes_, tokens


def prefill_cost(c: ModelCosts, spans: Iterable[Tuple[int, int]]
                 ) -> Tuple[float, float, int]:
    """(flops, bytes, tokens) of one batched prefill dispatch over
    ``(start, count)`` prompt spans (per active lane). The program computes
    the LM head once per lane (at ``logits_idx``) regardless of whether the
    host keeps the sample, so it is charged once per lane."""
    flops = 0.0
    kv_read = 0.0
    tokens = 0
    lanes = 0
    for start, count in spans:
        tokens += count
        lanes += 1
        flops += (count * (c.mat_flops_per_token + c.state_flops_per_token)
                  + c.lm_head_flops)
        for p in range(start, start + count):
            fl, rd = _attn_cost(c, p + 1)
            flops += fl
            kv_read += rd
    bytes_ = (c.weight_bytes + kv_read
              + tokens * c.kv_write_bytes_per_token
              + 2 * lanes * c.state_bytes_per_lane)
    return flops, bytes_, tokens


def verify_cost(c: ModelCosts, lengths: Iterable[int], t: int
                ) -> Tuple[float, float, int]:
    """(flops, bytes, tokens) of a speculative verify dispatch: ONE forward
    over ``t = k+1`` positions per active lane, LM head at every position
    (the verify sampler consumes all of them)."""
    flops = 0.0
    kv_read = 0.0
    lanes = 0
    for s0 in lengths:
        lanes += 1
        for j in range(t):
            fl, rd = _attn_cost(c, s0 + j)
            flops += c.mat_flops_per_token + c.lm_head_flops + fl
            kv_read += rd
    tokens = lanes * t
    bytes_ = (c.weight_bytes + kv_read
              + tokens * c.kv_write_bytes_per_token)
    return flops, bytes_, tokens


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------
class GoodputMeter:
    """Accumulates dispatch costs and answers utilization questions.

    ``account()`` is called once per *measured* dispatch (dispatch-to-host-
    results wall time; pipelined decode deliberately overlaps, same as the
    ``llm_decode_step_seconds`` convention). ``snapshot()`` rates over a
    sliding window of recent dispatches — what the live gauges and
    ForwardPassMetrics export; ``lifetime()`` over every accounted dispatch
    — what bench artifacts record. First-call-per-program compile time must
    NOT be accounted here (the engine routes it to the compile counters
    instead), or one XLA compile would crater the window's MFU."""

    def __init__(self, costs: ModelCosts, peaks: Peaks,
                 window_s: float = 10.0):
        import threading

        self.costs = costs
        self.peaks = peaks
        self.window_s = window_s
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.busy_s_total = 0.0
        self.tokens_total = 0
        self.dispatches = 0
        self._recent: collections.deque = collections.deque()
        # account() runs on the engine thread; snapshot()/lifetime() on the
        # asyncio metrics loop — iterating the deque mid-append raises and
        # would kill the caller's loop, so every touch takes this lock
        self._lock = threading.Lock()

    def account(self, flops: float, bytes_: float, elapsed_s: float,
                tokens: int = 0) -> None:
        if elapsed_s <= 0:
            return
        now = time.monotonic()
        with self._lock:
            self.flops_total += flops
            self.bytes_total += bytes_
            self.busy_s_total += elapsed_s
            self.tokens_total += tokens
            self.dispatches += 1
            self._recent.append((now, flops, bytes_, elapsed_s))
            cutoff = now - self.window_s
            while self._recent and self._recent[0][0] < cutoff:
                self._recent.popleft()

    def _rates(self, flops: float, bytes_: float, busy: float
               ) -> Dict[str, float]:
        if busy <= 0:
            return {"mfu": 0.0, "mbu": 0.0, "hbm_gbps": 0.0}
        return {
            "mfu": flops / busy / self.peaks.flops,
            "mbu": bytes_ / busy / self.peaks.hbm_bytes,
            "hbm_gbps": bytes_ / busy / 1e9,
        }

    def snapshot(self) -> Dict[str, float]:
        """MFU/MBU/GB/s over the recent window (0.0 when idle)."""
        cutoff = time.monotonic() - self.window_s
        f = b = t = 0.0
        with self._lock:
            recent = list(self._recent)
        for ts, fl, by, el in recent:
            if ts >= cutoff:
                f += fl
                b += by
                t += el
        return self._rates(f, b, t)

    def lifetime(self) -> Dict[str, float]:
        """Cumulative utilization over every accounted dispatch, plus the
        raw totals (bench artifacts embed these)."""
        with self._lock:
            totals = (self.flops_total, self.bytes_total, self.busy_s_total,
                      self.tokens_total, self.dispatches)
        out = self._rates(totals[0], totals[1], totals[2])
        out.update(flops_total=totals[0],
                   bytes_total=totals[1],
                   busy_s=totals[2],
                   tokens=float(totals[3]),
                   dispatches=float(totals[4]),
                   peak_flops=self.peaks.flops,
                   peak_hbm_gbps=self.peaks.hbm_bytes / 1e9,
                   peak_source=self.peaks.source)
        return out


def record_compile(kind: str, seconds: float) -> None:
    """Fold one program build into the process compile-plane counters
    (``dyn_compile_seconds_total`` / ``dyn_compiled_programs{kind}``)."""
    from .prometheus import stage_metrics

    sm = stage_metrics()
    sm.compile_seconds.inc(kind, amount=seconds)
    sm.compiled_programs.inc(kind)


_xla_compiles_counted = False


def count_xla_compiles() -> None:
    """Count every XLA compile of this process, not only the bucket
    programs ``record_compile`` sees: ``jax.monitoring`` listeners feed
    ``dyn_xla_compiles_total`` / ``dyn_xla_compile_seconds_total``. JAX
    reports ``backend_compile_duration`` around "compile or load from the
    persistent cache", with a ``cache_hits`` event first on the same thread
    when it was a load: a load is not counted. Registered once per process,
    where an engine is built; listeners cannot be taken back singly."""
    global _xla_compiles_counted
    if _xla_compiles_counted:
        return
    _xla_compiles_counted = True
    import threading

    import jax.monitoring

    from .prometheus import stage_metrics

    served_from_cache = threading.local()

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            served_from_cache.hit = True

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if getattr(served_from_cache, "hit", False):
            served_from_cache.hit = False
            return
        sm = stage_metrics()
        sm.xla_compiles.inc()
        sm.xla_compile_seconds.inc(amount=seconds)

    # a process that has compiled nothing says 0, not nothing: a reader of
    # two scrapes has to tell "no compile" from "no such counter"
    for counter in (stage_metrics().xla_compiles,
                    stage_metrics().xla_compile_seconds):
        counter.inc(amount=0.0)
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def instrument_compile(kind: str, fn: Callable,
                       on_compile: Callable[[str, float], None]) -> Callable:
    """Wrap a freshly-built jitted program so its FIRST call — the one that
    traces and XLA-compiles synchronously before launching — is timed and
    reported via ``on_compile(kind, seconds)``. Later calls pass through
    untouched. This is how ``dyn_compile_seconds_total`` /
    ``dyn_compiled_programs`` see warmup AND mid-serving bucket compiles
    without instrumenting every dispatch site. The jitted program itself
    stays reachable as ``wrapper.jitted`` (``.lower(...).compile()`` for
    ``memory_analysis()`` / compiled text)."""
    state = {"first": True}

    def wrapper(*args, **kwargs):
        if state["first"]:
            state["first"] = False
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            on_compile(kind, time.perf_counter() - t0)
            return out
        return fn(*args, **kwargs)

    wrapper.jitted = fn
    return wrapper
