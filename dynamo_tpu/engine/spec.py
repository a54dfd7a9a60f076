"""Speculative decoding for the JAX engine: pluggable draft proposers.

Decode is memory-bandwidth-bound — every step streams the whole weight set
through HBM to emit one token per lane. Speculative decoding drafts k cheap
candidate tokens per lane and verifies all of them in ONE wider forward pass
(`EngineCore._verify_fn`), so each dispatch can commit up to k+1 tokens
instead of one. This module owns the host side of that subsystem:

- :class:`NgramProposer` — prompt-lookup / self-speculation: the draft for
  the next k tokens is the continuation of the most recent earlier
  occurrence of the current suffix n-gram within the request's own
  prompt+generated tokens. No extra weights; the right default for a
  serving framework (strong on code, JSON, extraction, multi-turn chat).
- :class:`DraftModelProposer` — a second, smaller model loaded alongside
  (sharing the tokenizer) that greedily drafts k tokens against its own
  private paged KV pool. Optional; single-process deployments only.

Acceptance (greedy exact-match; rejection sampling for temperature>0) lives
in :mod:`.sampling` (``spec_verify``/``spec_accept``); the verify program
and scheduling live in :mod:`.engine`. Rejected tokens roll back by simply
never being accounted: pages are reserved ahead, block hashes seal only
over accepted tokens, and the next dispatch overwrites the stale KV slots
(the same write-then-read contract single-token decode already relies on).

Env knobs (all overridable per-engine via ``JaxEngineConfig``):

- ``DYN_SPEC``            "" (off, default) | ``ngram`` | ``draft``
- ``DYN_SPEC_K``          max draft tokens per lane per dispatch (default 4)
- ``DYN_SPEC_K_MIN``      adaptive-k floor (default 1)
- ``DYN_SPEC_ADAPT``      per-lane adaptive k on/off (default 1)
- ``DYN_SPEC_NGRAM_MAX``  longest suffix n-gram to look up (default 3)
- ``DYN_SPEC_NGRAM_MIN``  shortest suffix n-gram to fall back to (default 1)
- ``DYN_SPEC_DRAFT``      draft model: a preset name or checkpoint dir
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

log = logging.getLogger("dynamo_tpu.engine.spec")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        log.warning("invalid %s=%r; using %d", name, os.environ.get(name),
                    default)
        return default


@dataclass
class SpecConfig:
    """Resolved speculative-decoding configuration (spec is ON)."""

    mode: str                   # "ngram" | "draft"
    k_max: int = 4
    k_min: int = 1
    adapt: bool = True
    ngram_max: int = 3
    ngram_min: int = 1
    ngram_window: int = 2048    # lookback tokens the n-gram match scans
    draft: Optional[str] = None  # preset name or checkpoint dir

    def __post_init__(self):
        self.k_max = max(1, int(self.k_max))
        self.k_min = max(1, min(int(self.k_min), self.k_max))
        self.ngram_min = max(1, int(self.ngram_min))
        self.ngram_max = max(self.ngram_min, int(self.ngram_max))
        self.ngram_window = max(self.ngram_max + 1, int(self.ngram_window))
        # dispatch-width buckets: powers of two up to k_max (plus k_max
        # itself) — bounds compiled verify-program count to
        # |k_buckets| x |s_buckets| no matter how adaptive k wanders
        b, out = 1, []
        while b < self.k_max:
            out.append(b)
            b *= 2
        out.append(self.k_max)
        self.k_buckets: List[int] = sorted(set(out))

    def bucket(self, k: int) -> int:
        """Smallest dispatch width covering ``k`` drafts (always >= 1: a
        zero-draft round still verifies one position, which IS a plain
        single-token decode step)."""
        for b in self.k_buckets:
            if k <= b:
                return b
        return self.k_buckets[-1]

    def next_k(self, k: int, accepted: int, proposed: int) -> int:
        """Per-lane adaptive draft length: grow on full acceptance, shrink
        on total rejection, hold otherwise."""
        if not self.adapt:
            return k
        if proposed and accepted >= proposed:
            return min(k * 2, self.k_max)
        if proposed and accepted == 0:
            return max(k // 2, self.k_min)
        return k


def resolve_spec(cfg) -> Optional[SpecConfig]:
    """Build a :class:`SpecConfig` from a ``JaxEngineConfig`` + ``DYN_SPEC*``
    env knobs. Returns None (spec fully off — zero extra compiled programs,
    untouched decode path) unless explicitly enabled."""
    mode = cfg.spec if cfg.spec is not None else os.environ.get("DYN_SPEC", "")
    mode = (mode or "").strip().lower()
    if mode in ("", "0", "off", "none", "false"):
        return None
    if mode not in ("ngram", "draft"):
        raise ValueError(f"spec/DYN_SPEC must be ngram|draft, got {mode!r}")
    return SpecConfig(
        mode=mode,
        k_max=(cfg.spec_k if cfg.spec_k is not None
               else _env_int("DYN_SPEC_K", 4)),
        k_min=_env_int("DYN_SPEC_K_MIN", 1),
        adapt=os.environ.get("DYN_SPEC_ADAPT", "1") not in ("0", "false"),
        ngram_max=_env_int("DYN_SPEC_NGRAM_MAX", 3),
        ngram_min=_env_int("DYN_SPEC_NGRAM_MIN", 1),
        ngram_window=_env_int("DYN_SPEC_NGRAM_WINDOW", 2048),
        draft=(cfg.spec_draft if cfg.spec_draft is not None
               else os.environ.get("DYN_SPEC_DRAFT") or None),
    )


@dataclass
class SeqSpecState:
    """Per-sequence speculation state (host side, engine thread)."""

    tokens: List[int]                    # committed prompt + generated
    k: int                               # current adaptive draft length
    # tokens committed since the last verify dispatch — folded into the
    # on-device penalty counts at the start of the next dispatch
    pending: List[int] = field(default_factory=list)


class NgramProposer:
    """Prompt-lookup decoding: self-speculation from the request's own
    context, no extra weights (vLLM's ``[ngram]`` method / prompt-lookup
    decoding). Looks up the most recent earlier occurrence of the current
    suffix n-gram (longest first) within a bounded lookback window and
    proposes its continuation. The match is numpy-vectorized and window-
    clipped: this runs per lane per verify round ON the engine thread, so
    a pure-Python scan over a 32k context would cost more than the verify
    forward it feeds."""

    def __init__(self, sc: SpecConfig):
        self.sc = sc

    def propose(self, seq_id: str, st: SeqSpecState, k: int) -> List[int]:
        ctx = st.tokens
        arr = np.asarray(ctx[-self.sc.ngram_window:], dtype=np.int32)
        L = arr.size
        for n in range(self.sc.ngram_max, self.sc.ngram_min - 1, -1):
            if L <= n:
                continue
            pat = arr[-n:]
            # candidate starts j in [0, L-n-1] (the suffix itself excluded)
            m = np.ones(L - n, dtype=bool)
            for o in range(n):
                m &= arr[o:o + L - n] == pat[o]
            idx = np.nonzero(m)[0]
            if idx.size:
                j = int(idx[-1]) + n   # most recent occurrence wins
                # j <= L - 1, so there is always at least one continuation
                # token (clipped at the context end)
                return [int(t) for t in arr[j:j + k]]
        return []

    def warmup(self) -> int:
        return 0   # no compiled programs on the lookup path

    def drop(self, seq_id: str) -> None:
        pass


class DraftModelProposer:
    """Greedy drafting from a second, smaller model against its own private
    paged KV pool (one page table per engine slot's sequence).

    The draft pool mirrors the main engine's bookkeeping discipline: pages
    are reserved ahead, only committed tokens are accounted, and drafted
    (uncommitted) KV writes overshoot into reserved pages where the next
    sync chunk simply overwrites them. Two jitted programs, both B=1 (the
    draft model is small; per-lane dispatch keeps shapes trivial):

    - sync: one chunk forward feeding committed tokens into the draft KV
    - propose: a ``lax.scan`` of k greedy single-token steps in ONE dispatch
    """

    def __init__(self, sc: SpecConfig, cfg, s_buckets: List[int],
                 c_buckets: List[int], device=None):
        """``device``: where the draft model and its pool live — the main
        engine's first device (default: this process's first device), so a
        replica built on another chip keeps its draft beside it."""
        import jax

        from ..models import llama

        if device is None:
            device = jax.devices()[0]

        if jax.process_count() > 1:
            raise ValueError(
                "spec='draft' is single-process only for now (the draft "
                "model is not mirrored to followers); use spec='ngram'")
        self.sc = sc
        src = sc.draft or "tiny-byte"
        if os.path.exists(src):
            from ..llm.model_card import ModelDeploymentCard
            card = ModelDeploymentCard.from_local_path(src)
            if not card.model_config:
                raise ValueError(f"draft checkpoint {src} has no config")
            mcfg = llama.LlamaConfig.from_hf_config(card.model_config)
        else:
            mcfg = llama.preset(src)
        self.mcfg = mcfg
        self.page = cfg.page_size
        from .cache import PagePool
        pad = -(-(sc.k_max + 1) // self.page) * self.page
        self.pages_per_seq = -(-(cfg.max_context + pad) // self.page)
        self.pool = PagePool(cfg.max_batch * self.pages_per_seq + 1,
                             self.page)
        self.s_buckets = [min(b, self.pages_per_seq * self.page)
                          for b in s_buckets]
        self.c_buckets = list(c_buckets)
        self.chunk = self.c_buckets[-1]
        if os.path.exists(src):
            from ..parallel.mesh import serving_mesh, sharding as mk_sharding
            from jax.sharding import PartitionSpec as P

            mesh = serving_mesh(1, 1, 1, 1, [device])
            specs = llama.param_specs(mcfg, 1, 1)
            shardings = jax.tree.map(
                lambda s: mk_sharding(mesh, *s), specs,
                is_leaf=lambda x: isinstance(x, P))
            from .loader import load_llama_params
            self.params = load_llama_params(src, mcfg, shardings)
        else:
            with jax.default_device(device):
                self.params = llama.init_params(
                    mcfg, jax.random.PRNGKey(cfg.seed + 101))
        import jax.numpy as jnp

        pool_shape = (mcfg.num_layers, mcfg.num_kv_heads,
                      self.pool.num_pages, self.page, mcfg.head_dim)
        zeros = jax.jit(lambda: jnp.zeros(pool_shape, mcfg.dtype),
                        out_shardings=jax.sharding.SingleDeviceSharding(
                            device))
        self.k_pool = zeros()
        self.v_pool = zeros()
        self._sync_fns: Dict[Tuple[int, int], Any] = {}
        self._prop_fns: Dict[int, Any] = {}
        self.synced: Dict[str, int] = {}   # committed tokens in draft KV

    # -- compiled programs ---------------------------------------------
    def _sync_fn(self, C: int, S: int):
        if (C, S) not in self._sync_fns:
            import jax
            import jax.numpy as jnp

            from ..models import llama
            mcfg = self.mcfg

            @partial(jax.jit, donate_argnums=(1, 2))
            def fn(params, k_pool, v_pool, tokens, positions, write_idx,
                   read_idx, read_pos, read_valid, last_i):
                logits, k_pool, v_pool = llama.forward(
                    params, mcfg, tokens, positions, k_pool, v_pool,
                    write_idx, read_idx, read_pos, read_valid,
                    attn_impl="xla", logits_idx=last_i)
                return (jnp.argmax(logits[:, 0], -1).astype(jnp.int32),
                        k_pool, v_pool)

            from ..utils.roofline import instrument_compile, record_compile
            self._sync_fns[(C, S)] = instrument_compile(
                "draft", fn, record_compile)
        return self._sync_fns[(C, S)]

    def _prop_fn(self, S: int):
        if S not in self._prop_fns:
            import jax
            import jax.numpy as jnp

            from ..models import llama
            mcfg = self.mcfg
            n_steps = self.sc.k_max

            @partial(jax.jit, donate_argnums=(1, 2))
            def fn(params, k_pool, v_pool, tok, page_table, length):
                def one(carry, _):
                    tok, length, k_pool, v_pool = carry
                    logits, k_pool, v_pool = llama.forward_decode(
                        params, mcfg, tok, k_pool, v_pool, page_table,
                        length, attn_impl="xla")
                    nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                    return (nxt, length + 1, k_pool, v_pool), nxt

                (_, _, k_pool, v_pool), toks = jax.lax.scan(
                    one, (tok, length, k_pool, v_pool), None, length=n_steps)
                return toks[:, 0], k_pool, v_pool   # [n_steps]

            from ..utils.roofline import instrument_compile, record_compile
            self._prop_fns[S] = instrument_compile(
                "draft", fn, record_compile)
        return self._prop_fns[S]

    @staticmethod
    def _bucket(n: int, buckets: List[int]) -> int:
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    # -- proposal ------------------------------------------------------
    def propose(self, seq_id: str, st: SeqSpecState, k: int) -> List[int]:
        from .cache import OutOfPages

        ctx = st.tokens
        if len(ctx) < 2:
            return []
        if seq_id not in self.synced:
            self.pool.create(seq_id, block_hashing=False)
            self.synced[seq_id] = 0
        try:
            self.pool.ensure_pages(seq_id, len(ctx) + self.sc.k_max)
        except OutOfPages:
            return []   # draft pool pressure: skip speculation this round
        # sync committed tokens (all but the last, which feeds the scan)
        n = self.synced[seq_id]
        while n < len(ctx) - 1:
            count = min(len(ctx) - 1 - n, self.chunk)
            self._sync_chunk(seq_id, ctx, n, count)
            n += count
            # accounted tokens never shrink: num_tokens tracks the sync
            # high-water mark, so re-synced (post-rollback) slots are
            # rewritten in place without re-accounting
            sc = self.pool.seqs[seq_id]
            if n > sc.num_tokens:
                sc.num_tokens = n
        self.synced[seq_id] = n
        # greedy scan from the last committed token
        import jax.numpy as jnp
        S = self._bucket(len(ctx) + self.sc.k_max, self.s_buckets)
        pt = self.pool.page_table_row(seq_id, S // self.page)[None, :]
        fn = self._prop_fn(S)
        toks, self.k_pool, self.v_pool = fn(
            self.params, self.k_pool, self.v_pool,
            jnp.asarray([ctx[-1]], jnp.int32), pt,
            np.asarray([len(ctx)], np.int32))
        # dynalint: ok(host-sync) draft-chain fetch: k drafted tokens in
        # one array per proposal round (the proposer is host-side by design)
        return [int(t) for t in np.asarray(toks)[:k]]

    def _sync_chunk(self, seq_id: str, ctx: List[int], start: int,
                    count: int) -> None:
        import jax.numpy as jnp

        C = self._bucket(count, self.c_buckets)
        S = self._bucket(start + count, self.s_buckets)
        tokens = np.zeros((1, C), np.int32)
        positions = np.zeros((1, C), np.int32)
        write_idx = np.zeros((1, C), np.int32)
        tokens[0, :count] = ctx[start:start + count]
        positions[0, :count] = np.arange(start, start + count)
        write_idx[0, :count] = self.pool.write_slots(seq_id, start, count)
        r_s, r_p, r_v = self.pool.read_slots(seq_id, start + count, S)
        fn = self._sync_fn(C, S)
        _, self.k_pool, self.v_pool = fn(
            self.params, self.k_pool, self.v_pool, tokens, positions,
            write_idx, r_s[None], r_p[None], r_v[None],
            np.asarray([count - 1], np.int32))

    def warmup(self) -> int:
        """Compile every draft sync/propose bucket program on dummy inputs
        (called from ``EngineCore.warmup``): without this, the first
        spec='draft' request to land in a fresh bucket pays a full XLA
        compile mid-serving. All dummy writes target scratch page 0."""
        import jax.numpy as jnp

        n = 0
        for S in sorted(set(self.s_buckets)):
            pt = np.zeros((1, S // self.page), np.int32)
            # argument placement must match propose() exactly (device tok,
            # host tables/lengths): jit cache keys include placement
            _, self.k_pool, self.v_pool = self._prop_fn(S)(
                self.params, self.k_pool, self.v_pool,
                jnp.zeros(1, jnp.int32), pt, np.ones(1, np.int32))
            n += 1
            for C in sorted(set(self.c_buckets)):
                zc = np.zeros((1, C), np.int32)
                _, self.k_pool, self.v_pool = self._sync_fn(C, S)(
                    self.params, self.k_pool, self.v_pool, zc, zc, zc,
                    np.zeros((1, S), np.int32), np.zeros((1, S), np.int32),
                    np.zeros((1, S), bool), np.zeros(1, np.int32))
                n += 1
        return n

    def drop(self, seq_id: str) -> None:
        if seq_id in self.synced:
            self.synced.pop(seq_id, None)
            self.pool.release(seq_id)


def build_proposer(sc: SpecConfig, cfg, s_buckets: List[int],
                   c_buckets: List[int], device=None):
    if sc.mode == "draft":
        return DraftModelProposer(sc, cfg, s_buckets, c_buckets, device)
    return NgramProposer(sc)
