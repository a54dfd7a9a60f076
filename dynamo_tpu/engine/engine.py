"""The in-tree JAX engine: continuous batching over a paged KV pool.

Architecture (TPU-first):
- All device work happens in exactly two jitted programs per (bucket) shape:
  ``prefill_mid`` (chunk forward, no LM head) and ``prefill_last``/``decode``
  (forward + sample). Shapes are bucketed so XLA compiles a handful of
  programs once and replays them forever; KV pools are donated so updates are
  in-place in HBM.
- A synchronous :class:`EngineCore` owns all mutable state (slots, page
  tables, sampling vectors) and is driven from one engine thread — the same
  single-owner actor discipline the reference uses for its schedulers.
- :class:`JaxEngine` is the asyncio facade implementing the AsyncEngine
  contract (BackendInput -> stream of EngineOutput).

Reference capability: the role vLLM/TRT-LLM play behind the reference's
adapters (continuous batching, paged KV, streaming detached tokens), per
SURVEY §7 step 3.
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import logging
import math
import os
import queue as thread_queue
import threading
import time
from dataclasses import dataclass, field
from functools import cached_property, partial
from typing import Any, AsyncIterator, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..llm.model_card import ModelDeploymentCard
from ..llm.protocols.common import BackendInput, EngineOutput, FinishReason
from ..models import llama
from ..obs import flightrec as _flightrec
from ..ops.attention import (PAGES_PER_BLOCK, flash_attention,
                             latent_flash_blocks, latent_flash_copies,
                             latent_flash_fetch, paged_attention,
                             paged_live_pages)
from ..parallel.mesh import AXIS_TP, serving_mesh
from ..runtime.engine import AsyncEngine, Context
from ..utils import tracing as _tracing
from ..utils.jaxenv import on_tpu
from .cache import OutOfPages, PagePool, WindowPages, cache_kinds
from .sampling import (STATIC_K, SamplingState, any_sampling,
                       apply_penalties, resume_seed, sample)

log = logging.getLogger("dynamo_tpu.engine")


# named ``jax.profiler`` scope around each device dispatch: lines the XLA
# timeline up with the host-side request spans in captured profiles
_trace_annotation = jax.profiler.TraceAnnotation

# What the engine thread can be doing. Exactly one phase is open at a time:
# siblings, back to back, never nested, so a reader of a capture that takes
# "the last dynamo.* span begun before an idle gap" names what the host was
# doing in it. ``prefill`` / ``decode`` / ``verify`` are the enqueue of a
# dispatch (their annotations carry the bucket in brackets); the ``*_fetch``
# phases are the engine thread blocked on the device.
PHASES = ("inbox", "admit", "prefill_build", "prefill", "prefill_fetch",
          "decode_build", "decode", "verify", "decode_fetch", "emit",
          "deliver", "housekeeping", "paged", "idle")
_PHASE_SCOPE = {p: "dynamo." + p for p in PHASES}


class _Phases:
    """The engine thread's phase switch: ``to(name)`` closes the open phase
    and opens the next. One switch, two outputs: a ``TraceAnnotation``
    ``dynamo.<phase>`` on the profiler's clock (free when no capture runs),
    and the phase's ``perf_counter`` seconds in
    ``dyn_engine_phase_seconds_total{phase}``."""

    __slots__ = ("_seconds", "_name", "_scope", "_t0")

    def __init__(self, seconds):
        self._seconds = seconds
        self._name: Optional[str] = None
        self._scope = None
        self._t0 = 0.0

    def to(self, name: Optional[str], scope: Optional[str] = None) -> None:
        """``scope`` names the annotation where it carries a dispatch's
        bucket (``dynamo.decode[S512]``); otherwise ``dynamo.<name>``."""
        now = time.perf_counter()
        if self._name is not None:
            self._scope.__exit__(None, None, None)
            self._seconds.inc(self._name, amount=now - self._t0)
        self._name, self._t0 = name, now
        if name is not None:
            self._scope = _trace_annotation(scope or _PHASE_SCOPE[name])
            self._scope.__enter__()

    def close(self) -> None:
        self.to(None)


class _LaneClock:
    """Seconds, so far, during which a slot was free and every prefill lane
    was taken by a prompt mid-prefill: what a request waiting then waited
    for is a lane, not a slot. ``step()`` tells it the condition once an
    iteration and ``_prefill_round`` once it has admitted; a request's share
    is the difference of two ``read``s."""

    __slots__ = ("_total", "_since")

    def __init__(self) -> None:
        self._total = 0.0
        self._since = 0.0       # time.monotonic() the lanes filled; 0 = free

    def set(self, full: bool) -> None:
        if full != bool(self._since):
            now = time.monotonic()
            if full:
                self._since = now
            else:
                self._total += now - self._since
                self._since = 0.0

    def read(self, at: float) -> float:
        since = self._since
        return self._total + (max(0.0, at - since) if since else 0.0)


def global_put(host_array, sharding) -> jax.Array:
    """device_put that also works on a multi-process mesh: every process
    contributes only its addressable shards (all processes must call this
    with the same host data)."""
    if all(d.process_index == jax.process_index()
           for d in sharding.device_set):
        # dynalint: ok(flow-accounting) primitive wrapper — callers meter
        # the tree-level flow (cold weight load, swap slab stream)
        return jax.device_put(host_array, sharding)
    return jax.make_array_from_callback(
        host_array.shape, sharding,
        lambda idx: np.asarray(host_array[idx]))


def _buckets(lo: int, hi: int) -> List[int]:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


@dataclass
class JaxEngineConfig:
    model: llama.LlamaConfig
    tp: int = 1
    sp: int = 1                         # sequence-parallel (ring) axis size
    ep: int = 1                         # expert-parallel axis size (MoE)
    pp: int = 1                         # pipeline-parallel stage count
    page_size: int = 64
    max_batch: int = 8
    max_context: int = 2048
    prefill_chunk: int = 512
    num_pages: Optional[int] = None     # default: max_batch*max_context worth
    decode_steps: int = 8               # decode iterations per XLA dispatch
    prefill_lanes: Optional[int] = None  # sequences per prefill dispatch
    #                                      (None => max_batch: whole wave)
    params_path: Optional[str] = None   # safetensors dir; None => random init
    seed: int = 0
    preset: Optional[str] = None
    # attention backend: "auto" => Pallas kernels on TPU, XLA dense elsewhere.
    # Explicit values: "pallas" | "xla" | "ring" (sequence-parallel prefill
    # over the sp mesh axis; decode stays pallas/xla).
    attn_impl: str = "auto"
    # precompile every (lanes, chunk, context) prefill bucket and every
    # decode context bucket at init — tail latency becomes predictable
    # (the reference engines' startup warmup / CUDA-graph capture role)
    warmup: bool = False
    # KV block manager (SURVEY §2.4): prefix reuse + tiered offload
    enable_prefix_reuse: bool = True
    host_cache_blocks: int = 0          # host-DRAM KV tier capacity (0 = off)
    disk_cache_blocks: int = 0          # mmap spill tier capacity (0 = off)
    disk_cache_path: Optional[str] = None
    # cluster KV sharing (llm/kv_cluster/): mirror every newly sealed
    # block to the host tier write-through, so peers can fetch hot
    # prefixes that never saw device-pool eviction pressure. Requires
    # host_cache_blocks > 0; the worker CLI turns it on with
    # DYN_KV_CLUSTER=1.
    cluster_writethrough: bool = False
    # speculative decoding (engine/spec.py). None => consult the DYN_SPEC*
    # env knobs; "" / "off" force-disables regardless of env. Off by
    # default: zero extra compiled programs, decode path untouched.
    spec: Optional[str] = None          # "ngram" | "draft" | "off"/None
    spec_k: Optional[int] = None        # max drafts/lane (None => DYN_SPEC_K)
    spec_draft: Optional[str] = None    # draft preset/dir (None => env)
    # KV paging (llm/kvpage/): serve contexts beyond max_context with
    # device residency bounded to a page budget — chunked prefill demotes
    # sealed blocks d2h, decode streams the cold tail back through staged
    # uploads. None => consult the DYN_KVPAGE_* env knobs; 0 disables.
    # Requires host_cache_blocks > 0 and composes with neither spec
    # decoding nor pp/sp/multi-host (validated at construction).
    kvpage_budget: Optional[int] = None      # device pages for the lane
    kvpage_seg_pages: Optional[int] = None   # blocks per staging segment
    kvpage_prefetch: Optional[int] = None    # segments prefetched ahead
    kvpage_max_context: Optional[int] = None  # paged context ceiling
    kvpage_batch: Optional[int] = None       # concurrent decode lanes

    @classmethod
    def from_card(cls, card: ModelDeploymentCard, tensor_parallel: int = 1,
                  **extra) -> "JaxEngineConfig":
        if card.model_config:
            mcfg = llama.LlamaConfig.from_hf_config(card.model_config)
        elif extra.get("preset"):
            mcfg = llama.preset(extra["preset"])
        elif card.path and (gpath := _gguf_file(card.path)):
            # GGUF cards carry no HF config dict — the model shape lives in
            # the container metadata; sizing from a preset here would build
            # sampler state (penalty counts) at the wrong vocab width
            from ..llm.gguf import read_gguf
            g = read_gguf(gpath)
            try:
                mcfg = g.llama_config()
            finally:
                g.close()
        else:
            mcfg = llama.preset("tiny-byte")
        kw = dict(
            model=mcfg,
            tp=tensor_parallel,
            page_size=card.kv_block_size,
            params_path=card.path,
        )
        # every config field is overridable from extra args; unknown keys
        # raise instead of being silently dropped (a typo'd or unplumbed
        # key — e.g. page_size once — must not ship a different engine
        # than the config asked for)
        managed = {"model", "params_path"}
        for k, v in extra.items():
            if k == "preset":
                continue
            if k in cls.__dataclass_fields__ and k not in managed:
                kw[k] = v
            else:
                raise ValueError(f"unknown engine arg {k!r}")
        cfg = cls(**kw)
        cfg.max_context = min(cfg.max_context, card.context_length)
        return cfg


@dataclass
class _Slot:
    seq_id: str
    request: BackendInput
    prompt: List[int]
    prefill_done: int = 0           # prompt tokens already in cache
    generated: int = 0
    last_token: int = 0
    cum_logprob: float = 0.0
    cancelled: bool = False
    # physical tokens written after every ENQUEUED decode dispatch executes
    # (runs ahead of `generated`, which advances when results are fetched)
    sched_len: int = 0
    # VLM: per-position image-group ids + projected soft tokens
    # [n_images, mm_tokens, D] (None for text-only requests)
    mm_spans: Optional[np.ndarray] = None
    mm_soft: Optional[np.ndarray] = None
    # the request's way to its first token, ``time.monotonic()`` stamps
    # taken on the engine thread (0.0 = not reached), and what its spans in
    # the request trace hang under and say
    t_admitted: float = 0.0
    t_first_token: float = 0.0      # on the host
    trace_parent: Any = None        # tracing.SpanContext of the submitter
    prefix_hit: int = 0
    chunks: int = 0


@dataclass
class StepOutput:
    seq_id: str
    token: int
    logprob: float                  # cumulative over the sequence
    finish: Optional[FinishReason] = None
    prompt_tokens: int = 0
    error: Optional[str] = None     # cause when finish == ERROR
    # this token's own logprob (not re-derivable from the cumulative without
    # float cancellation)
    token_logprob: float = 0.0
    # typed-error fields (meaningful only with finish == ERROR): the
    # http-ish status + stage/reason triple the uniform error body exposes,
    # so an engine-side rejection (over-length prompt -> 400) survives to
    # the frontend instead of collapsing into a generic 500
    error_code: int = 500
    error_stage: Optional[str] = None
    error_reason: Optional[str] = None
    # admission's sealed-prefix restore length, set on a sequence's FIRST
    # output only (None elsewhere) — rides to EngineOutput.
    # kv_prefix_hit_tokens
    prefix_hit: Optional[int] = None
    # ``time.monotonic()`` when the sequence's first token reached the host,
    # on its FIRST output only: the frontend's ``post_engine`` stage starts
    # here
    first_token_at: Optional[float] = None


class EngineCore:
    """Synchronous continuous-batching core. Single-threaded by contract."""

    def __init__(self, cfg: JaxEngineConfig,
                 devices: Optional[List[jax.Device]] = None):
        self.cfg = cfg
        m = cfg.model
        llama.validate_tp(m, cfg.tp, cfg.ep)
        llama.validate_pp(m, cfg.pp, cfg.tp)
        if cfg.pp > 1 and cfg.sp > 1:
            # ring prefill shards the sequence axis the pp stage loop
            # microbatches — the two prefill schedules don't compose (the
            # reference's vLLM pp has the same envelope); pp x tp x ep all
            # compose (round 5)
            raise ValueError("pp > 1 composes with tp/ep (sp must be 1)")
        self.mesh = serving_mesh(cfg.tp, cfg.sp, cfg.ep, cfg.pp, devices)
        # every platform decision below (kernels, peaks, the draft model's
        # home) is keyed on the devices this engine was given, not on the
        # default backend's name: utils/jaxenv.on_tpu
        dev0 = self.mesh.devices.flat[0]
        tpu = on_tpu(dev0)
        from ..utils.prometheus import stage_metrics

        self.stage = stage_metrics()   # cached: observe() runs per harvest
        self.phase = _Phases(self.stage.engine_phase_seconds)
        self.lane_clock = _LaneClock()
        from ..utils.roofline import count_xla_compiles

        count_xla_compiles()
        self.page_size = cfg.page_size
        # speculative decoding: resolved up front because the page-pad and
        # bucket sizing below must cover the verify program's k+1 positions
        from .spec import resolve_spec
        self.spec = resolve_spec(cfg)
        if self.spec is not None and cfg.pp > 1:
            raise ValueError("speculative decoding does not compose with "
                             "pp > 1 yet (the staged decode path takes no "
                             "multi-position verify inputs)")
        # every sequence may overshoot up to 2*decode_steps speculative
        # tokens (one dispatch in flight plus one chained behind it) — or,
        # under spec decode, k_max drafts + 1 bonus token per verify round
        overshoot = 2 * cfg.decode_steps
        if self.spec is not None:
            overshoot = max(overshoot, self.spec.k_max + 1)
        self._spec_pad = -(-overshoot // cfg.page_size) * cfg.page_size
        # ceil: a seq at max_context with the speculative pad must always fit
        self.max_pages_per_seq = -(-(cfg.max_context + self._spec_pad)
                                   // cfg.page_size)
        num_pages = cfg.num_pages or (cfg.max_batch * self.max_pages_per_seq + 1)
        self.pool = PagePool(num_pages, cfg.page_size)

        # --- params ---------------------------------------------------
        # sharding() drops spec axes the mesh doesn't carry (e.g. the ep
        # axis of MoE expert weights on an ep=1 mesh)
        from ..parallel.mesh import sharding as mk_sharding

        specs = llama.param_specs(m, cfg.tp, cfg.pp)
        shardings = jax.tree.map(
            lambda s: mk_sharding(self.mesh, *s), specs,
            is_leaf=lambda x: isinstance(x, P))
        if cfg.params_path and _has_safetensors(cfg.params_path):
            from .loader import load_llama_params
            self.params = load_llama_params(cfg.params_path, m, shardings)
        elif cfg.params_path and (gguf := _gguf_file(cfg.params_path)):
            from ..llm.gguf import load_llama_params_gguf
            _, self.params = load_llama_params_gguf(
                gguf, cfg=m, shardings=shardings, dtype=m.dtype)
        else:
            params = llama.init_params(m, jax.random.PRNGKey(cfg.seed))
            self.params = jax.tree.map(
                # dynalint: ok(flow-accounting) random-init placement (no
                # checkpoint): init_params already materialized on device,
                # the put is a resharding — checkpoint loads meter in the
                # loader
                lambda a, s: global_put(a, s), params, shardings)
        # whatever the source, the bucket programs are handed the q / k / v
        # projections as their matmuls read them (llama.stored_params: a
        # matrix [H x Dh, D] a layer, so that no program re-lays a weight
        # or cuts a layer out of a stack), made once here, a leaf at a
        # time. What indexes the stack by a traced layer keeps the
        # published tree: a pipeline stage, the pager's programs
        # (dyn_engine_info{attn_proj})
        from ..llm.kvpage.runner import PagedConfig
        self.attn_proj = ("published" if cfg.pp > 1
                          or PagedConfig.resolve(cfg) is not None
                          else "out_in")
        if self.attn_proj == "out_in":
            self.params = llama.stored_params(self.params, donate=True,
                                              cfg=m)

        # --- vision tower (Gemma3 VLM) --------------------------------
        # replicated params (the tower is tiny next to the LM; sharding it
        # would only add collectives to a once-per-request encode)
        self.vision_cfg = None
        if m.vision is not None:
            from ..models import siglip as _siglip

            self.vision_cfg = _siglip.SiglipVisionConfig.from_hf_config(
                m.vision, dtype=m.dtype)
            vt = None
            if cfg.params_path and _has_safetensors(cfg.params_path):
                from .loader import _get, _open_all

                tensors = _open_all(cfg.params_path)
                vnames = [k for k in tensors
                          if "vision_tower" in k
                          or "multi_modal_projector" in k]
                if vnames:
                    strip = ("model." if any(
                        k.startswith("model.vision_tower") for k in vnames)
                        else "")
                    vt = {k[len(strip):]: _get(tensors, k) for k in vnames}
            if vt is not None:
                self.vision_params = _siglip.params_from_hf(
                    vt, self.vision_cfg)
                self.proj_params = _siglip.projector_from_hf(
                    vt, self.vision_cfg)
            elif cfg.params_path and _has_safetensors(cfg.params_path):
                # a real checkpoint WITHOUT vision tensors must not fall
                # back to random tower weights: images would get
                # confidently wrong completions
                raise ValueError(
                    f"model config declares a vision tower but "
                    f"{cfg.params_path} has no vision_tower/"
                    f"multi_modal_projector tensors; serve the text-only "
                    f"config instead")
            else:
                # no checkpoint at all: random init (tests/benching)
                kv1, kv2 = jax.random.split(jax.random.PRNGKey(cfg.seed + 1))
                self.vision_params = _siglip.init_params(self.vision_cfg, kv1)
                self.proj_params = _siglip.init_projector_params(
                    self.vision_cfg, m.hidden_size, kv2)

            def _encode(px):
                feats = _siglip.forward(self.vision_params, self.vision_cfg,
                                        px)
                return _siglip.project(self.proj_params, self.vision_cfg,
                                       feats, m.mm_tokens_per_image)

            # jit caches per image-count; image requests are rare relative
            # to decode steps, so lazy compile is fine
            self._encode_images = jax.jit(_encode)

        # --- attention backend ---------------------------------------
        impl = cfg.attn_impl
        if m.attn_logit_softcap or m.sliding_window is not None:
            # Gemma2/3: the Pallas flash/paged kernels take softcap +
            # sliding windows natively (round 5); only ring attention
            # still lacks them (cross-shard windows don't compose with
            # the ring schedule)
            if impl == "ring":
                raise ValueError(
                    "attn_impl='ring' does not support softcapping/"
                    "sliding-window models (Gemma2/3); use 'pallas' or "
                    "'xla'")
        if cfg.pp > 1 and impl == "ring":
            # ring rides the sp axis; pp stages the layer stack — the two
            # prefill shardings don't compose
            raise ValueError("attn_impl='ring' is not supported with pp")
        if impl == "auto":
            # Pallas kernels on TPU (shard_map-wrapped per tp shard); XLA
            # dense off-TPU or when the model's GQA grouping can't split.
            # Both are choices by platform/shape, never a recovery: on a
            # TPU the probe below raises the compiler's own message rather
            # than degrading to the dense path
            impl = ("pallas" if tpu and llama.pallas_tp_ok(m, cfg.tp)
                    else "xla")
            if impl == "pallas":
                _pallas_probe(m, cfg, dev0)
        if impl not in ("pallas", "xla", "ring"):
            raise ValueError(
                f"attn_impl must be auto|pallas|xla|ring, got {impl!r}")
        if impl == "pallas" and not llama.pallas_tp_ok(m, cfg.tp):
            raise ValueError(
                f"attn_impl='pallas' needs an integral per-shard GQA group: "
                f"Hq={m.num_heads}/tp={cfg.tp} per shard must divide by the "
                f"per-shard kv heads")
        if impl == "ring" and cfg.sp < 2:
            raise ValueError("attn_impl='ring' needs sp >= 2")
        self.attn_impl = impl
        # decode is single-token — the ring (prefill) axis does not apply;
        # decode attention runs pallas on TPU, dense XLA elsewhere
        if impl == "ring":
            self.decode_attn_impl = ("pallas"
                                     if tpu and llama.pallas_tp_ok(m, cfg.tp)
                                     else "xla")
        else:
            self.decode_attn_impl = impl
        # how decode's paged_attention call runs on these devices (None on
        # the dense path) — reported, never used to select
        self.paged_kernel = (("dma" if tpu else "dma[interpret]")
                             if self.decode_attn_impl == "pallas" else None)
        log.info("attention: prefill=%s decode=%s paged_kernel=%s on %s (%s)",
                 self.attn_impl, self.decode_attn_impl, self.paged_kernel,
                 dev0.platform, dev0.device_kind)
        # the decode step's calls of the paged kernel, by the window they
        # take (forward_decode's ``paged_for``): kind -> (window, layers), for
        # _count_attn_pages; none on the dense path
        self._attn_calls: Dict[str, Tuple[Optional[int], int]] = {}
        if self.decode_attn_impl == "pallas" and cfg.pp == 1:
            slides = [bool(m.layer_sliding(l)) for l in range(m.num_layers)
                      if not m.layer_state(l)]
            calls = {"full": (None, slides.count(False)),
                     "window": (m.sliding_window, slides.count(True))}
            self._attn_calls = {k: c for k, c in calls.items() if c[1]}

        # --- KV pools: [L, Hkv, n_pages, page, Dh], head-major, stored
        # once in XLA's default tiled layout. The paged kernel reads the
        # whole pool in place by layer index; every program writes and
        # gathers it row-wise (head index spelt out), which keeps that
        # layout — a window over [Hkv, ·, ·, Dh] makes XLA re-lay the whole
        # pool at a program's entry and exit (PERF.md §6, PR 26) ----------
        kv_spec = llama.kv_cache_spec(m, cfg.tp, cfg.pp)
        self.kv_sharding = NamedSharding(self.mesh, kv_spec)
        # one descriptor a cache kind (engine/cache.py): the global cache
        # of every model, and the window cache of a per-kind model
        self.cache_kinds = cache_kinds(m)
        # what puts a decode step's new K/V rows into each kind's pools: the
        # paged kernel or the row scatter (llama.kernel_writes, the predicate
        # forward_decode itself asks) — reported, never used to select
        how = {k.name: "kernel" if cfg.pp == 1 and llama.kernel_writes(
                   self.mesh, self.decode_attn_impl, k.k_store, k.fold)
               else "scatter"
               for k in self.cache_kinds if k.state is None}
        self.decode_kv_write = (
            next(iter(how.values())) if len(set(how.values())) == 1
            else ",".join(f"{n}:{h}" for n, h in how.items()))
        # ... and a prefill chunk's: a window a page run (llama.
        # kv_write_pages) where the chunks this engine cuts start on a
        # page's first slot, else a window a row. What a dispatch ran is
        # counted (_chunk_form, dyn_engine_prefill_kv_writes_total)
        self.prefill_kv_write = (
            "page" if cfg.pp == 1 and cfg.prefill_chunk % cfg.page_size == 0
            else "row")

        zero_fns: Dict[Tuple[int, ...], Any] = {}

        def zeros(shape):
            # jitted zeros with explicit out_sharding: allocates straight
            # into the (possibly multi-process) sharded layout, no host
            # staging (one program a shape)
            if shape not in zero_fns:
                zero_fns[shape] = jax.jit(
                    lambda: jnp.zeros(shape, m.dtype),
                    out_shardings=self.kv_sharding)
            return zero_fns[shape]()

        k_shape, v_shape = self.cache_kinds[0].pool_shapes(num_pages,
                                                           cfg.page_size)
        self.k_pool = zeros(k_shape)
        self.v_pool = zeros(v_shape)
        # A per-kind model's window layers keep their K/V in a second pair
        # of pools, of their own head count and pages, through a second
        # page table a lane: a lane holds there only the pages a query of
        # its can still see (cache.WindowPages), so the pool is lanes x a
        # window's and a chunk's pages, whatever the context. Whatever
        # moves, matches or re-enters blocks knows one cache: such a model
        # refuses those features by name, as a model with an indexer does.
        self.win = self.wk_pool = self.wv_pool = None
        if m.has_window:
            self._refuse_configured(impl, self._two_caches_refusal)
            wk = self.cache_kinds[1]
            self.win_pages = cfg.max_batch * WindowPages.lane_pages(
                wk.window, cfg.prefill_chunk, cfg.page_size) + 1
            self.win = WindowPages(self.win_pages, cfg.page_size, wk.window)
            wk_shape, wv_shape = wk.pool_shapes(self.win_pages,
                                                cfg.page_size)
            self.wk_pool, self.wv_pool = zeros(wk_shape), zeros(wv_shape)
            if cfg.enable_prefix_reuse:
                log.info("prefix reuse is off for this model: a block of "
                         "the global cache cannot be re-entered without "
                         "the window layers' keys (no block is hashed, "
                         "sealed or published)")
        # a model with an indexer (learned top-k attention) keeps its index
        # keys in a third pool on the SAME pages and page tables: allocated,
        # donated, written and threaded through the programs with the other
        # two. Whatever moves blocks off the device pool knows two pools, and
        # a block that came back without its index keys would select wrongly
        # without any error: such a model refuses those features by name.
        self.i_pool = None
        if m.has_indexer:
            if cfg.pp > 1:
                raise ValueError(self._indexer_refusal(
                    "pp > 1 (the staged forward)"))
            self._refuse_configured(impl, self._indexer_refusal)
            self.idx_sharding = NamedSharding(self.mesh, P())
            i_shape = llama.index_pool_shape(m, num_pages, cfg.page_size)
            self.i_pool = jax.jit(lambda: jnp.zeros(i_shape, m.dtype),
                                  out_shardings=self.idx_sharding)()

        # A model with state-space layers keeps, per LANE and not per token,
        # a recurrent state and a convolution tail for each such layer:
        # [state layers, max_batch, ...] beside the K/V pools of its
        # attention layers, donated and threaded through the programs with
        # them; a model with gated short-convolution layers keeps a tail
        # ALONE (``s_pool`` stays None: one pool, not two). Nothing pages or
        # hashes it, and a K/V block re-entered or moved without the state
        # at its boundary would decode from the wrong state: such a model
        # refuses those features by name too.
        self.s_pool = self.c_pool = None
        if m.has_state:
            self._refuse_configured(impl, self._lane_state_refusal)
            self.state_sharding = NamedSharding(self.mesh, P())
            *s_shape, c_shape = self.cache_kinds[1].state_shapes(
                cfg.max_batch)
            if s_shape:
                self.s_pool = jax.jit(
                    lambda: jnp.zeros(s_shape[0], jnp.float32),
                    out_shardings=self.state_sharding)()
            self.c_pool = jax.jit(lambda: jnp.zeros(c_shape, m.dtype),
                                  out_shardings=self.state_sharding)()
            self.stage.ssm_state_bytes.set(value=float(
                self.cache_kinds[1].lane_bytes(np.dtype(m.dtype).itemsize)
                * cfg.max_batch))
            if cfg.enable_prefix_reuse:
                log.info("prefix reuse is off for this model: a block of "
                         "the K/V cache cannot be re-entered without the "
                         "state-space layers' state at its boundary (no "
                         "block is hashed, sealed or published)")
        # no block of such a model is hashed, matched or adopted
        self._no_block_reuse = self.win is not None or m.has_state
        # A model with latent attention keeps ONE row a token for all heads,
        # in the two pools above (the shared rotary key; the compressed
        # vector), per token and on the global pages: hashed, sealed,
        # matched and adopted like any K/V page. What MOVES blocks off the
        # device pool sizes its buffers by kv heads x head_dim of one shape
        # for both pools: refused by name.
        if m.has_latent:
            if cfg.pp > 1:
                raise ValueError(self._latent_refusal(
                    "pp > 1 (the staged forward)"))
            self._refuse_configured(impl, self._latent_refusal)

        # --- KV block manager: tiered offload + prefix reuse ----------
        from ..llm.kvbm.transfer import CopyStream
        self.copy_stream = CopyStream()
        self.tiered = None
        if cfg.host_cache_blocks > 0:
            from ..llm.kvbm.tiers import (DiskKvTier, HostKvTier,
                                          TieredKvCache)
            blk_shape = (m.num_layers, m.num_kv_heads, cfg.page_size,
                         m.head_dim)
            # ml_dtypes gives numpy a real bfloat16, so the host tier stores
            # KV at device precision
            # dynalint: ok(host-sync) init-time dtype probe of a 0-d
            # scalar, once per engine construction — never on a request
            np_dtype = np.asarray(jnp.zeros((), m.dtype)).dtype
            host = HostKvTier(cfg.host_cache_blocks, blk_shape, np_dtype)
            disk = None
            if cfg.disk_cache_blocks > 0:
                import os
                # default path is per-process: two engines on one host
                # (e.g. prefill + decode workers) must not memmap the same
                # spill files in w+ mode and corrupt each other's blocks
                path = (cfg.disk_cache_path
                        or f"/tmp/dynamo_tpu_kv_spill.{os.getpid()}")
                disk = DiskKvTier(cfg.disk_cache_blocks, blk_shape,
                                  np_dtype, path)
            self.tiered = TieredKvCache(host, disk)
        self._evict_buf: List[Tuple[int, int]] = []
        self.pool.on_block_evicted = self._offload_evicted
        # cluster write-through: newly sealed blocks queue for a host-tier
        # mirror copy. A block SEALS before the dispatch that writes its
        # KV is issued (extend/account run pre-dispatch), so entries
        # ratchet through two step boundaries (pending -> armed -> buf)
        # before the d2h: by then the writing dispatch has been issued and
        # JAX sequences the copy after it by data dependency.
        self._writethrough_buf: List[Tuple[int, int]] = []
        self._writethrough_armed: List[Tuple[int, int]] = []
        self._writethrough_pending: List[Tuple[int, int]] = []
        if self.tiered is not None and cfg.cluster_writethrough:
            self.pool.add_seal_hook(self._writethrough_sealed)

        # prefix-cache accounting (feeds ForwardPassMetrics + disagg router)
        self.last_prefix_hit = 0
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0

        # --- slots / scheduler ---------------------------------------
        self.slots: List[Optional[_Slot]] = [None] * cfg.max_batch
        self.by_seq: Dict[str, _Slot] = {}
        # (seq_id, request, time.monotonic() at submit, the lane clock
        # then, submitter's span)
        self.waiting: Deque[Tuple[str, BackendInput, float, float, Any]] = \
            collections.deque()
        self.sampling = SamplingState.host_init(cfg.max_batch)
        # commit to a canonical replicated sharding: program cache keys
        # include argument shardings, so an uncommitted key would recompile
        # every bucket once more after the first on-device key update
        self._rep_sharding = NamedSharding(self.mesh, P())
        self.sampling.key = jax.jit(
            lambda: jax.random.split(jax.random.key(0), cfg.max_batch),
            out_shardings=self._rep_sharding)()
        # generated-token occurrence counts per lane (frequency/presence
        # penalties): persistent device state threaded through every decode
        # dispatch like the KV pools; lanes reset in-program when a new
        # sequence enters decode (multi-host lockstep holds — the resets
        # ride the mirrored dispatch, never a side op)
        self.gen_counts = jax.jit(
            lambda: jnp.zeros((cfg.max_batch, m.vocab_size), jnp.int32),
            out_shardings=self._rep_sharding)()
        self._decode_seen: Dict[int, str] = {}

        # --- goodput accounting (utils/roofline.py) -------------------
        # analytic FLOPs/bytes per dispatch over measured dispatch wall
        # time, against the platform peak (whole-mesh: per-chip table
        # peaks scale by device count; the calibrated CPU fallback is
        # already host-wide, virtual devices share one memory bus)
        from ..utils import roofline

        peaks = roofline.detect_peaks(dev0.device_kind, dev0.platform)
        if peaks.source.startswith("table"):
            n_dev = int(self.mesh.devices.size)
            peaks = roofline.Peaks(peaks.flops * n_dev,
                                   peaks.hbm_bytes * n_dev, peaks.source)
        weight_bytes = float(sum(
            int(a.size) * np.dtype(a.dtype).itemsize
            for a in jax.tree.leaves(self.params)))
        self.costs = roofline.model_costs(m, weight_bytes=weight_bytes)
        self.goodput = roofline.GoodputMeter(self.costs, peaks)
        # set by the compile-instrumentation wrapper when a dispatch's
        # first call just XLA-compiled: that dispatch's wall time is
        # compile, not compute, and must not poison the MFU window
        self._just_compiled = False

        # --- compiled programs ---------------------------------------
        # decode reads are indexed through page tables of width S/page_size:
        # every S bucket MUST be a page multiple or the final partial page
        # would clamp out of bounds and silently read/write the wrong page
        # Buckets above 128 are also multiples of 128: the flash kernel
        # tiles the context axis in 128-lane blocks and takes an axis it
        # cannot tile as ONE block (ops/attention._pick_block) — fine for a
        # short context, past VMEM for max_context + pad (2048 + 64 = 2112
        # was refused by the v5e compiler; 2176 tiles)
        pg = cfg.page_size
        raw = _buckets(min(256, cfg.max_context), cfg.max_context + self._spec_pad)

        def s_round(b: int) -> int:
            q = math.lcm(pg, 128) if b > 128 else pg
            return -(-b // q) * q

        self.s_buckets = sorted({s_round(b) for b in raw})
        self.c_buckets = _buckets(min(32, cfg.prefill_chunk), cfg.prefill_chunk)
        # prefill lane budget: the whole admission wave prefills in one
        # dispatch by default — splitting a 32-request wave into 8-lane
        # dispatches quadruples the per-dispatch host round-trips, which
        # dominate TTFT when the host link is slow
        lanes = cfg.prefill_lanes or cfg.max_batch
        self.b_buckets = _buckets(1, max(1, min(lanes, cfg.max_batch)))
        self.moe_dispatch = self._moe_dispatch_forms()
        self._decode_fns: Dict[int, Any] = {}
        self._prefill_batch_fns: Dict[Tuple[int, int, int, bool, str],
                                      Any] = {}
        # verify programs, keyed (S, K): compiled lazily, and ONLY when spec
        # decoding is enabled — spec off costs zero extra programs
        self._verify_fns: Dict[Tuple[int, int], Any] = {}
        self.proposer = None
        self._spec_states: Dict[str, Any] = {}
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_dispatch_total = 0
        if self.spec is not None:
            from .spec import build_proposer
            self.proposer = build_proposer(self.spec, cfg, self.s_buckets,
                                           self.c_buckets, device=dev0)

        # --- in-flight dispatches -------------------------------------
        # Each record is a dispatch (``kind`` decode or prefill) whose
        # results have not been fetched yet, in enqueue order, which is the
        # order the device runs them in. A decode chains off the previous
        # one's on-device token/key arrays and a prefill chunk's inputs are
        # all on the host, so the fetch of one dispatch's results overlaps
        # the next dispatches' execution instead of gating it. ``seq``
        # numbers the records: a deferred page release names the newest one
        # it has to outlive (see _free_slot).
        self._inflight: Deque[Dict[str, Any]] = collections.deque()
        self._dispatch_seq = 0
        # a DYN_PROFILE_DIR capture is running (set by the engine thread's
        # loop before each step): see _count_model_work
        self.capturing = False
        self._deferred_release: List[Tuple[str, int]] = []  # (seq_id, seq)
        self._pending_seeds: List[Tuple[int, int]] = []
        # seq_id -> admission's prefix-restore length, consumed by step()'s
        # tagging post-pass on the sequence's first output
        self._pending_prefix_hit: Dict[str, int] = {}
        # --- layer-streamed KV injection (disagg receive path) --------
        # seq_id -> in-flight stream-inject state: pool pages are leased
        # at begin (unsealed, unregistered — invisible to attention and
        # prefix matching), per-layer scatters enqueue as layers arrive,
        # and only finish seals/publishes the blocks. Abort releases the
        # pages untouched-by-anyone: a torn stream can never leave a
        # half-written block reachable.
        self._stream_injects: Dict[str, Dict[str, Any]] = {}
        # --- placement-driven h2d prefetch staging --------------------
        # seq_hash -> (k_dev, v_dev) device blocks uploaded by
        # stage_prefetch (asyncio thread) while the request queues at the
        # slot gate; admission's restore consumes them with a d2d scatter
        # instead of a critical-path h2d. Bounded FIFO (insertion-ordered
        # dict), guarded by _h2d_stage_lock (two-thread access).
        self._h2d_stage: Dict[int, Tuple[Any, Any]] = {}
        self._h2d_stage_lock = threading.Lock()
        # hashes a prefetch was REQUESTED for: admission counts a host
        # upload on one of these as a prefetch stall (vs a plain miss)
        self._h2d_requested: set = set()
        self._last_final_tok = None   # device [B] from the last decode
        self._last_prefill_tok = None  # device [Bp] from the last chunk
        # a completed prompt's first token joins the chained decode on the
        # device: lane l of the chunk becomes slot lane_slot[l]'s token
        # (an index past the batch is dropped)
        self._join_fn = jax.jit(
            lambda final_tok, chunk_tok, lane_slot:
            final_tok.at[lane_slot].set(chunk_tok, mode="drop"),
            out_shardings=self._rep_sharding)
        # multi-host lockstep: called with (kind, meta, arrays) right before
        # every device dispatch so follower processes can replay it
        self.dispatch_hook: Optional[Any] = None

        # --- KV paging lane (llm/kvpage/) -----------------------------
        # long-context requests the pool/max_context would reject are
        # served with bounded device residency: chunked prefill demotes
        # sealed blocks to the host tier, decode streams them back per
        # layer through staged uploads (docs/long_context.md)
        self.kvpager = None
        pcfg = PagedConfig.resolve(cfg)
        if pcfg is not None:
            from ..llm.kvpage.programs import PagedPrograms
            from ..llm.kvpage.runner import PagedEngine
            why = PagedPrograms.validate(cfg)
            if why is not None:
                raise ValueError(f"KV paging does not support {why}")
            if self.tiered is None:
                raise ValueError("KV paging needs a host tier to demote "
                                 "into (set host_cache_blocks > 0)")
            if self.spec is not None:
                raise ValueError("KV paging does not compose with "
                                 "speculative decoding")
            self.kvpager = PagedEngine(self, pcfg)

        if cfg.warmup:
            self.warmup()

    def _refuse_configured(self, impl: str, refusal) -> None:
        """Raise ``refusal(what)`` for the first feature this engine was
        CONFIGURED with that moves, matches or re-enters K/V blocks (the
        calls that do so at run time go through
        :meth:`_refuse_block_moves`)."""
        cfg = self.cfg
        for on, what in (
                (cfg.sp > 1 or impl == "ring", "sp > 1 / ring prefill"),
                (cfg.host_cache_blocks > 0 or cfg.disk_cache_blocks > 0,
                 "the host / disk KV tiers (host_cache_blocks, "
                 "disk_cache_blocks), and with them cluster "
                 "write-through, tier prefetch and the paged "
                 "long-context lane"),
                (cfg.cluster_writethrough, "cluster write-through"),
                (self.spec is not None, "speculative decoding (verify)"),
                (jax.process_count() > 1, "multi-host serving")):
            if on:
                raise ValueError(refusal(what))

    @staticmethod
    def _indexer_refusal(what: str) -> str:
        return (f"a model with an indexer (learned top-k attention) does "
                f"not run with {what}: it would move KV blocks without "
                f"their index keys, and a block that comes back without "
                f"them selects wrongly without any error")

    @staticmethod
    def _latent_refusal(what: str) -> str:
        return (f"a model with latent attention (the latent cache kind: one "
                f"compressed row a token for all heads, its rotary key and "
                f"its compressed vector in pools of two widths) does not "
                f"run with {what}: that path's cache I/O knows K and V "
                f"pools of one shape, kv heads x head_dim")

    @staticmethod
    def _two_caches_refusal(what: str) -> str:
        return (f"a model whose window layers keep a cache of their own "
                f"(a second page pool and page table a lane) does not run "
                f"with {what}: it would move, match or re-enter blocks of "
                f"the global cache without the window layers' keys")

    @staticmethod
    def _state_refusal(what: str) -> str:
        return (f"a model with state-space layers (a recurrent state a lane "
                f"beside the K/V cache) does not run with {what}: it would "
                f"move, match or re-enter K/V blocks without the state at "
                f"their boundary, or advance a state that cannot be rolled "
                f"back")

    @staticmethod
    def _conv_refusal(what: str) -> str:
        return (f"a model with gated short-convolution layers (a "
                f"convolution tail a lane beside the K/V cache) does not "
                f"run with {what}: it would move, match or re-enter K/V "
                f"blocks without the tail at their boundary, or advance a "
                f"tail that cannot be rolled back")

    @property
    def _lane_state_refusal(self):
        """The refusal that names what this model keeps a lane."""
        return (self._conv_refusal if self.cfg.model.has_conv
                else self._state_refusal)

    def _refuse_block_moves(self, what: str) -> None:
        """Refuse a feature that moves K/V blocks off or onto the device
        pool for a model that keeps more than K and V on those pages (an
        indexer's keys), keeps a second cache beside them (a per-kind
        model's window layers), keeps a state a lane that no block holds
        (state-space layers' state, gated short-convolution layers' tail) or
        keeps its blocks in pools of two widths (the latent cache kind)."""
        if self.cfg.model.has_indexer:
            raise ValueError(self._indexer_refusal(what))
        if self.cfg.model.has_window:
            raise ValueError(self._two_caches_refusal(what))
        if self.cfg.model.has_state:
            raise ValueError(self._lane_state_refusal(what))
        if self.cfg.model.has_latent:
            raise ValueError(self._latent_refusal(what))

    def _idx(self) -> Dict[str, Any]:
        """The programs' further pool operands: the index-key pool of a
        model with an indexer, the window cache's two pools of a per-kind
        model, the state and convolution-tail pools of a model with
        state-space layers, nothing for every other model (whose programs
        therefore compile to what they always did)."""
        if self.win is not None:
            return {"wk_pool": self.wk_pool, "wv_pool": self.wv_pool}
        if self.c_pool is not None:
            return self._state_pools()
        return {} if self.i_pool is None else {"i_pool": self.i_pool}

    def _moe_dispatch_forms(self) -> str:
        """What the routed experts' dispatch is in this engine's programs
        (``moe.dispatch_form``, the rule ``moe_ffn`` itself asks), as
        ``dyn_engine_info{moe_dispatch}`` shows it: ``decode:<form>``
        (``by_hit``: the program holds both forms) and
        ``chunk:<form>`` with the rows of the chunk programs that take it
        (``chunk:dense32-512,sorted1024-1024``); ``none`` for a dense model.
        Reported, never used to select."""
        m = self.cfg.model
        if not m.num_experts:
            return "none"
        if self.cfg.pp > 1:
            return "decode:dense,chunk:dense"     # moe_ffn_in_stage
        rows = sorted({b * c for b in self.b_buckets for c in self.c_buckets})
        runs = [(f, list(g)) for f, g in itertools.groupby(
            rows, key=self._moe_form)]
        chunk = (runs[0][0] if len(runs) == 1 else ",".join(
            f"{f}{g[0]}-{g[-1]}" for f, g in runs))
        return f"decode:{self._decode_moe_form},chunk:{chunk}"

    def _moe_form(self, rows: int, masked: bool = False) -> str:
        """``moe.dispatch_form`` of a call of ``rows`` rows of this model on
        this mesh (``masked``: a decode step, which knows its busy rows)."""
        from ..models.moe import dispatch_form
        m = self.cfg.model
        return dispatch_form(rows, m.experts_per_token, m.num_experts,
                             self._moe_share, self.mesh, m.expert_width,
                             masked=masked)

    @property
    def _moe_share(self) -> float:
        """The part of the router's outputs that are experts this chip
        holds (identity experts are outputs no chip holds)."""
        m = self.cfg.model
        return m.num_experts / m.router_width

    @cached_property
    def _decode_moe_form(self) -> Optional[str]:
        """The routed experts' dispatch in the decode programs: ``sorted``,
        ``dense``, or ``by_hit`` (both forms, chosen each call on the device
        from the experts the busy rows hit; its calls that went sorted ride
        a ``sorted`` column of ``packed``). None: no routed call that knows
        its busy rows (a dense model, pp > 1)."""
        if not self.cfg.model.num_experts or self.cfg.pp > 1:
            return None
        return self._moe_form(self.cfg.max_batch, masked=True)

    def _state_pools(self) -> Dict[str, Any]:
        """The state pools this model has, by their program operand: both
        of a state-space model, the tail pool alone of a gated short
        convolution."""
        return {n: p for n, p in (("s_pool", self.s_pool),
                                  ("c_pool", self.c_pool)) if p is not None}

    def _program_extras(self):
        """-> (jit options, out_shardings tail, the columns ``packed``
        carries behind token and log-probability: ``experts_hit``,
        ``held``, the assignments to held experts, under a chip's share, and
        ``zero``, those to identity experts, where the router has them) of
        this model's bucket programs."""
        m = self.cfg.model
        cols = (("experts_hit",) if m.num_experts and (
            m.has_indexer or self.cfg.pp == 1) else ()) + (
            ("held",) if m.router_experts else ()) + (
            ("zero",) if m.zero_experts else ())
        if m.has_indexer:
            return ({"donate_argnames": ("i_pool",)}, (self.idx_sharding,),
                    cols)
        if m.has_window:
            return ({"donate_argnames": ("wk_pool", "wv_pool")},
                    (self.kv_sharding, self.kv_sharding), cols)
        if m.has_state:
            names = tuple(self._state_pools())
            return ({"donate_argnames": names},
                    (self.state_sharding,) * len(names), cols)
        return {}, (), cols

    @cached_property
    def _packed_cols(self) -> Tuple[str, ...]:
        """The columns of ``packed`` behind token and log-probability
        (:meth:`_program_extras`), as every fetch reads them."""
        return self._program_extras()[2]

    @cached_property
    def _decode_routes_busy(self) -> bool:
        """Whether a decode step's routed calls dispatch and count their
        busy rows alone (``moe.heeds_active``)."""
        from ..models.moe import heeds_active
        form = self._decode_moe_form
        return form is not None and heeds_active(form, self._moe_share)

    @cached_property
    def _decode_cols(self) -> Tuple[str, ...]:
        """The columns of a DECODE program's ``packed``: a program that
        holds both dispatch forms adds ``sorted``, the routed layers of the
        step that took the sorted one."""
        return self._packed_cols + (
            ("sorted",) if self._decode_moe_form == "by_hit" else ())

    def _take_pools(self, pools) -> None:
        """(k_pool, v_pool[, i_pool | wk_pool, wv_pool | s_pool, c_pool])
        as a program returned them."""
        self.k_pool, self.v_pool, *rest = pools
        if self.win is not None:
            self.wk_pool, self.wv_pool = rest
        elif self.c_pool is not None:
            *s_pool, self.c_pool = rest
            if s_pool:
                self.s_pool, = s_pool
        elif rest:
            self.i_pool, = rest

    def _win_dummies(self, Bp: int, C: int) -> Dict[str, Any]:
        """Warm-up's window operands of a prefill program (nothing valid to
        read, writes to scratch page 0), as serving passes them."""
        if self.win is None:
            return {}
        Sw = WindowPages.chunk_read_pages(self.win.window, C,
                                          self.page_size) * self.page_size
        return {"w_write": np.zeros((Bp, C), np.int32),
                "w_pages": np.zeros((Bp, Sw // self.page_size), np.int32),
                "w_pos": np.zeros((Bp, Sw), np.int32),
                "w_valid": np.zeros((Bp, Sw), bool)}

    def _ssm_rows(self, Bp: int) -> Dict[str, Any]:
        """The state operands of a prefill program of ``Bp`` rows with no
        row filled in: every row names a lane past the pool (writes
        nothing) and holds no real token. Warm-up passes them as they are;
        a dispatch fills in its rows."""
        if self.c_pool is None:
            return {}
        return {"s_lanes": np.full(Bp, self.cfg.max_batch, np.int32),
                "s_reset": np.zeros(Bp, bool),
                "s_valid": np.zeros(Bp, np.int32)}

    def _count_state_work(self, kind: str, lane_steps: int, served: int,
                          tokens: int, resets: int, captured: bool) -> None:
        """Host counters of what a dispatch made the state-space layers do
        (one layer's worth): ``lane_steps`` whose state it read and wrote
        (decode: every lane of the pool, each step), those of lanes it
        ``served``, the real ``tokens`` through the mixers, the lanes it
        started from a zero state; and the traced dispatches' share while a
        ``DYN_PROFILE_DIR`` capture runs, as :meth:`_count_model_work`."""
        st = self.stage
        work = {st.ssm_lane_steps: float(lane_steps),
                st.ssm_active_lane_steps: float(served),
                st.ssm_tokens: float(tokens)}
        for counter, amount in work.items():
            counter.inc(kind, amount=amount)
        if resets:
            st.ssm_state_resets.inc(amount=float(resets))
        if captured:
            seen_by = st.profile_captured_work
            for counter, amount in work.items():
                seen_by.inc(counter.name, kind, amount=amount)
            seen_by.inc("dispatches", kind)
            seen_by.inc("tokens", kind, amount=float(tokens))

    def _count_attn_pages(self, lengths: np.ndarray, served: np.ndarray,
                          P: int) -> None:
        """Host counters of the pages a decode dispatch makes the paged dma
        kernel copy a pool, and of the pages of the blocks it is in for them
        (``ops.attention.paged_live_pages``, the kernel's own arithmetic):
        every lane of the program as the kernel is handed it (``lengths``
        [B], a token longer each step; a lane that is not ``served`` [B]
        reaches the kernel as length 0 and is skipped: no page), times the
        attention layers of a kind. Beside them the lanes the kernel was run
        over and those of them it skipped. All mirrored while a
        ``DYN_PROFILE_DIR`` capture runs, as :meth:`_count_state_work`."""
        st = self.stage
        steps = self.cfg.decode_steps
        at = np.where(served[:, None], lengths[:, None] + np.arange(steps), 0)
        for kind, (window, layers) in self._attn_calls.items():
            live, visited = paged_live_pages(at, P, self.page_size,
                                             PAGES_PER_BLOCK, window)
            for counter, n in ((st.attn_pages_live, live.sum()),
                               (st.attn_pages_visited, visited.sum()),
                               (st.attn_lane_calls, at.size),
                               (st.attn_lane_calls_skipped,
                                steps * int((~served).sum()))):
                amount = float(n * layers)
                counter.inc(kind, amount=amount)
                if self.capturing:
                    st.profile_captured_work.inc(counter.name, kind,
                                                 amount=amount)

    def _release_seq(self, seq_id: str) -> None:
        self.pool.release(seq_id)
        if self.win is not None:
            self.win.release(seq_id)

    def _ensure_pages(self, seq_id: str, total_tokens: int) -> None:
        """Room for ``total_tokens`` of the sequence in every cache."""
        self.pool.ensure_pages(seq_id, total_tokens)
        if self.win is not None:
            self.win.ensure(seq_id, total_tokens)

    def _window_fetched(self, seq_id: str, position: int,
                        phase: str) -> None:
        """A dispatch (``phase``: prefill / decode) of the sequence whose
        first query stood at ``position`` has been fetched: the window pages
        wholly behind its window go back (cache.WindowPages.release_behind).
        A prompt longer than the window gives pages back while it is still
        being prefilled: a lane holds a window and the chunks in flight
        (three at most: ``_can_admit``), whatever the prompt's length."""
        n = self.win.release_behind(seq_id, position)
        if n:
            self.stage.kv_window_pages_released.inc(phase, amount=float(n))

    @staticmethod
    def _latent_key_blocks(q_pos: np.ndarray, k_pos: np.ndarray,
                           k_valid: np.ndarray, heads: int) -> Tuple[int, int]:
        """(key blocks of the grid, those copied) of ONE latent flash call
        of a chunk program, from the positions and validity the dispatch
        hands it ([Bp, C], [Bp, S], [Bp, S]) by the call's own block shape
        and table (``ops.attention.latent_flash_blocks`` /
        ``latent_flash_fetch``)."""
        return latent_flash_copies(latent_flash_fetch(
            q_pos, k_pos, k_valid,
            *latent_flash_blocks(q_pos.shape[1], k_pos.shape[1], heads),
            xp=np))

    def _count_model_work(self, kind: str, spans, hit,
                          captured: bool = False, S: int = 0,
                          held: Optional[float] = None,
                          key_blocks: Optional[Tuple[int, int]] = None,
                          steps: int = 1,
                          sorted_calls: Optional[float] = None,
                          zero: Optional[float] = None) -> None:
        """Host counters of what a dispatch made the experts and the
        indexer do. ``spans``: (first position, queries) per lane; a query
        at position p sees p + 1 keys. ``hit``: experts hit, read from the
        dispatch's packed result (None for a dense model). ``captured``:
        the dispatch was enqueued while a ``DYN_PROFILE_DIR`` capture ran,
        so its device time is in the trace: the same amounts also go to
        ``dyn_profile_captured_work_total``, with the dispatch itself and
        its tokens, so that a reader of the trace knows the work of the
        traced dispatches themselves and not a window's mean; ``S``, the
        dispatch's context bucket, says whether its program scored at all
        (a bucket no longer than ``index_topk`` selects every visible key
        by construction and skips the scoring): ``scored_keys``. ``held``
        (a chip's share of the experts): the part of the real tokens'
        assignments that went to experts held here, which is then what
        ``dyn_moe_assignments_total`` counts (computed here), while
        ``dyn_moe_routed_assignments_total`` counts all of them.
        ``key_blocks``: :meth:`_latent_key_blocks` of a chunk dispatch whose
        attention is the latent flash call. ``steps``: the steps of a decode
        dispatch (``dyn_moe_layer_calls_total``); ``sorted_calls``: those of
        its routed-layer calls that were dispatched sorted
        (``dyn_moe_sorted_calls_total``). ``zero``: the real tokens'
        assignments that went to identity experts
        (``dyn_moe_zero_assignments_total``)."""
        m = self.cfg.model
        if not (m.num_experts or m.has_indexer):
            return
        tokens = sum(n for _, n in spans)
        work = {}
        if m.num_experts:
            routed = float(tokens * m.experts_per_token * m.routed_layers)
            work[self.stage.moe_assignments] = routed
            if held is not None:
                work[self.stage.moe_assignments] = float(held)
                work[self.stage.moe_routed_assignments] = routed
            if zero is not None:
                work[self.stage.moe_zero_assignments] = float(zero)
            if m.shared_experts:
                work[self.stage.moe_shared_rows] = float(
                    tokens * m.routed_layers)
            if hit is not None:
                work[self.stage.moe_experts_hit] = float(hit)
                # the calls those experts were hit in: every routed layer,
                # each step of a decode dispatch, once of a chunk
                work[self.stage.moe_layer_calls] = float(
                    m.routed_layers * steps)
                if sorted_calls is not None:
                    work[self.stage.moe_sorted_calls] = float(sorted_calls)
        if m.has_indexer:
            k = m.index_topk
            seen = sel = 0
            for p0, n in spans:
                seen += n * p0 + n * (n + 1) // 2
                # min(p + 1, k) summed over p = p0 .. p0 + n - 1
                below = max(0, min(n, k - p0))
                sel += (below * p0 + below * (below + 1) // 2
                        + (n - below) * k)
            work[self.stage.sparse_attn_context] = float(seen)
            work[self.stage.sparse_attn_selected] = float(sel)
        if m.has_latent:
            # what the dispatch's attention had to read and multiply at
            # least (one layer's worth; where a published layer is two
            # sublayers with a row each, ONE sublayer's): the latent rows (a
            # decode query reads its lane's visible rows; a chunk's queries
            # share their lane's, read once) and the (query, visible key)
            # pairs
            pairs = [n * p0 + n * (n + 1) // 2 for p0, n in spans]
            work[self.stage.attn_latent_pairs] = float(sum(pairs))
            work[self.stage.attn_latent_keys] = float(
                sum(p0 + n for p0, n in spans) if kind == "prefill"
                else sum(pairs))
        blocks = dict(zip(("bucket", "copied"), key_blocks or ()))
        for counter, amount in work.items():
            counter.inc(kind, amount=amount)
        for state, amount in blocks.items():
            self.stage.attn_latent_key_blocks.inc(kind, state,
                                                  amount=float(amount))
        if captured:
            seen_by = self.stage.profile_captured_work
            for counter, amount in work.items():
                seen_by.inc(counter.name, kind, amount=amount)
            for state, amount in blocks.items():
                seen_by.inc(f"latent_key_blocks_{state}", kind,
                            amount=float(amount))
            seen_by.inc("dispatches", kind)
            seen_by.inc("tokens", kind, amount=float(tokens))
            if m.has_indexer and S > m.index_topk:
                seen_by.inc("scored_keys", kind, amount=float(seen))
                seen_by.inc("scoring_dispatches", kind)
                seen_by.inc("scoring_tokens", kind, amount=float(tokens))
            if m.has_window:
                # what the traced dispatches' attention had to read and
                # multiply at least, by kind of layer (one layer's worth):
                # ``*_keys`` the keys read (a decode query reads its lane's
                # visible keys; a chunk's queries share one lane's, read
                # once), ``*_pairs`` the (query, visible key) pairs
                W = m.sliding_window
                attn = dict.fromkeys(("attn_full_keys", "attn_full_pairs",
                                      "attn_window_keys",
                                      "attn_window_pairs"), 0)
                for p0, n in spans:
                    full = n * p0 + n * (n + 1) // 2
                    below = max(0, min(n, W - p0))  # queries that see all
                    win = (below * p0 + below * (below + 1) // 2
                           + (n - below) * W)
                    attn["attn_full_pairs"] += full
                    attn["attn_window_pairs"] += win
                    chunk = kind == "prefill"
                    attn["attn_full_keys"] += p0 + n if chunk else full
                    attn["attn_window_keys"] += (min(p0, W - 1) + n if chunk
                                                 else win)
                for name, amount in attn.items():
                    seen_by.inc(name, kind, amount=float(amount))

    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Compile every bucket program up front on dummy inputs.

        Without this, the first request that lands in a fresh (lanes,
        chunk, context) bucket pays a full XLA compile mid-serving — a
        multi-second TTFT outlier on CPU, tens of seconds on TPU. All
        dummy writes go to scratch page 0 (what padded lanes use), so
        engine state is untouched. Runs identically on multi-host leader
        and followers (same ctor, same dummy data — lockstep holds).
        """
        cfg = self.cfg
        t0 = time.monotonic()
        n = 0
        s = self.sampling
        B = cfg.max_batch
        # argument TYPES must match serving exactly (host numpy for tables/
        # lengths/sampling vectors, committed device arrays for keys and
        # tokens): jit cache keys include arg placement, so a differently
        # placed warmup would compile a different program than the serving
        # dispatch uses
        # dynalint: ok(flow-accounting) B dummy token ids, once at warm-up
        zb = global_put(np.zeros(B, np.int32), self._rep_sharding)
        zf = np.zeros(B, np.float32)
        ones = np.ones(B, np.int32)
        fresh = np.zeros(B, bool)
        act = np.zeros(B, bool)
        for S in self.s_buckets:
            fn = self._decode_fn(S)
            pt = np.zeros((B, S // self.page_size), np.int32)
            # one program serves chained and unchained dispatches alike
            # (_run_decode_program commits host tokens to the sharding the
            # previous dispatch's on-device tokens carry)
            _, _, _, kp, vp, self.gen_counts, *ip = fn(
                self.params, zb, self.k_pool, self.v_pool, pt, ones,
                s.temperature, s.top_p, s.top_k, s.key,
                self.gen_counts, fresh, act, s.freq_pen, s.pres_pen,
                **self._idx(),
                **({} if self.win is None else {"w_tables": pt}))
            self._take_pools((kp, vp, *ip))
            n += 1
            if self.spec is not None:
                # spec enabled: also pre-compile every (S, K-bucket) verify
                # program (spec off compiles zero of these)
                U = self.spec.k_max + 1
                for K in self.spec.k_buckets:
                    vfn = self._verify_fn(S, K)
                    (_, _, self.k_pool, self.v_pool, self.gen_counts) = vfn(
                        self.params, np.zeros((B, K + 1), np.int32),
                        self.k_pool, self.v_pool, pt, ones,
                        s.temperature, s.top_p, s.top_k, s.key,
                        self.gen_counts, fresh, act, s.freq_pen, s.pres_pen,
                        np.zeros((B, U), np.int32), np.zeros((B, U), bool))
                    n += 1
        for Bp in self.b_buckets:
            for C in self.c_buckets:
                for S in self.s_buckets:
                    # the form a text chunk takes (_chunk_form); the other
                    # compiles on first use
                    fn = self._prefill_fn(Bp, C, S)
                    zt = np.zeros((Bp, C), np.int32)
                    keys = s.key[jnp.asarray(np.zeros(Bp, np.int32))]
                    _, tok, _, *pools = fn(
                        self.params, zt, zt, self.k_pool, self.v_pool,
                        zt, np.zeros((Bp, S), np.int32),
                        np.zeros((Bp, S), np.int32),
                        np.zeros((Bp, S), bool),
                        np.zeros(Bp, np.int32), np.zeros(Bp, np.float32),
                        np.ones(Bp, np.float32), np.zeros(Bp, np.int32),
                        keys, **self._idx(), **self._win_dummies(Bp, C),
                        **self._ssm_rows(Bp))
                    self._take_pools(pools)
                    n += 1
            # a chunk of this many lanes handing first tokens to a decode
            self._join_fn(zb, tok, np.full(Bp, B, np.int32))
        if self.proposer is not None:
            n += self.proposer.warmup()   # draft model's own bucket set
        # dynalint: ok(host-sync) warmup barrier: block ONCE at startup so
        # every bucket compile lands before serving, not on a request
        jax.block_until_ready(self.k_pool)
        # warmup's own compiles are counted; the first SERVING dispatch
        # must not be skipped by the goodput meter on their account
        self._just_compiled = False
        log.info("warmup compiled %d bucket programs in %.1fs",
                 n, time.monotonic() - t0)

    # ------------------------------------------------------------------
    # compiled program builders
    # ------------------------------------------------------------------
    def _record_compile(self, kind: str, seconds: float) -> None:
        """A fresh bucket program's first call just traced+XLA-compiled:
        count it (compile plane) and flag the enclosing dispatch so the
        goodput meter skips its wall time."""
        from ..utils.roofline import record_compile

        record_compile(kind, seconds)
        self._just_compiled = True

    def _take_compiled_flag(self) -> bool:
        flag = self._just_compiled
        self._just_compiled = False
        return flag

    def _decode_fn(self, S: int):
        """Multi-step decode: N autoregressive iterations inside one jitted
        lax.scan — indices computed on device from page tables, sampled token
        fed straight back in. Lanes that hit a finish condition mid-scan
        overshoot harmlessly into their own pre-allocated pages; the host
        trims afterwards.

        Returns (packed [N, B, 2] f32 (token, logprob) — ONE host fetch per
        dispatch — plus the final token [B] i32, key, pools, all of which
        stay on device so the next dispatch can chain off them without a
        host round-trip)."""
        if S not in self._decode_fns:
            cfg = self.cfg
            N = cfg.decode_steps
            impl = self.decode_attn_impl
            mesh = self.mesh
            rep, kv = self._rep_sharding, self.kv_sharding

            # out_shardings pinned so the pools keep the canonical kv
            # sharding across programs: without this, XLA may emit an
            # equivalent-but-differently-spec'd sharding and every *other*
            # bucket program compiles a second variant against it
            B = self.cfg.max_batch
            jit_kw, out_tail, _ = self._program_extras()
            hit_col = self._decode_cols
            windowed, stateful = cfg.model.has_window, cfg.model.has_state

            @partial(jax.jit, donate_argnums=(2, 3, 10), **jit_kw,
                     out_shardings=(rep, rep, rep, kv, kv, rep, *out_tail))
            def step(params, tokens, k_pool, v_pool, page_tables, lengths,
                     temp, top_p, top_k, key, counts, fresh, active,
                     freq_pen, pres_pen, i_pool=None, wk_pool=None,
                     wv_pool=None, w_tables=None, s_pool=None, c_pool=None):
                # lanes whose sequence just entered decode restart their
                # generated-token counts at one-hot(first generated token);
                # chained dispatches pass fresh all-False
                with llama.scope("sample"):
                    lane = jnp.arange(B)
                    counts = jnp.where(
                        fresh[:, None],
                        jnp.zeros_like(counts).at[lane, tokens].add(1),
                        counts)
                    act = active.astype(jnp.int32)

                def one(carry, _):
                    tokens, lengths, k_pool, v_pool, key, counts, *ip = carry
                    stats: Dict[str, Any] = {}
                    if cfg.pp > 1:
                        # in-stage kernels: flash per pp×tp shard (the
                        # paged kernel would need page tables threaded
                        # into the stage loop — flash covers T=1 decode)
                        logits, k_pool, v_pool = llama.forward_decode_pp(
                            params, cfg.model, tokens, k_pool, v_pool,
                            page_tables, lengths, mesh=mesh,
                            attn_impl=("flash" if impl == "pallas"
                                       else "xla"))
                    else:
                        logits, k_pool, v_pool, *ip = llama.forward_decode(
                            params, cfg.model, tokens, k_pool, v_pool,
                            page_tables, lengths, attn_impl=impl, mesh=mesh,
                            stats=stats, active=active,
                            **({"win": (*ip, w_tables)} if windowed
                               # a lane this dispatch does not serve keeps
                               # its state: it cannot be trimmed afterwards
                               else {"ssm": (*ip, active)} if stateful
                               else {"i_pool": ip[0]} if ip else {}))
                    with llama.scope("sample"):
                        lg = apply_penalties(logits[:, 0], counts, freq_pen,
                                             pres_pen)
                        tok, logp, new_key = sample(lg, temp, top_p, top_k,
                                                    key, active)
                        # only lanes ACTIVE in this dispatch count their
                        # sample: a deferred (pool-pressure) lane's garbage
                        # tokens must not poison its penalties when it
                        # resumes
                        counts = counts.at[lane, tok].add(act)
                        lengths = lengths + 1
                    ys = (tok, logp) + tuple(stats[c] for c in hit_col)
                    return ((tok, lengths, k_pool, v_pool, new_key,
                             counts, *ip), ys)

                carry = (tokens, lengths, k_pool, v_pool, key, counts,
                         *((wk_pool, wv_pool) if windowed
                           else tuple(p for p in (s_pool, c_pool)
                                      if p is not None) if stateful
                           else () if i_pool is None else (i_pool,)))
                ((tok, lengths, k_pool, v_pool, key, counts, *ip),
                 (toks, logps, *hit)) = jax.lax.scan(one, carry, None,
                                                     length=N)
                # token ids < 2^24 are exact in f32, so one packed array
                # (one host fetch) carries both streams losslessly; a
                # routed model's experts hit in each step ride a third
                # column (the same number on every lane)
                with llama.scope("sample"):
                    cols = [toks.astype(jnp.float32), logps]
                    for h in hit:
                        cols.append(jnp.broadcast_to(
                            h.astype(jnp.float32)[:, None], toks.shape))
                    packed = jnp.stack(cols, -1)
                return (packed, tok, key, k_pool, v_pool, counts, *ip)

            from ..utils.roofline import instrument_compile
            self._decode_fns[S] = instrument_compile(
                "decode", step, self._record_compile)
        return self._decode_fns[S]

    def _chunk_form(self, C: int, aligned: bool = True,
                    mm: bool = False) -> str:
        """How a chunk program of bucket ``C`` writes its new K/V rows:
        ``page`` (a window a page run: every lane's chunk starts on a page's
        first slot, ``aligned``, which the host checks as it builds the
        chunk, and the bucket is whole page runs or shorter than a page)
        or ``row`` (a window a token: every other chunk, an image wave)."""
        pg = self.page_size
        m = self.cfg.model
        fold = max(m.kv_fold,
                   llama.index_fold(m) if m.has_indexer else 1)
        return ("page" if self.prefill_kv_write == "page" and aligned
                and not mm and (C % pg == 0 or C < pg)
                and min(C, pg) % fold == 0 else "row")

    def _prefill_fn(self, Bp: int, C: int, S: int, mm: bool = False,
                    form: Optional[str] = None):
        """Batched prefill: Bp sequence chunks advance in ONE dispatch (the
        whole admission wave prefills together instead of one dispatch — and
        one host round-trip — per sequence). Every lane computes the LM head
        only at its own last chunk position (``logits_idx``) and samples; the
        host keeps results only for lanes whose prompt completed. Padded
        lanes write to scratch page 0 with nothing valid to read. ``form``:
        :meth:`_chunk_form` of the chunk (a text chunk's by default)."""
        form = form or self._chunk_form(C, mm=mm)
        if (Bp, C, S, mm, form) not in self._prefill_batch_fns:
            cfg = self.cfg
            impl = {"pallas": "flash", "ring": "ring"}.get(
                self.attn_impl, "xla")
            mesh = self.mesh
            rep, kv = self._rep_sharding, self.kv_sharding

            # pp microbatching: shared rule with forward_decode_pp
            M = llama.pp_microbatches(Bp, cfg.pp)
            page = self.page_size
            jit_kw, out_tail, hit_col = self._program_extras()

            @partial(jax.jit, donate_argnums=(3, 4), **jit_kw,
                     out_shardings=(rep, rep, rep, kv, kv, *out_tail))
            def fn(params, tokens, positions, k_pool, v_pool, write_idx,
                   read_idx, read_pos, read_valid, last_i, temp, top_p,
                   top_k, keys, ov_vals=None, ov_mask=None, q_span=None,
                   read_span=None, i_pool=None, wk_pool=None, wv_pool=None,
                   w_write=None, w_pages=None, w_pos=None, w_valid=None,
                   s_pool=None, c_pool=None, s_lanes=None, s_reset=None,
                   s_valid=None):
                stats: Dict[str, Any] = {}
                ip = ()
                if cfg.pp > 1:
                    def mb(a):
                        return a.reshape(M, Bp // M, *a.shape[1:])
                    logits, k_pool, v_pool = llama.forward_pp(
                        params, cfg.model, mb(tokens), mb(positions),
                        k_pool, v_pool, mb(write_idx), mb(read_idx),
                        mb(read_pos), mb(read_valid), mesh,
                        logits_idx=mb(last_i),
                        attn_impl=("flash" if impl == "flash" else "xla"))
                    logits = logits.reshape(Bp, 1, -1)
                else:
                    # read slots come from PagePool.read_slots: whole
                    # pages in order (S is a page multiple), so the
                    # context is gathered by page
                    with llama.scope("attn"):
                        read_pages = read_idx[:, ::page] // page
                    # ... and a chunk that starts on a page's first slot
                    # writes its rows by page, a run every `page` tokens
                    write_pages = None
                    if form == "page":
                        with llama.scope("kv_write"):
                            write_pages = write_idx[:, ::page] // page
                    # image waves run the xla attention path: the span
                    # or-mask has no Pallas kernel input (text waves keep
                    # the fast path — mm programs compile separately)
                    logits, k_pool, v_pool, *ip = llama.forward(
                        params, cfg.model, tokens, positions, k_pool, v_pool,
                        write_idx, read_idx, read_pos, read_valid,
                        attn_impl="xla" if mm else impl, mesh=mesh,
                        logits_idx=last_i, stats=stats,
                        **({} if i_pool is None else {"i_pool": i_pool}),
                        **({} if wk_pool is None else {"win": (
                            wk_pool, wv_pool, w_write, w_pages, w_pos,
                            w_valid)}),
                        **({} if c_pool is None else {"ssm": (
                            *(p for p in (s_pool, c_pool) if p is not None),
                            s_lanes, s_reset, s_valid)}),
                        embed_override=((ov_vals, ov_mask) if mm else None),
                        attn_spans=((q_span, read_span) if mm else None),
                        read_pages=read_pages, write_pages=write_pages)
                with llama.scope("sample"):
                    tok, logp, new_keys = sample(
                        logits[:, 0], temp, top_p, top_k, keys)
                    cols = [tok.astype(jnp.float32), logp]
                    for c in hit_col:
                        cols.append(jnp.broadcast_to(
                            stats[c].astype(jnp.float32), logp.shape))
                    packed = jnp.stack(cols, -1)
                return (packed, tok, new_keys, k_pool, v_pool, *ip)

            from ..utils.roofline import instrument_compile
            self._prefill_batch_fns[(Bp, C, S, mm, form)] = (
                instrument_compile("prefill", fn, self._record_compile))
        return self._prefill_batch_fns[(Bp, C, S, mm, form)]

    def _verify_fn(self, S: int, K: int):
        """Speculative-decoding verify program: ONE forward over K+1
        positions per lane against the paged pool (the prefill machinery —
        device-computed write/read indices off the page tables — at decode
        membership), then in-program verify sampling. Column 0 of
        ``tokens`` is each lane's last committed token (whose KV this
        dispatch writes, exactly like single-token decode); columns 1..K
        are draft tokens. The host accepts/rejects afterwards; rejected
        tokens are never accounted, so their stale KV slots are overwritten
        by the next dispatch (the standard decode write-then-read
        contract). ``upd_tok``/``upd_mask`` fold the PREVIOUS round's
        committed tokens into the penalty counts; ``fresh`` lanes restart
        their counts first (same mechanic as the decode scan)."""
        if (S, K) not in self._verify_fns:
            from .sampling import spec_verify

            cfg = self.cfg
            impl = "flash" if self.decode_attn_impl == "pallas" else "xla"
            mesh = self.mesh
            rep, kv = self._rep_sharding, self.kv_sharding
            B = cfg.max_batch
            T = K + 1
            page = self.page_size

            # upd_tok/upd_mask width is k_max+1 (the most one round can
            # commit), NOT T: a lane can emit more tokens under a wide
            # bucket than the next round's narrower bucket could carry
            @partial(jax.jit, donate_argnums=(2, 3, 10),
                     out_shardings=(rep, rep, kv, kv, rep))
            def fn(params, tokens, k_pool, v_pool, page_tables, lengths,
                   temp, top_p, top_k, key, counts, fresh, active,
                   freq_pen, pres_pen, upd_tok, upd_mask):
                lane = jnp.arange(B)
                counts = jnp.where(fresh[:, None],
                                   jnp.zeros_like(counts), counts)
                counts = counts.at[lane[:, None], upd_tok].add(
                    (upd_mask & active[:, None]).astype(jnp.int32))
                pos = (lengths - 1)[:, None] + jnp.arange(T)[None, :]
                write_idx = (jnp.take_along_axis(page_tables, pos // page,
                                                 axis=1) * page + pos % page)
                t = jnp.arange(S, dtype=jnp.int32)
                read_pos = jnp.broadcast_to(t[None], (B, S))
                # causality (read_pos <= position) masks the not-yet-written
                # tail per query; validity only needs the max coverage
                read_valid = t[None] < (lengths[:, None] + K)
                # the context is the page table's pages, in order
                logits, k_pool, v_pool = llama.forward(
                    params, cfg.model, tokens, pos, k_pool, v_pool,
                    write_idx, None, read_pos, read_valid,
                    attn_impl=impl, mesh=mesh,
                    read_pages=page_tables)             # [B, T, V]
                cf = counts.astype(jnp.float32)[:, None, :]
                lg = (logits - freq_pen[:, None, None] * cf
                      - pres_pen[:, None, None]
                      * (cf > 0).astype(jnp.float32))
                packed, new_key = spec_verify(lg, tokens[:, 1:], temp,
                                              top_p, top_k, key)
                return packed, new_key, k_pool, v_pool, counts

            from ..utils.roofline import instrument_compile
            self._verify_fns[(S, K)] = instrument_compile(
                "verify", fn, self._record_compile)
        return self._verify_fns[(S, K)]

    @staticmethod
    def _bucket(n: int, buckets: List[int]) -> int:
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    # ------------------------------------------------------------------
    # public API (engine thread)
    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release host-side cache resources (the disk tier's spill
        memmaps + files, the pager's prefetch thread). Idempotent; called
        from JaxEngine.shutdown."""
        if self.kvpager is not None:
            self.kvpager.close()
        if self.tiered is not None:
            self.tiered.close()

    def submit(self, seq_id: str, request: BackendInput,
               submitted: Optional[float] = None, parent: Any = None) -> None:
        """``submitted`` is ``time.monotonic()`` where the request was handed
        over (now, when the caller is the engine thread itself) and
        ``parent`` the submitter's span: the queue stage starts there."""
        if submitted is None:
            submitted = time.monotonic()
        self.waiting.append((seq_id, request, submitted,
                             self.lane_clock.read(submitted), parent))

    def cancel(self, seq_id: str) -> None:
        slot = self.by_seq.get(seq_id)
        if slot is not None:
            slot.cancelled = True
        else:
            self.waiting = collections.deque(
                w for w in self.waiting if w[0] != seq_id)
            if self.kvpager is not None:
                self.kvpager.cancel(seq_id)
            if seq_id in self._stream_injects:
                # mid-stream cancel: release the half-written pages
                self.abort_stream_inject(seq_id)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.by_seq or self._inflight
                    or (self.kvpager is not None
                        and self.kvpager.has_work))

    @property
    def active(self) -> int:
        return len(self.by_seq)

    def utilization(self) -> Dict[str, float]:
        total = self.pool.num_pages - 1
        hit_rate = (self.prefix_hit_tokens / self.prefix_query_tokens
                    if self.prefix_query_tokens else 0.0)
        goodput = self.goodput.snapshot()
        # byte-honest residency: device pool bytes in use plus the paged
        # lane's pinned host working set, against device + host-tier
        # capacity — the router's bytes-pressure scoring input (a 128k
        # request shows up here at its true size, not as one slot)
        blk_bytes = float(llama.kv_block_bytes(self.cfg.model,
                                               self.cfg.page_size))
        resident = float(total - self.pool.free_pages) * blk_bytes
        capacity = float(total) * blk_bytes
        if self.tiered is not None:
            capacity += float(self.tiered.host.num_blocks) * blk_bytes
        if self.kvpager is not None:
            # the lane's device pages are already counted in-pool; its
            # pinned host working set is the part slots cannot see
            resident += self.kvpager.resident_bytes()[1]
        return {
            "request_active_slots": float(self.active),
            "request_total_slots": float(self.cfg.max_batch),
            "kv_active_blocks": float(total - self.pool.free_pages),
            "kv_total_blocks": float(total),
            "num_requests_waiting": float(len(self.waiting)),
            "gpu_prefix_cache_hit_rate": hit_rate,
            # speculative decoding: drafted-token acceptance rate (0 when
            # spec is off or nothing proposed yet) — surfaced through
            # ForwardPassMetrics so the planner/router/tracectl can see it
            "spec_accept_rate": (
                self.spec_accepted_total / self.spec_proposed_total
                if self.spec_proposed_total else 0.0),
            # goodput plane: windowed device-efficiency rates (0 when the
            # engine has been idle for the whole window)
            "mfu": goodput["mfu"],
            "mbu": goodput["mbu"],
            "hbm_gbps": goodput["hbm_gbps"],
            "kv_resident_bytes": resident,
            "kv_capacity_bytes": capacity,
        }

    # ------------------------------------------------------------------
    # KV export/import (disaggregated prefill -> decode transfer)
    # ------------------------------------------------------------------
    def extract_kv(self, seq_id: str, layer: Optional[int] = None,
                   count: Optional[int] = None):
        """Gather a sequence's KV out of the pool -> host numpy arrays.
        With ``layer`` set, returns that layer only ([T,Hkv,Dh] k, v) for
        layer-pipelined transfer; otherwise all layers ([L,T,Hkv,Dh]).
        ``count`` limits extraction to the first N tokens (e.g. the prompt)."""
        self._refuse_block_moves("disaggregated KV extract")
        sc = self.pool.seqs[seq_id]
        n = sc.num_tokens if count is None else min(count, sc.num_tokens)
        slots = jnp.asarray(self.pool.write_slots(seq_id, 0, n))
        if layer is None:
            # dynalint: ok(host-sync) the KV export IS the transfer: disagg
            # prefill->decode ships blocks host-staged, once per sequence
            k = np.asarray(self._kv_gather(self.k_pool, slots))
            # dynalint: ok(host-sync) second half of the same export
            v = np.asarray(self._kv_gather(self.v_pool, slots))
        else:
            # dynalint: ok(host-sync) layer-pipelined variant of the same
            # once-per-sequence disagg KV export
            k = np.asarray(self._kv_gather_layer(self.k_pool, slots, layer))
            # dynalint: ok(host-sync) second half of the same export
            v = np.asarray(self._kv_gather_layer(self.v_pool, slots, layer))
        return k, v

    def _kv_gather(self, pool, slots):
        # pool [L, Hkv, n_pages, page, Dh], flat slots [n] -> [L, n, Hkv, Dh]
        # (adjacent advanced indices stay in place: [L, Hkv, n, Dh])
        if not hasattr(self, "_gather_fn"):
            pg = self.page_size
            self._gather_fn = jax.jit(
                lambda p, s: jnp.transpose(p[:, :, s // pg, s % pg],
                                           (0, 2, 1, 3)))
        return self._gather_fn(pool, slots)

    def _kv_gather_layer(self, pool, slots, layer: int):
        if not hasattr(self, "_gather_layer_fn"):
            pg = self.page_size
            self._gather_layer_fn = jax.jit(
                lambda p, s, l: jnp.transpose(p[l][:, s // pg, s % pg],
                                              (1, 0, 2)), static_argnums=2)
        return self._gather_layer_fn(pool, slots, layer)

    def prefill_extract(self, seq_id: str, request: BackendInput
                        ) -> Tuple[np.ndarray, np.ndarray, int, float]:
        """Prefill-worker path: run the full (chunked) prefill for a request,
        sample its first token, gather the prompt KV to host, release the
        slot. Returns (k [L,T,Hkv,Dh], v, first_token, first_logprob).
        The caller owns queue/transfer; this runs on the engine thread."""
        self._refuse_block_moves("disaggregated prefill (KV extract)")
        from dataclasses import replace

        prompt = list(request.token_ids)
        if len(prompt) + 1 >= self.cfg.max_context:
            # typed 400 (not a bare ValueError): the disagg frontend's
            # error body names the configured limit and the stage that
            # rejected, end to end over the wire
            from ..runtime.engine import EngineError
            raise EngineError(
                f"prompt of {len(prompt)} tokens exceeds the configured "
                f"max_context of {self.cfg.max_context}", 400,
                stage="prefill", reason="context_exceeded")
        if request.images:
            raise ValueError("disaggregated prefill does not take image "
                             "requests yet; serve VLM prompts aggregated")
        if None not in self.slots:
            raise RuntimeError("no free slot for prefill job")
        # the first sampled token must never finish the slot (we need the KV
        # before release) — neutralize stop conditions for the prefill pass
        req = replace(request, stop=replace(
            request.stop, max_tokens=None, stop_token_ids=[],
            min_tokens=None, ignore_eos=True))
        slot_idx = self.slots.index(None)
        slot = _Slot(seq_id, req, prompt)
        slot.t_admitted = time.monotonic()   # no queue here: prefill only
        self.slots[slot_idx] = slot
        self.by_seq[seq_id] = slot
        self.pool.create(seq_id, lora_id=getattr(req, "lora_id", 0))
        self._load_sampling(slot_idx, req)
        out: List[StepOutput] = []
        try:
            while slot.prefill_done < len(prompt):
                self._prefill_dispatch([(slot_idx, slot)], out)
                if out and out[-1].finish == FinishReason.ERROR:
                    raise OutOfPages("prefill ran out of KV pages")
            so = out[-1]
            k, v = self.extract_kv(seq_id, count=len(prompt))
        finally:
            self._free_slot(slot_idx)
        return k, v, so.token, so.logprob

    def inject_prefilled(self, seq_id: str, request: BackendInput,
                         k: np.ndarray, v: np.ndarray,
                         first_token: int,
                         first_logprob: float = 0.0) -> StepOutput:
        """Receive a remotely-prefilled sequence: write its prompt KV into
        this pool and enter it straight into decode (prefill_done=len).
        ``k``/``v``: [L, T, Hkv, Dh] for the prompt tokens."""
        self._refuse_block_moves("disaggregated KV inject")
        if None not in self.slots:
            raise RuntimeError("no free slot for injected sequence")
        prompt = list(request.token_ids)
        T = k.shape[1]
        if T != len(prompt):
            raise ValueError(f"KV covers {T} tokens, prompt is {len(prompt)}")
        self.pool.create(seq_id, lora_id=getattr(request, "lora_id", 0))
        self.pool.extend(seq_id, prompt)
        self._flush_evictions()
        slots = jnp.asarray(self.pool.write_slots(seq_id, 0, T))
        if not hasattr(self, "_scatter_fn"):
            pg = self.page_size
            # vals [L, T, Hkv, Dh] -> pool indexed shape [L, Hkv, T, Dh]
            self._scatter_fn = jax.jit(
                lambda p, s, vals: p.at[:, :, s // pg, s % pg].set(
                    jnp.transpose(vals, (0, 2, 1, 3))), donate_argnums=0)
        self.k_pool = self._scatter_fn(self.k_pool, slots,
                                       k.astype(self.cfg.model.dtype))
        self.v_pool = self._scatter_fn(self.v_pool, slots,
                                       v.astype(self.cfg.model.dtype))
        return self._enter_injected(seq_id, request, prompt, first_token,
                                    first_logprob)

    def _enter_injected(self, seq_id: str, request: BackendInput,
                        prompt: List[int], first_token: int,
                        first_logprob: float) -> StepOutput:
        """Shared tail of the two KV-import paths (bulk inject / layer
        stream): claim a slot straight into decode, seed bookkeeping, and
        emit the prefill-worker-sampled first token."""
        slot_idx = self.slots.index(None)
        slot = _Slot(seq_id, request, prompt, prefill_done=len(prompt))
        self.slots[slot_idx] = slot
        self.by_seq[seq_id] = slot
        self._load_sampling(slot_idx, request)
        self._apply_pending_seeds()
        if request.sampling.seed is not None:
            # the prefill worker consumed one key step sampling the first
            # token; advance the freshly-seeded key the same way so token 2
            # onward matches a local prefill of the same seeded request
            s = self.sampling
            s.key = s.key.at[slot_idx].set(
                jax.random.split(s.key[slot_idx], 2)[0])
        self._append_generated(slot, int(first_token))
        slot.cum_logprob = float(first_logprob)
        fin = self._finish_reason(slot, int(first_token))
        so = StepOutput(seq_id, int(first_token), slot.cum_logprob, fin,
                        prompt_tokens=len(prompt),
                        token_logprob=float(first_logprob))
        if fin is not None:
            self._free_slot(slot_idx)
        return so

    # ------------------------------------------------------------------
    # layer-streamed KV injection (disagg receive; engine thread)
    # ------------------------------------------------------------------
    def begin_stream_inject(self, seq_id: str,
                            request: BackendInput) -> None:
        """Lease pool pages for a remotely-prefilled prompt whose KV is
        still on the wire. The pages stay UNSEALED (no hash registration,
        no stored events, no write-through) until :meth:`
        finish_stream_inject` — a torn stream releases them with nothing
        ever having referenced them."""
        self._refuse_block_moves("layer-streamed KV inject")
        prompt = list(request.token_ids)
        if None not in self.slots:
            raise RuntimeError("no free slot for streamed sequence")
        self.pool.create(seq_id, lora_id=getattr(request, "lora_id", 0))
        try:
            self.pool.ensure_pages(seq_id, len(prompt))
        except Exception:
            self.pool.release(seq_id)
            raise
        # leasing may have evicted reusable pages: their offload d2h must
        # be enqueued before our scatters overwrite them
        self._flush_evictions()
        slots = jnp.asarray(self.pool.write_slots(seq_id, 0, len(prompt)))
        if not hasattr(self, "_stream_scatter_fns"):
            # grouped per-arrival scatter, keyed by group size G:
            # [G] layer ids + [G, T, Hkv, Dh] values land in one donated
            # dispatch (ls[:,None] broadcasts with the [T] slot indices
            # to a [G, T] advanced subspace, placed leading — the wire
            # layout lands without a host-side transpose). Grouping
            # bounds the per-transfer dispatch count: one jit call per
            # arriving layer would spend more host time on dispatch
            # overhead than the scatters it hides.
            self._stream_scatter_fns: Dict[int, Any] = {}
        self._stream_injects[seq_id] = {
            "request": request, "prompt": prompt, "slots": slots,
            "layers_done": 0, "buf": [], "buf_l0": 0,
            # flush granularity: ~4 scatter dispatches per pool per
            # transfer, never coarser than half the model
            "group": max(1, min(4, self.cfg.model.num_layers)),
        }

    def _stream_scatter(self, G: int):
        fn = self._stream_scatter_fns.get(G)
        if fn is None:
            pg = self.page_size
            fn = jax.jit(
                lambda p, ls, s, vals: p.at[
                    ls[:, None], :, s // pg, s % pg].set(vals),
                donate_argnums=0)
            self._stream_scatter_fns[G] = fn
        return fn

    def _flush_stream_buf(self, st) -> None:
        buf = st["buf"]
        if not buf:
            return
        dt = self.cfg.model.dtype
        G = len(buf)
        fn = self._stream_scatter(G)
        ls = jnp.arange(st["buf_l0"], st["buf_l0"] + G)
        k_vals = jnp.asarray(np.stack([b[0] for b in buf]), dt)
        v_vals = jnp.asarray(np.stack([b[1] for b in buf]), dt)
        self.k_pool = fn(self.k_pool, ls, st["slots"], k_vals)
        self.v_pool = fn(self.v_pool, ls, st["slots"], v_vals)
        st["buf_l0"] += G
        st["buf"] = []

    def stream_inject_layer(self, seq_id: str, layer: int,
                            k: np.ndarray, v: np.ndarray) -> None:
        """Accept ONE arriving layer ([T,Hkv,Dh] each) and enqueue its
        group's device scatter while later layers are still in flight.
        Donated, async: the engine keeps dispatching other sequences'
        work in between."""
        st = self._stream_injects[seq_id]
        st["buf"].append((k, v))
        st["layers_done"] = layer + 1
        if len(st["buf"]) >= st["group"]:
            self._flush_stream_buf(st)

    def finish_stream_inject(self, seq_id: str, first_token: int,
                             first_logprob: float) -> StepOutput:
        """All scatters enqueued: seal+register the blocks (stored events
        and write-through fire only now, for fully-arrived KV) and enter
        the sequence straight into decode."""
        st = self._stream_injects.pop(seq_id)
        prompt = st["prompt"]
        if st["layers_done"] != self.cfg.model.num_layers:
            self.pool.release(seq_id)
            raise ValueError(
                f"stream inject for {seq_id} finished at layer "
                f"{st['layers_done']}/{self.cfg.model.num_layers}")
        if None not in self.slots:
            self.pool.release(seq_id)
            raise RuntimeError("no free slot for streamed sequence")
        self._flush_stream_buf(st)         # tail group (< group layers)
        self.pool.account_tokens(seq_id, prompt)
        return self._enter_injected(seq_id, st["request"], prompt,
                                    first_token, first_logprob)

    def abort_stream_inject(self, seq_id: str) -> None:
        """Torn stream: drop the ingest state and release the leased
        pages. They were never sealed/registered, so nothing — attention,
        prefix match, write-through, peers — can have observed the
        partial writes; the pages return to the free list."""
        if self._stream_injects.pop(seq_id, None) is not None:
            self.pool.release(seq_id)

    # ------------------------------------------------------------------
    def step(self) -> List[StepOutput]:
        """One engine iteration (see :meth:`_step`), plus the prefix-hit
        tagging post-pass: a sequence's FIRST output carries admission's
        sealed-prefix restore length (``StepOutput.prefix_hit``), the
        client-observable proof of the KV re-attach path on resumes."""
        out = self._step()
        if self._pending_prefix_hit:
            for so in out:
                hit = self._pending_prefix_hit.pop(so.seq_id, None)
                if hit is not None:
                    so.prefix_hit = hit
        return out

    def _step(self) -> List[StepOutput]:
        """Run one engine iteration.

        Dispatches are PIPELINED: what an iteration enqueues (at most one
        prefill chunk, then one decode dispatch) goes behind whatever is
        still in flight, and only then are the earlier iterations' records
        fetched, oldest first. The host builds the next dispatch while the
        chip runs the last; the device sees one chunk per decode dispatch,
        in the order it always did.

        A prefill chunk needs nothing from the window: its tokens,
        positions and slots come from the prompt and the page pool. A
        decode dispatch behind a chunk that completes no prompt chains off
        the newest decode record's on-device tokens (same lanes). Behind a
        chunk that completes a prompt it chains too: the chunk's sampled
        token is on the device, and is written over the new lane's entry of
        those tokens there (a lane that takes the place of one that
        finished included), so the device goes from the chunk into the
        dispatch that takes the new lane in without waiting for the host.
        That chunk is still fetched within its own iteration, behind
        everything older: the first token never waits behind a deliver.
        At any other change of lanes (a finish with nothing to take its
        place, an injected sequence, a first prompt with no decode dispatch
        to chain off) nothing chains: the earlier records are fetched, and
        a dispatch built from host tokens follows at once. Only at such a
        change does the window hold no decode dispatch.

        Still sync points (the window drains before anything is enqueued):
        a reaped cancel, an admission that waits for pages a deferred
        release holds, and every speculative round."""
        out: List[StepOutput] = []
        phase = self.phase
        phase.to("housekeeping")
        self._advance_writethrough()
        out.extend(self._reap_cancelled())
        n_reaped = len(out)     # paged outputs below don't change slots
        if self.kvpager is not None and self.kvpager.has_work:
            # one unit of paged long-context work (a prefill chunk or a
            # decode token) interleaves with every normal engine step
            phase.to("paged")
            out.extend(self.kvpager.advance())

        phase.to("admit")
        prefilling = sum(s is not None and s.prefill_done < len(s.prompt)
                         for s in self.slots)
        prefill_work = prefilling > 0
        self.lane_clock.set(prefilling >= self.b_buckets[-1]
                            and None in self.slots)
        admit_possible = bool(self.waiting) and None in self.slots

        if self.spec is not None:
            # speculative mode is synchronous per round (acceptance needs
            # the fetch), so there is never an in-flight window
            if prefill_work or admit_possible:
                if self._prefill_round(out) is not None:
                    self._process_inflight(out)
            if any(s is not None and s.prefill_done >= len(s.prompt)
                   for s in self.slots):
                self._spec_round(out)
            return out

        held = len(self._inflight)      # earlier iterations' records
        if held and (n_reaped or (admit_possible
                                  and self._admission_awaits_release())):
            self._process_inflight(out, held)
            held = 0
        completes = None
        if prefill_work or admit_possible:
            completes = self._prefill_round(out)
            # if no prefill progress was possible (e.g. pool full), fall
            # through to decode so the engine never stalls
        # non-blocking enqueue, behind the chunk: decode keeps advancing
        # between the chunks of a long prompt, and a completed prompt's
        # first token joins it on the device
        behind = self._dispatch_decode(out, completing=bool(completes))
        # a chunk that completes a prompt is fetched within its own
        # iteration, behind everything older: the first token goes out now
        self._process_inflight(
            out, len(self._inflight) - behind if completes else held)
        if not behind:
            # the lanes changed with nothing on the device to chain off (a
            # sequence finished or was injected, a first prompt completed):
            # no decode dispatch is unfetched any more, so every lane's
            # token is on the host, and the dispatch goes out before this
            # iteration's outputs are delivered
            self._dispatch_decode(out)
        if not self.by_seq:
            # every live sequence finished: drain the stale window so its
            # pages release instead of idling in limbo
            self._process_inflight(out)
        return out

    def _admission_awaits_release(self) -> bool:
        """The head-of-line request does not fit the pool while a deferred
        release holds pages: fetch what holds them before admitting."""
        return bool(self._deferred_release) and not self._can_admit(
            len(self.waiting[0][1].token_ids) + 1)

    def _can_admit(self, tokens: int) -> bool:
        """Every cache has room for a sequence of ``tokens``: its whole
        context in the global pool and, for a per-kind model, a window and
        a chunk (and the in-flight dispatches' lag behind them) in the
        window pool."""
        if not self.pool.can_admit(tokens):
            return False
        if self.win is None:
            return True
        span = min(tokens, self.win.window - 1 + 3 * self.cfg.prefill_chunk)
        return self.win.free_pages >= -(-span // self.page_size) + 1

    # ------------------------------------------------------------------
    def _request_span(self, slot: _Slot, name: str, start: float,
                      end: float, **attrs: Any) -> None:
        """One interval of a request (``time.monotonic()`` stamps) into its
        trace as ``engine.<name>``: trace id = request id, parent = the span
        that was current where the request was submitted (the engine thread
        has no context of its own). The tracer decides whether it records
        (``DYN_TRACING=0``) and its sinks whether they keep (sampling)."""
        tracer = _tracing.get_tracer()
        if tracer.enabled:
            epoch = time.time() - time.monotonic()
            tracer.record("engine." + name, start + epoch, end + epoch,
                          parent=slot.trace_parent, trace_id=slot.seq_id,
                          **attrs)

    def _request_stage(self, slot: _Slot, stage: str, start: float,
                       end: float, **attrs: Any) -> None:
        """A stage of a request's way to its first token: always into
        ``llm_request_stage_seconds{stage}``, and into its trace."""
        self.stage.request_stage.observe(stage, value=end - start)
        self._request_span(slot, stage, start, end, **attrs)

    def _reap_cancelled(self) -> List[StepOutput]:
        outs = []
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.cancelled:
                outs.append(StepOutput(slot.seq_id, slot.last_token, 0.0,
                                       FinishReason.CANCELLED))
                self._free_slot(i)
        return outs

    def _free_slot(self, i: int) -> None:
        slot = self.slots[i]
        if slot is None:
            return
        if slot.t_first_token:
            self._request_span(slot, "decode", slot.t_first_token,
                               time.monotonic(),
                               output_tokens=slot.generated)
        # a queued-but-unapplied seed for this slot must die with it, or a
        # later occupant of the slot could get two key writes at one index
        # (implementation-defined winner)
        self._pending_seeds = [(ix, sd) for ix, sd in self._pending_seeds
                               if ix != i]
        self._decode_seen.pop(i, None)
        self._spec_states.pop(slot.seq_id, None)
        if self.proposer is not None:
            self.proposer.drop(slot.seq_id)
        if self._inflight:
            # a dispatch already enqueued may still write into this
            # sequence's pages (a chained decode's overshoot, a cancelled
            # prompt's chunk): hold the release until every record in
            # flight NOW has been fetched, so the pages cannot be
            # reallocated under them. Records enqueued later never name
            # this sequence, so they do not hold its pages.
            self._deferred_release.append(
                (slot.seq_id, self._inflight[-1]["seq"]))
        else:
            self._release_seq(slot.seq_id)
        self.by_seq.pop(slot.seq_id, None)
        self.slots[i] = None

    def _apply_deferred_release(self) -> None:
        """Release the pages of every freed sequence whose barrier record
        (the newest in flight when it was freed) has been fetched."""
        oldest = (self._inflight[0]["seq"] if self._inflight
                  else self._dispatch_seq + 1)
        # barriers only grow down the list
        while self._deferred_release and self._deferred_release[0][1] < oldest:
            self.phase.to("housekeeping")
            self._release_seq(self._deferred_release.pop(0)[0])

    def _offload_evicted(self, seq_hash: int, page: int) -> None:
        """Eviction hook: queue the page for host-tier offload. The data
        stays valid until the page's new owner WRITES (the next device
        dispatch), so :meth:`_flush_evictions` batches the copies out right
        before any dispatch that could overwrite pool pages."""
        if self.tiered is None:
            return
        # an evicted page's slot can be rewritten by the very next
        # dispatch: deferred write-through entries for it would mirror the
        # new owner's data under the old hash. Drop them — this eviction
        # entry offloads the same block with still-valid data.
        entry = (seq_hash, page)
        for buf in (self._writethrough_buf, self._writethrough_armed,
                    self._writethrough_pending):
            if entry in buf:
                buf.remove(entry)
        self._evict_buf.append(entry)

    def _writethrough_sealed(self, seq_id: str, block, page: int,
                             lora_id: int) -> None:
        """Seal hook (cluster sharing): mirror the block to the host tier
        so peers can fetch it while it is still hot on device. The KV for
        a freshly sealed block is NOT on device yet — see the ratchet in
        :meth:`_advance_writethrough`. Host-tier restores also seal
        (``fire_stored``) — those blocks came FROM the tier, so mirroring
        them back would be a wasted d2h exactly on the cluster-warm path."""
        if block.sequence_hash in self.tiered:
            return
        self._writethrough_pending.append((block.sequence_hash, page))

    def _advance_writethrough(self) -> None:
        """Step-boundary ratchet for cluster write-through mirrors: a
        block sealed during step N has its KV written by a dispatch issued
        no later than step N+1 (pipelined decode chains one step behind
        the seal), so entries become d2h-safe at the top of step N+2 —
        the copy then reads the post-dispatch pool binding. Also drains
        the ready batch on decode-only steps, which never hit the
        extend-path flush sites."""
        if (not self._writethrough_pending and not self._writethrough_armed
                and not self._writethrough_buf):
            return
        self._writethrough_buf.extend(self._writethrough_armed)
        self._writethrough_armed = self._writethrough_pending
        self._writethrough_pending = []
        if self._writethrough_buf:
            self._flush_evictions()

    def _flush_evictions(self) -> None:
        if not self._evict_buf and not self._writethrough_buf:
            return
        # evictions + write-through mirrors share one batched d2h; dedupe
        # (a written-through block can also be in the eviction batch)
        buf = list(dict.fromkeys(self._evict_buf + self._writethrough_buf))
        self._evict_buf, self._writethrough_buf = [], []
        pages = [p for _, p in buf]
        t0 = time.perf_counter()
        k, v = self.copy_stream.d2h_pages(self.k_pool, self.v_pool, pages,
                                          pipeline=len(pages) > 4)
        from ..obs.flows import record_flow
        record_flow("d2h_writethrough", k.nbytes + v.nbytes,
                    time.perf_counter() - t0)
        for i, (seq_hash, _) in enumerate(buf):
            self.tiered.offload(seq_hash, k[i], v[i])

    # ------------------------------------------------------------------
    # placement-driven h2d prefetch (asyncio thread -> admission restore)
    # ------------------------------------------------------------------
    def stage_prefetch(self, token_ids, lora_id: int = 0) -> int:
        """Upload matched host/disk-tier prefix blocks to the device
        STAGING buffer while the request still queues at the slot gate
        (asyncio thread; the engine thread keeps dispatching). Admission's
        restore then consumes them with a d2d scatter instead of paying
        the h2d on first prefill's critical path. Returns blocks staged.

        Safe concurrently with the engine thread: the tier is internally
        locked, staged arrays are fresh device buffers nothing else
        references, and the stage dict is lock-guarded."""
        self._refuse_block_moves("tier prefetch staging")
        from ..llm.tokens import compute_seq_hashes
        from ..utils.knobs import env_float

        cap = int(env_float("DYN_H2D_PREFETCH_BLOCKS", 32, minimum=0.0))
        if cap <= 0 or self.tiered is None:
            return 0
        dt = self.cfg.model.dtype
        staged = 0
        nbytes = 0
        t0 = time.perf_counter()
        for h in compute_seq_hashes(list(token_ids), self.page_size,
                                    lora_id=lora_id):
            if self.pool.blocks.contains(h):
                continue            # device-resident: nothing to move
            with self._h2d_stage_lock:
                if h in self._h2d_stage:
                    continue
            kv = self.tiered.peek(h)   # copies; no LRU perturbation
            if kv is None:
                break               # consecutive-prefix property
            # enqueue the h2d now — by admission time the copy has been
            # overlapping the queue wait instead of gating first prefill
            k_dev = jnp.asarray(kv[0], dt)
            v_dev = jnp.asarray(kv[1], dt)
            nbytes += kv[0].nbytes + kv[1].nbytes
            with self._h2d_stage_lock:
                while len(self._h2d_stage) >= cap:
                    self._h2d_stage.pop(next(iter(self._h2d_stage)))
                self._h2d_stage[h] = (k_dev, v_dev)
                self._h2d_requested.add(h)
                if len(self._h2d_requested) > 4 * cap:
                    # cancelled/never-admitted requests must not grow the
                    # stall-attribution set forever
                    self._h2d_requested.clear()
            staged += 1
            if staged >= cap:
                break
        if staged:
            from ..obs.flows import record_flow
            record_flow("h2d_prefetch", nbytes,
                        time.perf_counter() - t0)
        return staged

    def _restore_prefix(self, seq_id: str, prompt: List[int]) -> int:
        """Prefix reuse at admission: claim matching device blocks,
        consume prefetch-staged device blocks (d2d), and upload the
        remaining matching host-tier blocks; returns tokens satisfied
        from cache (always < len(prompt) so the last token still
        computes logits)."""
        host_lookup = None
        fetched: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        staged: Dict[int, Tuple[Any, Any]] = {}
        if self.tiered is not None:
            def host_lookup(h):
                with self._h2d_stage_lock:
                    dev = self._h2d_stage.pop(h, None)
                    self._h2d_requested.discard(h)
                if dev is not None:
                    staged[h] = dev
                    return True
                # fetch (and copy) eagerly: leasing the upload page can evict
                # a device block whose offload lands in — and LRU-drops from —
                # the very host tier we matched against
                kv = self.tiered.lookup(h)
                if kv is None:
                    return False
                fetched[h] = (kv[0].copy(), kv[1].copy())
                return True
        matched, uploads = self.pool.match_prefix(
            seq_id, prompt, len(prompt) - 1, host_lookup)
        if uploads:
            self._flush_evictions()
            from ..utils.prometheus import stage_metrics
            stage = stage_metrics()
            host_up = [(h, p) for h, p in uploads if h not in staged]
            dev_up = [(h, p) for h, p in uploads if h in staged]
            if host_up:
                pages = [p for _, p in host_up]
                ks = np.stack([fetched[h][0] for h, _ in host_up])
                vs = np.stack([fetched[h][1] for h, _ in host_up])
                t0 = time.perf_counter()
                self.k_pool, self.v_pool = self.copy_stream.h2d_pages(
                    self.k_pool, self.v_pool, pages, ks, vs)
                from ..obs.flows import record_flow
                record_flow("h2d_prefetch", ks.nbytes + vs.nbytes,
                            time.perf_counter() - t0, trace_id=seq_id)
                stalls = 0
                with self._h2d_stage_lock:
                    for h, _ in host_up:
                        if h in self._h2d_requested:
                            self._h2d_requested.discard(h)
                            stalls += 1
                if stalls:
                    stage.prefetch_h2d_stalls.inc(amount=float(stalls))
            if dev_up:
                self.k_pool, self.v_pool = self.copy_stream.scatter_blocks(
                    self.k_pool, self.v_pool, [p for _, p in dev_up],
                    [staged[h][0] for h, _ in dev_up],
                    [staged[h][1] for h, _ in dev_up])
                stage.prefetch_h2d_hits.inc(amount=float(len(dev_up)))
        return matched

    def _prepare_mm(self, req: BackendInput, prompt: List[int]):
        """Validate + encode a VLM request. Returns (spans, soft, digest)
        or an error string. Vision encode happens here (admission, engine
        thread) so the prefill dispatch itself stays token-shaped."""
        import hashlib

        from . import multimodal as mm

        m = self.cfg.model
        if self.vision_cfg is None:
            return ("this model has no vision tower; images are not "
                    "servable (text-only deployment)")
        if self.cfg.pp > 1:
            return ("image requests are not supported on pipeline-parallel "
                    "engines yet (the staged prefill takes no span inputs)")
        if m.image_token_id is None:
            return "model config has no image_token_id"
        spans = mm.image_spans(prompt, m.image_token_id)
        err = mm.validate_mm_prompt(spans, len(req.images),
                                    m.mm_tokens_per_image,
                                    self.cfg.prefill_chunk)
        if err:
            return err
        try:
            px = np.stack([mm.normalize_image(im, self.vision_cfg.image_size)
                           for im in req.images])
        except ValueError as e:
            return str(e)
        digest = 0
        if not getattr(req, "kv_salt", 0):
            # only needed when the frontend didn't already salt the request
            # (preprocessor.image_kv_salt): hashing the full normalized
            # pixel stack on the engine thread is pure waste otherwise
            digest = int.from_bytes(
                hashlib.blake2b(px.tobytes(), digest_size=8).digest(),
                "little")
        # dynalint: ok(host-sync) vision-tower fetch: one soft-token array
        # per image batch at admission, reused for every prefill chunk
        soft = np.asarray(self._encode_images(jnp.asarray(px)))
        return spans, soft, digest

    def _admit_one(self, out: List[StepOutput]):
        """Admit the head-of-line request into a free slot (no prefill yet).
        Returns (slot_idx, slot), "rejected" (popped with an error emitted),
        or "blocked" (no KV capacity right now)."""
        seq_id, req, submitted, lane_clock, parent = self.waiting[0]
        prompt = list(req.token_ids)
        over_ctx = len(prompt) >= self.cfg.max_context
        over_pool = (self.pool.pages_needed(len(prompt) + 1)
                     > self.pool.num_pages - 1)
        if over_ctx or over_pool:
            # beyond the dense path's reach. With KV paging enabled this
            # is exactly the long-context lane's workload; without it,
            # reject with the typed 400 body naming the configured limit
            # (can NEVER fit, even with an empty pool: don't starve)
            self.waiting.popleft()
            if self.kvpager is not None:
                so = self.kvpager.try_route(seq_id, req)
                if so is None:
                    return "paged"
                out.append(so)
                return "rejected"
            if over_ctx:
                msg = (f"prompt of {len(prompt)} tokens exceeds the "
                       f"configured max_context of {self.cfg.max_context}")
            else:
                msg = (f"prompt of {len(prompt)} tokens cannot fit in the "
                       f"KV pool ({self.pool.num_pages - 1} pages)")
            out.append(StepOutput(
                seq_id, 0, 0.0, FinishReason.ERROR, error=msg,
                error_code=400, error_stage="engine_admission",
                error_reason="context_exceeded"))
            return "rejected"
        if not self._can_admit(len(prompt) + 1):
            return "blocked"  # decode will free KV space eventually
        mm_spans = mm_soft = None
        chain_salt = getattr(req, "lora_id", 0)
        if req.images:
            err = self._prepare_mm(req, prompt)
            if isinstance(err, str):
                self.waiting.popleft()
                out.append(StepOutput(seq_id, 0, 0.0, FinishReason.ERROR,
                                      error=err))
                return "rejected"
            mm_spans, mm_soft, img_digest = err
            # salt the block-hash chain with the image content: identical
            # (prompt, images) requests still prefix-match, but the same
            # placeholder ids with DIFFERENT images can never alias — in
            # local reuse or the router index. When the FRONTEND already
            # computed a salt (BackendInput.kv_salt, preprocessor digest),
            # use it verbatim: the router's prefix-overlap scoring hashes
            # with that same salt, so published VLM blocks stay routable
            chain_salt = (getattr(req, "kv_salt", 0)
                          or (chain_salt ^ img_digest) & ((1 << 63) - 1))
        self.waiting.popleft()
        slot_idx = self.slots.index(None)
        slot = _Slot(seq_id, req, prompt)
        slot.mm_spans, slot.mm_soft = mm_spans, mm_soft
        slot.trace_parent = parent
        self.slots[slot_idx] = slot
        self.by_seq[seq_id] = slot
        # (a model with a window cache or a state a lane hashes, seals and
        # matches no block: see __init__)
        self.pool.create(seq_id, lora_id=chain_salt,
                         block_hashing=not self._no_block_reuse)
        if self.win is not None:
            self.win.create(seq_id)
        matched = 0
        if self.cfg.enable_prefix_reuse and not self._no_block_reuse:
            matched = self._restore_prefix(seq_id, prompt)
            slot.prefill_done = matched
        slot.prefix_hit = matched
        # stamped after the restore: it is part of what admission costs
        slot.t_admitted = now = time.monotonic()
        # the wait, split by what it was for: the seconds with a slot free
        # and the prefill lanes taken (``lane_wait``), and the rest (the
        # inbox, the drain of the in-flight window, a slot, KV capacity:
        # ``queue``). Not two intervals: the span is the whole wait
        lane = min(self.lane_clock.read(now) - lane_clock, now - submitted)
        self.stage.request_stage.observe("lane_wait", value=lane)
        self.stage.request_stage.observe(
            "queue", value=now - submitted - lane)
        self._request_span(slot, "queue", submitted, now,
                           lane_wait_ms=round(1e3 * lane, 3))
        self.last_prefix_hit = matched
        self.prefix_hit_tokens += matched
        # surfaced on this sequence's FIRST StepOutput (step()'s tagging
        # post-pass) -> EngineOutput.kv_prefix_hit_tokens at the facade
        self._pending_prefix_hit[seq_id] = matched
        self.prefix_query_tokens += len(prompt)
        if getattr(req, "resume_pos", 0):
            # mid-stream resume: the restored prefix IS the KV re-attach —
            # everything past `matched` (including the dead worker's
            # emitted tail) is teacher-forced prefill recompute. Counted
            # in blocks so the soak can assert the re-attach path (not
            # full re-prefill) was taken in the donor-alive arm.
            from ..utils.prometheus import stage_metrics

            stage_metrics().resume_kv_reattach_blocks.inc(
                amount=matched // self.pool.page_size)
        self._load_sampling(slot_idx, req)
        return slot_idx, slot

    def _prefill_round(self, out: List[StepOutput]) -> Optional[int]:
        """Advance every mid-prefill slot by one chunk and admit as many
        waiting requests as fit, all in ONE batched dispatch (up to the
        prefill lane budget), enqueued behind whatever is in flight.
        Returns how many prompts the dispatch completes, or None if
        nothing was dispatched."""
        self.phase.to("admit")
        max_lanes = self.b_buckets[-1]
        chunks = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None and s.prefill_done < len(s.prompt)]
        while (self.waiting and None in self.slots
               and len(chunks) < max_lanes):
            admitted = self._admit_one(out)
            if admitted == "blocked":
                break
            if admitted in ("rejected", "paged"):
                continue
            # fully satisfied by prefix reuse still needs its last token
            # computed, so every admission lands in the chunk list
            chunks.append(admitted)
        # whoever still waits with a slot free now waits for a lane
        self.lane_clock.set(len(chunks) >= max_lanes and None in self.slots)
        chunks = chunks[:max_lanes]
        if not chunks:
            return None
        return self._prefill_enqueue(chunks, out)

    def _load_sampling(self, slot_idx: int, req: BackendInput) -> None:
        s = self.sampling
        # written into copies: a decode dispatch still in flight was handed
        # these very arrays, and the CPU backend reads a host argument in
        # place, when the program runs
        for name in ("temperature", "top_p", "top_k", "freq_pen", "pres_pen"):
            setattr(s, name, np.array(getattr(s, name)))
        s.temperature[slot_idx] = float(req.sampling.temperature or 0.0)
        s.top_p[slot_idx] = float(req.sampling.top_p
                                  if req.sampling.top_p is not None else 1.0)
        s.top_k[slot_idx] = int(min(req.sampling.top_k or 0, STATIC_K))
        s.freq_pen[slot_idx] = float(req.sampling.frequency_penalty or 0.0)
        s.pres_pen[slot_idx] = float(req.sampling.presence_penalty or 0.0)
        if req.sampling.seed is not None:
            # deferred to the next prefill dispatch: keeps EVERY device op
            # at a mirrorable dispatch point (multi-host lockstep) and
            # batches the key writes. A resumed request folds its resume
            # position into the seed: the emitted prefix is replayed
            # verbatim (forced tokens, no draws), and the continuation
            # gets a fresh deterministic stream instead of re-issuing the
            # dead worker's already-consumed draws.
            self._pending_seeds.append((slot_idx, resume_seed(
                int(req.sampling.seed),
                int(getattr(req, "resume_pos", 0) or 0))))

    def _apply_pending_seeds(self) -> List[Tuple[int, int]]:
        applied, self._pending_seeds = self._pending_seeds, []
        if applied:
            s = self.sampling
            idx = jnp.asarray([i for i, _ in applied])
            keys = jax.vmap(jax.random.key)(
                jnp.asarray([seed for _, seed in applied]))
            s.key = s.key.at[idx].set(keys)
        return applied

    def _run_prefill_program(self, Bp, C, S, tokens, positions, write_idx,
                             read_idx, read_pos, read_valid, last_i, temp,
                             top_p, top_k, idxs, last_lanes,
                             mm_arrays=None, win_arrays=None,
                             ssm_arrays=None, form=None):
        """Execute the batched prefill program + key bookkeeping. The SAME
        code path runs on the leader (from _prefill_enqueue) and on
        followers (from mirror_dispatch) so device state stays in lockstep."""
        s = self.sampling
        keys = s.key[jnp.asarray(idxs)]
        fn = self._prefill_fn(Bp, C, S, mm=mm_arrays is not None, form=form)
        self.phase.to("prefill", f"dynamo.prefill[B{Bp},C{C},S{S}]")
        if mm_arrays is not None:
            packed, tok, new_keys, self.k_pool, self.v_pool = fn(
                self.params, tokens, positions, self.k_pool, self.v_pool,
                write_idx, read_idx, read_pos, read_valid, last_i,
                temp, top_p, top_k, keys, mm_arrays["ov_vals"],
                mm_arrays["ov_mask"], mm_arrays["q_span"],
                mm_arrays["read_span"])
        else:
            packed, tok, new_keys, *pools = fn(
                self.params, tokens, positions, self.k_pool, self.v_pool,
                write_idx, read_idx, read_pos, read_valid, last_i,
                temp, top_p, top_k, keys, **self._idx(),
                **(win_arrays or {}), **(ssm_arrays or {}))
            self._take_pools(pools)
        self._last_prefill_tok = tok
        self.phase.to("prefill_build")
        # persist advanced PRNG keys only for lanes that really sampled
        if last_lanes:
            la = jnp.asarray([int(idxs[l]) for l in last_lanes])
            s.key = s.key.at[la].set(new_keys[jnp.asarray(last_lanes)])
        return packed

    def _prefill_dispatch(self, chunks: List[Tuple[int, _Slot]],
                          out: List[StepOutput]) -> bool:
        """One chunk dispatch, enqueued and fetched at once, ahead of
        whatever else is in flight: for callers that need the sampled token
        before they go on (``prefill_extract``). Returns True if a dispatch
        ran."""
        if self._prefill_enqueue(chunks, out) is None:
            return False
        out.extend(self._fetch_prefill(newest=True))
        return True

    def _prefill_enqueue(self, chunks: List[Tuple[int, _Slot]],
                         out: List[StepOutput]) -> Optional[int]:
        """Advance each (slot_idx, slot) by one prompt chunk in a single
        batched dispatch, enqueued WITHOUT fetching its result: nothing a
        chunk takes in depends on a dispatch in flight. What does not
        depend on the sampled token is settled here; the rest when the
        record, now the newest of ``_inflight``, is fetched
        (:meth:`_fetch_prefill`). Returns how many prompts the dispatch
        completes (their first tokens come with the fetch), or None if no
        dispatch ran."""
        cfg = self.cfg
        self.phase.to("prefill_build")
        work = []  # (slot_idx, slot, start, count, is_last)
        for i, slot in chunks:
            prompt = slot.prompt
            start = slot.prefill_done
            if slot.mm_spans is not None:
                # never split an image span across chunks: its queries need
                # every span key written in the same dispatch
                from .multimodal import chunk_end
                count = chunk_end(slot.mm_spans, start, cfg.prefill_chunk)
            else:
                count = min(len(prompt) - start, cfg.prefill_chunk)
            try:
                if self.win is not None:
                    self.win.ensure(slot.seq_id, start + count)
                self.pool.extend(slot.seq_id, prompt[start:start + count])
            except OutOfPages:
                out.append(StepOutput(slot.seq_id, 0, 0.0,
                                      FinishReason.ERROR,
                                      error="out of KV pages during prefill"))
                self._free_slot(i)
                continue
            work.append((i, slot, start, count,
                         start + count == len(prompt)))
        if not work:
            return None
        self._flush_evictions()   # extend() may have evicted pages

        Bp = self._bucket(len(work), self.b_buckets)
        C = self._bucket(max(w[3] for w in work), self.c_buckets)
        S = self._bucket(max(w[2] + w[3] for w in work), self.s_buckets)
        s = self.sampling
        tokens = np.zeros((Bp, C), np.int32)
        positions = np.zeros((Bp, C), np.int32)
        write_idx = np.zeros((Bp, C), np.int32)   # pad -> scratch page 0
        read_idx = np.zeros((Bp, S), np.int32)
        read_pos = np.zeros((Bp, S), np.int32)
        read_valid = np.zeros((Bp, S), bool)
        last_i = np.zeros(Bp, np.int32)
        temp = np.zeros(Bp, np.float32)
        top_p = np.ones(Bp, np.float32)
        top_k = np.zeros(Bp, np.int32)
        idxs = np.zeros(Bp, np.int32)
        win_arrays = None
        if self.win is not None:
            win_arrays = self._win_dummies(Bp, C)
            Pw = win_arrays["w_pages"].shape[1]
            for lane, (_, slot, start, count, _) in enumerate(work):
                win_arrays["w_write"][lane, :count] = self.win.write_slots(
                    slot.seq_id, start, count)
                (win_arrays["w_pages"][lane], win_arrays["w_pos"][lane],
                 win_arrays["w_valid"][lane]) = self.win.read_window(
                    slot.seq_id, start, count, Pw)
        # a row's chunk starts from the state its lane holds (from zeros
        # where its sequence starts here: admission, or a re-prefill after
        # preemption) and leaves the state behind its last real token
        ssm_arrays = self._ssm_rows(Bp)
        if ssm_arrays:
            for lane, (i, _, start, count, _) in enumerate(work):
                ssm_arrays["s_lanes"][lane] = i
                ssm_arrays["s_reset"][lane] = start == 0
                ssm_arrays["s_valid"][lane] = count
        mm = any(w[1].mm_spans is not None for w in work)
        mm_arrays = None
        if mm:
            from .multimodal import soft_token_rows
            D = cfg.model.hidden_size
            ov_vals = np.zeros((Bp, C, D), np.float32)
            ov_mask = np.zeros((Bp, C), bool)
            q_span = np.zeros((Bp, C), np.int32)
            read_span = np.zeros((Bp, S), np.int32)
        for lane, (i, slot, start, count, _) in enumerate(work):
            tokens[lane, :count] = slot.prompt[start:start + count]
            positions[lane, :count] = np.arange(start, start + count)
            write_idx[lane, :count] = self.pool.write_slots(
                slot.seq_id, start, count)
            r_s, r_p, r_v = self.pool.read_slots(slot.seq_id,
                                                 start + count, S)
            read_idx[lane], read_pos[lane], read_valid[lane] = r_s, r_p, r_v
            last_i[lane] = count - 1
            temp[lane] = s.temperature[i]
            top_p[lane] = s.top_p[i]
            top_k[lane] = s.top_k[i]
            idxs[lane] = i
            if mm and slot.mm_spans is not None:
                vals, maskv = soft_token_rows(slot.mm_spans, slot.mm_soft,
                                              start, count)
                ov_vals[lane, :count] = vals
                ov_mask[lane, :count] = maskv
                q_span[lane, :count] = slot.mm_spans[start:start + count]
                # context slots map position -> image group (0 past prompt)
                sp = np.zeros(S, np.int32)
                n = min(len(slot.mm_spans), S)
                sp[:n] = slot.mm_spans[:n]
                read_span[lane] = np.where(r_v, sp[np.minimum(r_p, S - 1)],
                                           0)
        if mm:
            mm_arrays = {"ov_vals": ov_vals, "ov_mask": ov_mask,
                         "q_span": q_span, "read_span": read_span}
        seeds = self._apply_pending_seeds()
        last_lanes = [lane for lane, w in enumerate(work) if w[4]]
        form = self._chunk_form(
            C, all(w[2] % self.page_size == 0 for w in work), mm)
        if self.dispatch_hook is not None:
            arrays = {"tokens": tokens, "positions": positions,
                      "write_idx": write_idx, "read_idx": read_idx,
                      "read_pos": read_pos, "read_valid": read_valid,
                      "last_i": last_i, "temp": temp, "top_p": top_p,
                      "top_k": top_k, "idxs": idxs}
            if mm_arrays:
                arrays.update(mm_arrays)
            self.dispatch_hook("prefill", {
                "Bp": Bp, "C": C, "S": S, "seeds": seeds,
                "last_lanes": last_lanes, "mm": bool(mm_arrays),
                "form": form,
            }, arrays)
        for _, slot, start, count, _ in work:
            slot.chunks += 1
            slot.prefill_done = start + count
        key_blocks = None
        if cfg.model.has_latent and self.attn_impl == "pallas":
            # the latent flash call's grid and what it copies of it
            key_blocks = self._latent_key_blocks(
                positions, read_pos, read_valid,
                cfg.model.num_heads // max(1, cfg.tp))
        t_disp = time.perf_counter()
        captured = self.capturing
        packed = self._run_prefill_program(
            Bp, C, S, tokens, positions, write_idx, read_idx, read_pos,
            read_valid, last_i, temp, top_p, top_k, idxs, last_lanes,
            mm_arrays=mm_arrays, form=form,
            **({} if win_arrays is None else {"win_arrays": win_arrays}),
            **({"ssm_arrays": ssm_arrays} if ssm_arrays else {}))
        if ssm_arrays:
            self._count_state_work(
                "prefill", Bp, len(work), sum(w[3] for w in work),
                int(ssm_arrays["s_reset"].sum()), captured)
        self.stage.engine_dispatch_tokens.inc(
            "prefill", amount=float(sum(w[3] for w in work)))
        self._inflight.append({"kind": "prefill",
                               "seq": self._count_dispatch(
                                   "prefill", greedy=not any_sampling(temp),
                                   kv_write=form),
                               "packed": packed, "work": work,
                               "last_lanes": last_lanes,
                               "compiled": self._take_compiled_flag(),
                               "captured": captured, "S": S,
                               "rows": Bp * C,
                               "key_blocks": key_blocks,
                               "dispatched_at": t_disp})
        return len(last_lanes)

    def _count_dispatch(self, kind: str, greedy: bool,
                        kv_write: Optional[str] = None) -> int:
        """Count a dispatch just enqueued (whether it went behind records
        still unfetched; whether no lane it served sampled, so that its
        program skipped the sampler's window: ``greedy`` is the host's
        reading of the predicate the program reads, ``any_sampling``;
        ``kv_write``: the form in which a program built on ``llama.forward``
        wrote its new K/V rows, ``page`` or ``row``); returns its number in
        enqueue order."""
        self.stage.engine_dispatches.inc(kind)
        if kv_write is not None:
            self.stage.engine_prefill_kv_writes.inc(kv_write)
        if greedy:
            self.stage.engine_greedy_dispatches.inc(kind)
        if self._inflight:
            self.stage.engine_dispatches_behind.inc(kind)
        self._dispatch_seq += 1
        return self._dispatch_seq

    def _fetch_prefill(self, newest: bool = False) -> List[StepOutput]:
        """Fetch (blocking) the sampled tokens of the chunk dispatch at the
        head of the window (or at its end) with ONE host round-trip and
        account it; results are kept only for lanes whose prompt the chunk
        completed."""
        rec = self._inflight.pop() if newest else self._inflight.popleft()
        work = rec["work"]
        spans = [(w[2], w[3]) for w in work]
        self.phase.to("prefill_fetch")
        # dynalint: ok(host-sync) THE designed prefill fetch: one packed
        # [Bp,2] (token,logprob) array per dispatch, batched across lanes
        packed_np = np.asarray(rec["packed"])     # ONE host fetch
        self.phase.to("emit")
        cols = self._packed_cols
        self._count_model_work(
            "prefill", spans,
            packed_np[0, 2] if "experts_hit" in cols else None,
            rec["captured"], rec["S"],
            held=(packed_np[0, 2 + cols.index("held")]
                  * sum(n for _, n in spans) / rec["rows"]
                  if "held" in cols else None),
            key_blocks=rec["key_blocks"],
            # (a chunk routes its padding rows too, as ``held``)
            zero=(packed_np[0, 2 + cols.index("zero")]
                  * sum(n for _, n in spans) / rec["rows"]
                  if "zero" in cols else None))
        if self.win is not None:
            for _, slot, start, _, _ in work:
                self._window_fetched(slot.seq_id, start, "prefill")
        now = time.monotonic()
        if not rec["compiled"]:
            from ..utils.roofline import prefill_cost

            fl, by, tk = prefill_cost(self.costs, spans)
            self.goodput.account(
                fl, by, time.perf_counter() - rec["dispatched_at"], tk)
        outs: List[StepOutput] = []
        for lane in rec["last_lanes"]:
            i, slot = work[lane][:2]
            if self.slots[i] is not slot:
                continue   # freed since dispatch (cancel): discard
            t = int(packed_np[lane, 0])
            lp = float(packed_np[lane, 1])
            try:
                self._append_generated(slot, t)
            except OutOfPages:
                outs.append(StepOutput(slot.seq_id, t, lp,
                                       FinishReason.ERROR,
                                       error="out of KV pages appending the "
                                             "first generated token"))
                self._free_slot(i)
                continue
            slot.cum_logprob += lp
            slot.t_first_token = now
            self._request_stage(
                slot, "prefill", slot.t_admitted, now,
                prompt_tokens=len(slot.prompt),
                prefix_hit_tokens=slot.prefix_hit, chunks=slot.chunks)
            fin = self._finish_reason(slot, t)
            outs.append(StepOutput(slot.seq_id, t, slot.cum_logprob, fin,
                                   prompt_tokens=len(slot.prompt),
                                   token_logprob=lp, first_token_at=now))
            if fin is not None:
                self._free_slot(i)
        return outs

    def _append_generated(self, slot: _Slot, token: int) -> None:
        slot.generated += 1
        slot.last_token = token
        self.pool.extend(slot.seq_id, [token])

    def _finish_reason(self, slot: _Slot, token: int) -> Optional[FinishReason]:
        req = slot.request
        if not req.stop.ignore_eos:
            eos = set(req.eos_token_ids) | set(req.stop.stop_token_ids)
            if token in eos and slot.generated >= (req.stop.min_tokens or 0):
                return FinishReason.EOS
        if req.stop.max_tokens and slot.generated >= req.stop.max_tokens:
            return FinishReason.LENGTH
        if len(slot.prompt) + slot.generated >= self.cfg.max_context:
            return FinishReason.LENGTH
        return None

    # ------------------------------------------------------------------
    def _decode_eligible(self, lookahead: Optional[int] = None):
        """(slot_idx, slot, phys_len) for every decode-ready slot whose next
        dispatch's pages could be reserved; deferred = ready but no pages.
        ``lookahead`` is the page reservation beyond phys (default: the
        chained decode window; the spec path passes its verify window)."""
        N = self.cfg.decode_steps if lookahead is None else lookahead
        active, deferred = [], []
        for i, slot in enumerate(self.slots):
            if slot is None or slot.prefill_done < len(slot.prompt):
                continue
            phys = self._phys_len(slot)
            try:
                # reserve room for N speculative tokens up front
                self._ensure_pages(slot.seq_id, phys + N)
            except OutOfPages:
                # pool pressure: defer this slot — batchmates finishing will
                # free pages — rather than killing a healthy request
                deferred.append((i, slot))
                continue
            active.append((i, slot, phys))
        return active, deferred

    @staticmethod
    def _phys_len(slot: _Slot) -> int:
        """Tokens in the sequence once its next decode dispatch starts. A
        decode-ready slot holds its first token, fetched or still on the
        device (a chunk that completed its prompt in this iteration)."""
        return slot.sched_len or len(slot.prompt) + max(slot.generated, 1)

    def _can_chain(self, rec: Dict[str, Any], joining: Dict[int, int]) -> bool:
        """True if the next decode dispatch can be enqueued straight off
        ``rec``'s on-device outputs, ``rec`` being the newest decode record
        in flight (chunks may have been enqueued since): same membership but
        for the slots in ``joining`` (slot index -> lane of the newest
        chunk, which completes their prompts), and pages available for
        every lane."""
        # the chained dispatch feeds the previous dispatch's on-device
        # final_tok to EVERY lane, so the decode-ready set must be EXACTLY
        # the lanes that were active in that dispatch, plus those whose
        # first token the newest chunk holds on the device: a newly
        # injected or newly eligible slot (inject_prefilled, deferred slot
        # unblocking) has a real last_token no device array contains
        ready_now = {i for i, s in enumerate(self.slots)
                     if s is not None and s.prefill_done >= len(s.prompt)}
        if ready_now != {i for i, _, _ in rec["active"]} | set(joining):
            return False
        for i, slot, _ in rec["active"]:
            if self.slots[i] is not slot and i not in joining:
                return False   # membership changed (cancel) -> sync
        N = self.cfg.decode_steps
        for i in sorted(ready_now):
            slot = self.slots[i]
            try:
                self._ensure_pages(slot.seq_id, self._phys_len(slot) + N)
            except OutOfPages:
                return False
        return True

    def _evict_largest_deferred(self, deferred, out: List[StepOutput]) -> None:
        """No decode-ready lane can be dispatched and every deferred lane
        is blocked on KV capacity: evict the largest consumer so the rest
        of the system unblocks (capacity error). Shared by the chained
        decode path and the speculative verify path."""
        i, slot = max(deferred,
                      key=lambda t: len(self.pool.seqs[t[1].seq_id].pages))
        out.append(StepOutput(
            slot.seq_id, slot.last_token, slot.cum_logprob,
            FinishReason.ERROR,
            error="evicted under KV pool pressure (no capacity to "
                  "continue decoding)"))
        self._free_slot(i)

    def _dispatch_decode(self, out: List[StepOutput],
                         completing: bool = False) -> bool:
        """Enqueue one multi-step decode dispatch WITHOUT fetching results,
        behind whatever is in flight. With a decode dispatch among it,
        chain off the newest one's on-device token and key arrays (no host
        data dependency), or enqueue nothing if its lanes are not the
        decode-ready ones; with none, every lane's last token is on the
        host. ``completing``: the newest record is a chunk that completes
        prompts; their first tokens are on the device too, and join the
        chained tokens there (with no decode dispatch to chain off, the
        caller fetches the chunk and comes back). Returns whether a
        dispatch was enqueued."""
        self.phase.to("decode_build")
        B = self.cfg.max_batch
        N = self.cfg.decode_steps
        newest = next((r for r in reversed(self._inflight)
                       if r["kind"] == "decode"), None)
        chain = newest is not None
        joining: Dict[int, int] = {}     # slot index -> lane of the chunk
        if completing:
            if not chain:
                return False
            chunk = self._inflight[-1]
            joining = {chunk["work"][lane][0]: lane
                       for lane in chunk["last_lanes"]}
        if chain and not self._can_chain(newest, joining):
            return False
        active, deferred = self._decode_eligible()
        if not active:
            # (pages a deferred release holds are about to come back: no
            # lane is evicted for want of them)
            if deferred and not chain and not self._deferred_release:
                self._evict_largest_deferred(deferred, out)
            return False
        self._flush_evictions()   # ensure_pages() may have evicted pages
        S = self._bucket(max(phys for _, _, phys in active) + N,
                         self.s_buckets)
        P = S // self.page_size

        lengths = np.ones(B, np.int32)    # inactive lanes write into page 0
        page_tables = np.zeros((B, P), np.int32)
        w_tables = None if self.win is None else np.zeros((B, P), np.int32)
        for i, slot, phys in active:
            lengths[i] = phys
            page_tables[i] = self.pool.page_table_row(slot.seq_id, P)
            if w_tables is not None:
                w_tables[i] = self.win.table_row(slot.seq_id, P)
            slot.sched_len = phys + N
        if chain:
            tokens = None   # resolved to the previous dispatch's device toks
        else:
            tokens = np.zeros(B, np.int32)
            for i, slot, _ in active:
                tokens[i] = slot.last_token

        # lanes whose SEQUENCE changed since their last decode dispatch
        # restart their penalty counts in-program (a chained dispatch has
        # the membership of the one before it but for the lanes that join)
        fresh = np.zeros(B, bool)
        for i, slot, _ in active:
            if self._decode_seen.get(i) != slot.seq_id:
                fresh[i] = True
                self._decode_seen[i] = slot.seq_id
        active_mask = np.zeros(B, bool)
        for i, _, _ in active:
            active_mask[i] = True

        s = self.sampling
        if self.dispatch_hook is not None:
            payload = {"page_tables": page_tables, "lengths": lengths,
                       "temp": s.temperature, "top_p": s.top_p,
                       "top_k": s.top_k, "fresh": fresh,
                       "active_mask": active_mask,
                       "freq_pen": s.freq_pen, "pres_pen": s.pres_pen}
            if tokens is not None:
                payload["tokens"] = tokens
            self.dispatch_hook("decode", {"S": S, "chain": chain,
                                          "joining": sorted(joining.items())},
                               payload)
        packed, final_tok = self._run_decode_program(
            S, tokens, page_tables, lengths, fresh, active_mask, joining,
            **({} if w_tables is None else {"w_tables": w_tables}))
        if self.c_pool is not None:
            self._count_state_work("decode", B * N, len(active) * N,
                                   len(active) * N, 0, self.capturing)
        if self._attn_calls:
            self._count_attn_pages(lengths, active_mask, P)
        self.stage.engine_dispatch_tokens.inc(
            "decode", amount=float(len(active) * N))
        self._inflight.append({"kind": "decode",
                               "seq": self._count_dispatch(
                                   "decode", greedy=not any_sampling(
                                       s.temperature, active_mask)),
                               "packed": packed, "final_tok": final_tok,
                               "active": active,
                               "lengths": [phys for _, _, phys in active],
                               "compiled": self._take_compiled_flag(),
                               "captured": self.capturing, "S": S,
                               "dispatched_at": time.perf_counter()})
        # flight recorder: the hang watchdog judges "a dispatch in flight
        # with no fetch completing for N x the EWMA step time" off this
        _flightrec.hb_begin("engine.decode", stall="decode")
        _flightrec.note_event("engine.dispatch", depth=len(self._inflight),
                              batch=len(active), steps=S)
        return True

    def _run_decode_program(self, S: int, tokens, page_tables, lengths,
                            fresh, active_mask, joining=None,
                            w_tables=None):
        """Execute the multi-step decode program. ``tokens=None`` chains off
        the previous dispatch's on-device final tokens, with the newest
        chunk's sampled tokens written over the slots in ``joining`` (slot
        index -> lane of that chunk). The SAME code path runs on the leader
        and on follower mirrors (multi-host lockstep)."""
        if tokens is None:
            tokens = self._last_final_tok
            if joining:
                lane_slot = np.full(self._last_prefill_tok.shape[0],
                                    self.cfg.max_batch, np.int32)
                for i, lane in joining.items():
                    lane_slot[lane] = i
                # dynalint: ok(recompile-hazard) one size per lane bucket
                # Bp of the chunk programs, each compiled in warm-up
                tokens = self._join_fn(tokens, self._last_prefill_tok,
                                       lane_slot)
        else:
            # same placement as the chained case, so both are ONE compiled
            # program per bucket (jit keys on argument placement; a host
            # array here cost a second compile of every decode bucket —
            # ~11 s each for llama-3.2-1b on a v5e)
            # dynalint: ok(flow-accounting) B int32 token ids per unchained
            # dispatch — dispatch arguments, like the host page tables
            # beside them, are not a KV/weight flow
            tokens = global_put(tokens, self._rep_sharding)
        s = self.sampling
        fn = self._decode_fn(S)
        self.phase.to("decode", f"dynamo.decode[S{S}]")
        packed, final_tok, new_key, kp, vp, self.gen_counts, *ip = fn(
            self.params, tokens, self.k_pool, self.v_pool,
            page_tables, lengths, s.temperature, s.top_p, s.top_k, s.key,
            self.gen_counts, fresh, active_mask, s.freq_pen, s.pres_pen,
            **self._idx(),
            **({} if w_tables is None else {"w_tables": w_tables}))
        self._take_pools((kp, vp, *ip))
        self.phase.to("decode_build")
        s.key = new_key
        self._last_final_tok = final_tok
        return packed, final_tok

    # ------------------------------------------------------------------
    # speculative decoding (engine/spec.py owns proposers + acceptance)
    # ------------------------------------------------------------------
    def _run_verify_program(self, S: int, K: int, tokens, page_tables,
                            lengths, fresh, active_mask, upd_tok, upd_mask):
        """Execute the verify program. The SAME code path runs on the
        leader and on follower mirrors (multi-host lockstep)."""
        s = self.sampling
        fn = self._verify_fn(S, K)
        self.phase.to("verify", f"dynamo.verify[S{S},K{K}]")
        (packed, new_key, self.k_pool, self.v_pool,
         self.gen_counts) = fn(
            self.params, tokens, self.k_pool, self.v_pool, page_tables,
            lengths, s.temperature, s.top_p, s.top_k, s.key,
            self.gen_counts, fresh, active_mask, s.freq_pen, s.pres_pen,
            upd_tok, upd_mask)
        self.phase.to("decode_build")
        s.key = new_key
        return packed

    @staticmethod
    def _spec_opt_out(req: BackendInput) -> bool:
        """Lanes that must not speculate (they still ride the verify
        dispatch with zero drafts, which IS a plain single-token decode
        step): per-request opt-out, and penalty requests — the verify
        program applies penalty counts per-dispatch, which is only exact
        when each dispatch commits one token."""
        if getattr(req, "no_spec", False):
            return True
        sp = req.sampling
        return bool(sp.frequency_penalty or sp.presence_penalty)

    def _spec_seq_state(self, slot: _Slot):
        from .spec import SeqSpecState

        st = self._spec_states.get(slot.seq_id)
        if st is None:
            # created at first decode entry: exactly one generated token
            # exists (the prefill- or injection-sampled first token)
            st = SeqSpecState(
                tokens=list(slot.prompt) + [int(slot.last_token)],
                k=self.spec.k_max,
                pending=[int(slot.last_token)])
            self._spec_states[slot.seq_id] = st
        return st

    def _spec_round(self, out: List[StepOutput]) -> None:
        """One synchronous speculative-decoding round: propose k drafts per
        lane, verify all of them in ONE wider forward, accept host-side,
        commit only accepted tokens. Unlike the chained decode path this is
        a sync point every round (acceptance needs the fetch), but each
        dispatch can commit up to k+1 tokens instead of one."""
        from .sampling import spec_accept, spec_unpack

        cfg, sp = self.cfg, self.spec
        self.phase.to("decode_build")
        B = cfg.max_batch
        # reserve the whole verify window (k drafts + bonus) up front:
        # rollback is then pure bookkeeping, never data movement
        active, deferred = self._decode_eligible(lookahead=sp.k_max + 1)
        if not active:
            if deferred:
                self._evict_largest_deferred(deferred, out)
            return
        self._flush_evictions()   # ensure_pages() may have evicted pages

        drafts: Dict[int, List[int]] = {}
        for i, slot, phys in active:
            st = self._spec_seq_state(slot)
            d: List[int] = []
            if not self._spec_opt_out(slot.request):
                d = self.proposer.propose(slot.seq_id, st, st.k)[:st.k]
            drafts[i] = [int(x) for x in d]
            self.spec_proposed_total += len(d)
            if d:
                self.stage.spec_proposed.inc(amount=float(len(d)))

        K = sp.bucket(max(len(d) for d in drafts.values()))
        T = K + 1
        S = self._bucket(max(phys for _, _, phys in active) + K,
                         self.s_buckets)
        P = S // self.page_size
        U = sp.k_max + 1
        tokens = np.zeros((B, T), np.int32)
        lengths = np.ones(B, np.int32)     # inactive lanes write to page 0
        page_tables = np.zeros((B, P), np.int32)
        upd_tok = np.zeros((B, U), np.int32)
        upd_mask = np.zeros((B, U), bool)
        fresh = np.zeros(B, bool)
        active_mask = np.zeros(B, bool)
        for i, slot, phys in active:
            st = self._spec_states[slot.seq_id]
            d = drafts[i]
            tokens[i, 0] = slot.last_token
            tokens[i, 1:1 + len(d)] = d
            lengths[i] = phys
            page_tables[i] = self.pool.page_table_row(slot.seq_id, P)
            upd = st.pending[-U:]
            upd_tok[i, :len(upd)] = upd
            upd_mask[i, :len(upd)] = True
            active_mask[i] = True
            if self._decode_seen.get(i) != slot.seq_id:
                fresh[i] = True
                self._decode_seen[i] = slot.seq_id

        s = self.sampling
        if self.dispatch_hook is not None:
            self.dispatch_hook("verify", {"S": S, "K": K}, {
                "tokens": tokens, "page_tables": page_tables,
                "lengths": lengths, "fresh": fresh,
                "active_mask": active_mask, "upd_tok": upd_tok,
                "upd_mask": upd_mask, "temp": s.temperature,
                "top_p": s.top_p, "top_k": s.top_k,
                "freq_pen": s.freq_pen, "pres_pen": s.pres_pen})
        t0 = time.perf_counter()
        packed = self._run_verify_program(
            S, K, tokens, page_tables, lengths, fresh, active_mask,
            upd_tok, upd_mask)
        self.stage.engine_dispatches.inc("verify")
        # its slots start mid-page: llama.forward's row scatter
        self.stage.engine_prefill_kv_writes.inc("row")
        self.stage.engine_dispatch_tokens.inc(
            "verify", amount=float(sum(len(d) + 1 for d in drafts.values())))
        self.phase.to("decode_fetch")
        # dynalint: ok(host-sync) THE designed verify fetch: one packed
        # array per verify dispatch covers k+1 positions for every lane
        packed_np = np.asarray(packed)              # ONE host fetch
        self.phase.to("emit")
        r = spec_unpack(packed_np, K)
        if not self._take_compiled_flag():
            from ..utils.roofline import verify_cost

            fl, by, tk = verify_cost(
                self.costs, [phys for _, _, phys in active], T)
            self.goodput.account(fl, by, time.perf_counter() - t0, tk)
        n_emitted = 0
        self.spec_dispatch_total += 1               # one verify dispatch
        for i, slot, phys in active:
            st = self._spec_states[slot.seq_id]
            d = drafts[i]
            lane = {k: v[i] for k, v in r.items()}
            greedy = float(s.temperature[i]) <= 0.0
            toks, lps, acc = spec_accept(d, greedy, lane)
            self.spec_accepted_total += acc
            if d:
                self.stage.spec_accepted.inc(amount=float(acc))
                self.stage.spec_per_dispatch.observe(value=float(acc))
            st.pending = []
            for tok, lp in zip(toks, lps):
                self.pool.account_tokens(slot.seq_id, [tok])
                slot.generated += 1
                slot.last_token = tok
                slot.cum_logprob += lp
                st.tokens.append(tok)
                st.pending.append(tok)
                n_emitted += 1
                fin = self._finish_reason(slot, tok)
                out.append(StepOutput(slot.seq_id, tok, slot.cum_logprob,
                                      fin, token_logprob=lp))
                if fin is not None:
                    self._free_slot(i)
                    break
            if d and self.slots[i] is slot:
                st.k = sp.next_k(st.k, acc, len(d))
        if n_emitted:
            self.stage.decode_step.observe(
                value=(time.perf_counter() - t0) / n_emitted)

    def mirror_dispatch(self, kind: str, meta: Dict[str, Any],
                        arrs: Dict[str, np.ndarray]) -> None:
        """Follower-side replay of a leader dispatch (multi-host mode): runs
        the identical jitted program with the identical inputs so every
        process's sharded params/KV/key state advances in lockstep. Results
        are not fetched — only the leader streams tokens to clients."""
        if kind == "prefill":
            for slot_idx, seed in meta.get("seeds", []):
                self._pending_seeds.append((int(slot_idx), int(seed)))
            self._apply_pending_seeds()
            mm_arrays = ({k: arrs[k] for k in ("ov_vals", "ov_mask",
                                               "q_span", "read_span")}
                         if meta.get("mm") else None)
            self._run_prefill_program(
                meta["Bp"], meta["C"], meta["S"], arrs["tokens"],
                arrs["positions"], arrs["write_idx"], arrs["read_idx"],
                arrs["read_pos"], arrs["read_valid"], arrs["last_i"],
                arrs["temp"], arrs["top_p"], arrs["top_k"], arrs["idxs"],
                [int(x) for x in meta.get("last_lanes", [])],
                mm_arrays=mm_arrays, form=meta.get("form"))
        elif kind == "decode":
            s = self.sampling
            s.temperature = arrs["temp"]
            s.top_p = arrs["top_p"]
            s.top_k = arrs["top_k"]
            s.freq_pen = arrs["freq_pen"]
            s.pres_pen = arrs["pres_pen"]
            self._run_decode_program(
                meta["S"], arrs.get("tokens"), arrs["page_tables"],
                arrs["lengths"], arrs["fresh"], arrs["active_mask"],
                {int(i): int(lane) for i, lane in meta.get("joining", ())})
        elif kind == "verify":
            s = self.sampling
            s.temperature = arrs["temp"]
            s.top_p = arrs["top_p"]
            s.top_k = arrs["top_k"]
            s.freq_pen = arrs["freq_pen"]
            s.pres_pen = arrs["pres_pen"]
            self._run_verify_program(
                meta["S"], meta["K"], arrs["tokens"], arrs["page_tables"],
                arrs["lengths"], arrs["fresh"], arrs["active_mask"],
                arrs["upd_tok"], arrs["upd_mask"])
        else:
            raise ValueError(f"unknown dispatch kind {kind!r}")
        # a follower has no loop of phases: what it does between two
        # replayed dispatches (waiting for the leader) is not a phase
        self.phase.close()

    def _process_inflight(self, out: List[StepOutput],
                          n: Optional[int] = None) -> None:
        """Fetch (blocking) and account the ``n`` oldest in-flight
        dispatches (all of them by default), oldest first, and release the
        pages that only they still held."""
        for _ in range(len(self._inflight) if n is None else n):
            out.extend(self._fetch_prefill()
                       if self._inflight[0]["kind"] == "prefill"
                       else self._fetch_decode())
            self._apply_deferred_release()

    def _fetch_decode(self) -> List[StepOutput]:
        """Fetch (blocking) and account the decode dispatch at the head of
        the window."""
        rec = self._inflight.popleft()
        self.phase.to("decode_fetch")
        # dynalint: ok(host-sync) THE designed decode fetch: one [N,B,2]
        # array per N-step dispatch — 1/N host round-trips per token, and
        # the pipelined next dispatch is already running when we block here
        packed_np = np.asarray(rec["packed"])     # [N, B, 2] — ONE fetch
        self.phase.to("emit")
        N = packed_np.shape[0]
        cols = self._decode_cols
        col = lambda c: packed_np[:, 0, 2 + cols.index(c)].sum()
        form = self._decode_moe_form
        self._count_model_work(
            "decode", [(s0 - 1, N) for s0 in rec["lengths"]],
            col("experts_hit") if "experts_hit" in cols else None,
            rec.get("captured", False), rec.get("S", 0),
            # (a program that routes its busy rows alone counts theirs
            # alone; a share dispatched dense counts every row's)
            held=(col("held") * (1.0 if self._decode_routes_busy else len(
                rec["lengths"]) / packed_np.shape[1])
                  if "held" in cols else None), steps=N,
            sorted_calls=(col("sorted") if form == "by_hit" else
                          self.cfg.model.routed_layers * N * (
                              form == "sorted")),
            # (identity assignments are the busy rows' whatever the form)
            zero=col("zero") if "zero" in cols else None)
        if self.win is not None:
            steps = self.stage.kv_resident_token_steps
            for (_, slot, _), s0 in zip(rec["active"], rec["lengths"]):
                self._window_fetched(slot.seq_id, s0 - 1, "decode")
                steps.inc("global", amount=s0 + N - 1)
                steps.inc("window", amount=self.win.tokens_held(
                    slot.seq_id, s0 + N - 1))
        if N and "dispatched_at" in rec:
            # effective per-token decode latency: dispatch -> results on
            # host, amortized over the dispatch's N steps (pipelined
            # dispatches overlap compute, which this deliberately reflects)
            elapsed = time.perf_counter() - rec["dispatched_at"]
            self.stage.decode_step.observe(value=elapsed / N)
            # after the blocking fetch (a wedged device shows up THERE):
            # feed the watchdog's step-time EWMA and balance hb_begin
            _flightrec.hb_done("engine.decode", elapsed / N)
            _flightrec.note_event("engine.step", s=round(elapsed, 6), n=N,
                                  compiled=bool(rec.get("compiled")))
            if not rec.get("compiled"):
                from ..utils.roofline import decode_cost

                fl, by, tk = decode_cost(self.costs, rec["lengths"], N)
                self.goodput.account(fl, by, elapsed, tk)
        else:
            _flightrec.hb_done("engine.decode")
        outs: List[StepOutput] = []
        for i, slot, _ in rec["active"]:
            if self.slots[i] is not slot:
                continue   # freed since dispatch (finish/cancel): discard
            for j in range(N):
                t = int(packed_np[j, i, 0])
                self.pool.account_tokens(slot.seq_id, [t])
                slot.generated += 1
                slot.last_token = t
                tok_lp = float(packed_np[j, i, 1])
                slot.cum_logprob += tok_lp
                fin = self._finish_reason(slot, t)
                outs.append(StepOutput(slot.seq_id, t, slot.cum_logprob, fin,
                                       token_logprob=tok_lp))
                if fin is not None:
                    # overshoot tokens beyond the finish are discarded; their
                    # page-pool writes are inside this seq's own pages, which
                    # stay held until the records now in flight are fetched
                    self._free_slot(i)
                    break
        return outs


def _put_bursts(puts) -> None:
    for q, burst in puts:
        q.put_nowait(burst)


def _set_result(fut, res) -> None:
    if not fut.done():
        fut.set_result(res)


def _set_exception(fut, exc) -> None:
    if not fut.done():
        fut.set_exception(exc)


def _pallas_probe(m, cfg, device) -> None:
    """Compile+run both Pallas kernels once on ``device`` at engine shapes
    (tiny batch). Fail-fast for the auto path: a kernel the chip's compiler
    refuses raises here, at construction, with the compiler's message —
    seconds at init instead of every request erroring (or, worse, every
    request silently served by another path)."""
    # probe the PER-SHARD instantiation the shard_map wrappers actually
    # run at this tp — full-model head counts would validate a kernel
    # that never executes at tp>1
    tp = max(1, cfg.tp)
    Hq = m.num_heads // tp
    Hkv = (m.num_kv_heads // tp if m.num_kv_heads % tp == 0
           else m.num_kv_heads)
    page = cfg.page_size
    # probe the exact kernel variants this model will run: softcap and
    # (on sliding models) the windowed variant are distinct Mosaic
    # lowerings from the plain causal one; a per-kind model's two kinds
    # differ in head count and sink as well, and its K rows are as wide as
    # the pools store them
    kw = dict(scale=m.attn_scale, softcap=m.attn_logit_softcap)
    Dk, Dv = m.k_store_dim, m.v_dim
    if m.has_latent:
        # all heads against one row a key: the shared rotary key's pool and
        # the compressed vectors', the queries' second part beside them
        Dk, Dv = m.latent_k_store, m.kv_lora_rank
        with jax.default_device(device):
            T = max(8, min(128, cfg.prefill_chunk))
            pos = jnp.zeros((2, T), jnp.int32)
            paged_attention(
                jnp.zeros((2, Hq, Dk), m.dtype),
                jnp.zeros((2, 1, 3, page, Dk), m.dtype),
                jnp.zeros((2, 1, 3, page, Dv), m.dtype),
                jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), jnp.int32), 1,
                interpret=False, latent=jnp.zeros((2, Hq, Dv), m.dtype),
                **kw).block_until_ready()
            flash_attention(
                jnp.zeros((2, T, Hq, Dk), m.dtype),
                jnp.zeros((2, T, 1, Dk), m.dtype),
                jnp.zeros((2, T, 1, Dv), m.dtype), pos, pos, pos < 1,
                interpret=False, latent=jnp.zeros((2, T, Hq, Dv), m.dtype),
                **kw).block_until_ready()
        return
    if m.has_window:
        variants = [(k.kv_heads, k.window,
                     m.sink_window if k.window else m.sink_full)
                    for k in cache_kinds(m)]
    else:
        variants = [(Hkv, w, False) for w in (
            [None, m.sliding_window] if m.sliding_window is not None
            else [None])]
    with jax.default_device(device):
        pt = jnp.zeros((2, 1), jnp.int32)
        ln = jnp.ones((2,), jnp.int32)
        T = max(8, min(128, cfg.prefill_chunk))
        pos = jnp.zeros((2, T), jnp.int32)
        for hkv, w, sunk in variants:
            q = jnp.zeros((2, Hq, Dk), m.dtype)
            f = m.kv_fold     # 2 layers' pool, as the engine stores it
            kp = jnp.zeros((2, hkv, 3, page // f, f * Dk), m.dtype)
            vp = kp if Dv == Dk else jnp.zeros((2, hkv, 3, page, Dv),
                                               m.dtype)
            qf = jnp.zeros((2, T, Hq, Dk), m.dtype)
            kf = jnp.zeros((2, T, hkv, Dk), m.dtype)
            vf = kf if Dv == Dk else jnp.zeros((2, T, hkv, Dv), m.dtype)
            sink = {"sink": jnp.zeros((Hq,), jnp.float32)} if sunk else {}
            paged_attention(q, kp, vp, pt, ln, 1, interpret=False,
                            window=w, **kw, **sink,
                            **({"fold": f} if f > 1 else {})
                            ).block_until_ready()
            flash_attention(qf, kf, vf, pos, pos, pos < 1, interpret=False,
                            window=w, **kw, **sink).block_until_ready()


def _has_safetensors(path: str) -> bool:
    import glob
    import os

    return bool(glob.glob(os.path.join(path, "*.safetensors")))


def _gguf_file(path: str) -> Optional[str]:
    """The GGUF weights file for ``path``: the file itself or the first
    *.gguf inside the directory."""
    import glob
    import os

    if os.path.isfile(path) and path.endswith(".gguf"):
        return path
    hits = sorted(glob.glob(os.path.join(path, "*.gguf")))
    return hits[0] if hits else None


# ---------------------------------------------------------------------------
# Async facade
# ---------------------------------------------------------------------------

class _ProfileCapture:
    """``DYN_PROFILE_DIR``: an XLA profile of the first ``DYN_PROFILE_STEPS``
    (default 32) working engine iterations, driven from the engine thread.
    The phase annotations name the host side of the device timeline. The
    Python tracer is off (it slows the host it measures), and the capture
    is stopped and written by a thread of its own, which takes about a
    second a megabyte, so the engine goes on dispatching meanwhile. At
    start and at stop the engine thread emits
    ``dyn.clock[epoch_ns=..,mono_ns=..]``, which pins ``time.time()``
    (request-trace spans) and ``time.perf_counter()`` to the capture's
    clock."""

    def __init__(self) -> None:
        self.dir = os.environ.get("DYN_PROFILE_DIR")
        try:
            self.steps = int(os.environ.get("DYN_PROFILE_STEPS", "32"))
        except ValueError:
            # a typo'd env var must not kill the engine thread
            log.warning("invalid DYN_PROFILE_STEPS=%r; using 32",
                        os.environ.get("DYN_PROFILE_STEPS"))
            self.steps = 32
        self.active = False
        # stop() is the engine thread's and, at shutdown, the closing
        # thread's too: one of them ends the capture
        self._lock = threading.Lock()
        self._writer: Optional[threading.Thread] = None

    @staticmethod
    def _clock_anchor() -> None:
        with _trace_annotation(f"dyn.clock[epoch_ns={time.time_ns()},"
                               f"mono_ns={time.perf_counter_ns()}]"):
            pass

    def before_step(self) -> None:
        if not self.dir or self.active or self.steps <= 0:
            return
        with self._lock:
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=options)
            except Exception:
                log.exception("DYN_PROFILE_DIR capture failed to start")
                self.dir = None
                return
            self.active = True
            self._clock_anchor()
        log.info("XLA profile capture started -> %s", self.dir)

    def after_step(self) -> None:
        if self.active:
            self.steps -= 1
            if self.steps <= 0:
                self.stop()

    def stop(self) -> None:
        """End the capture; a writer thread does the slow part."""
        with self._lock:
            if not self.active:
                return
            self.active = False
            self._clock_anchor()
            self._writer = threading.Thread(
                target=self._write, args=(self.dir,),
                name="jax-profile-writer", daemon=True)
            self.dir = None
            self._writer.start()

    @staticmethod
    def _write(directory: str) -> None:
        t0 = time.perf_counter()
        try:
            jax.profiler.stop_trace()
            log.info("XLA profile capture written to %s in %.2fs",
                     directory, time.perf_counter() - t0)
        except Exception:
            log.exception("stopping XLA profile failed")

    def close(self, timeout: float = 120.0) -> None:
        """Shutdown: JAX only writes trace files on ``stop_trace``, so a
        capture cut short is still finalized, and a writer is waited for."""
        self.stop()
        if self._writer is not None:
            self._writer.join(timeout)
            if self._writer.is_alive():
                log.warning("XLA profile capture still being written "
                            "after %.0fs", timeout)


class JaxEngine(AsyncEngine[BackendInput, EngineOutput]):
    """AsyncEngine facade: one background engine thread runs EngineCore."""

    def __init__(self, cfg: JaxEngineConfig,
                 devices: Optional[List[jax.Device]] = None):
        self.core = EngineCore(cfg, devices)
        core, dev0 = self.core, self.core.mesh.devices.flat[0]
        core.stage.engine_info.set(
            str(os.getpid()), core.attn_impl, core.decode_attn_impl,
            core.paged_kernel or "none", dev0.platform, dev0.device_kind,
            str(core.mesh.devices.size), core.goodput.peaks.source,
            "+".join(k.label() for k in core.cache_kinds),
            core.decode_kv_write, core.moe_dispatch, core.prefill_kv_write,
            core.attn_proj, value=1)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._queues: Dict[str, asyncio.Queue] = {}
        self._inbox: thread_queue.Queue = thread_queue.Queue()
        self._wake = threading.Event()
        self._running = True
        self._capture = _ProfileCapture()
        self._thread = threading.Thread(target=self._run, name="jax-engine",
                                        daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    def _run(self) -> None:
        from ..utils.prometheus import stage_metrics

        stage = stage_metrics()
        capture = self._capture
        # every moment of this loop belongs to one phase (see PHASES); the
        # core switches between its own inside step()
        phase = self.core.phase
        last_gauges = 0.0
        last_disp = 0
        while self._running:
            phase.to("inbox")
            while True:
                try:
                    kind, seq_id, payload = self._inbox.get_nowait()
                except thread_queue.Empty:
                    break
                if kind == "submit":
                    self.core.submit(seq_id, *payload)
                elif kind == "cancel":
                    self.core.cancel(seq_id)
                elif kind == "inject":
                    try:
                        so = self.core.inject_prefilled(seq_id, *payload)
                    except Exception as e:  # noqa: BLE001
                        log.exception("KV injection failed")
                        so = StepOutput(seq_id, 0, 0.0, FinishReason.ERROR,
                                        error=f"KV injection failed: {e}")
                    self._hand_off([so])
                elif kind == "ingest_begin":
                    try:
                        self.core.begin_stream_inject(seq_id, payload)
                    except Exception as e:  # noqa: BLE001
                        log.exception("stream-inject begin failed")
                        self._ingest_fail(seq_id, e)
                elif kind == "ingest_layer":
                    # a begin/earlier-layer failure already dropped the
                    # state and delivered the error: later commands no-op
                    if seq_id in self.core._stream_injects:
                        try:
                            self.core.stream_inject_layer(seq_id, *payload)
                        except Exception as e:  # noqa: BLE001
                            log.exception("stream-inject layer failed")
                            self._ingest_fail(seq_id, e)
                elif kind == "ingest_finish":
                    if seq_id in self.core._stream_injects:
                        try:
                            self._hand_off([self.core.finish_stream_inject(
                                seq_id, *payload)])
                        except Exception as e:  # noqa: BLE001
                            log.exception("stream-inject finish failed")
                            self._ingest_fail(seq_id, e)
                elif kind == "ingest_abort":
                    self.core.abort_stream_inject(seq_id)
                elif kind == "prefill_extract":
                    request, loop, fut = payload
                    try:
                        res = self.core.prefill_extract(seq_id, request)
                        loop.call_soon_threadsafe(_set_result, fut, res)
                    except Exception as e:
                        log.exception("prefill_extract failed")
                        loop.call_soon_threadsafe(_set_exception, fut, e)
                    phase.to("inbox")   # the prefill moved through its own
                elif kind == "swap":
                    # model-mobility hot-swap: runs on the engine thread
                    # (single-threaded core contract) post-drain; typed
                    # SwapError propagates to the agent's fallback path
                    host_params, new_cfg, loop, fut = payload
                    from ..fleet.mobility.swap import hot_swap
                    try:
                        res = hot_swap(self.core, host_params, new_cfg)
                        loop.call_soon_threadsafe(_set_result, fut, res)
                    except Exception as e:
                        log.exception("weight hot-swap failed")
                        loop.call_soon_threadsafe(_set_exception, fut, e)
            if not self.core.has_work:
                # idle: keep the windowed goodput gauges honest (they
                # decay to 0 as the last burst ages out of the window)
                now = time.monotonic()
                if now - last_gauges >= 5.0:
                    last_gauges = now
                    phase.to("housekeeping")
                    self._set_goodput_gauges(stage)
                phase.to("idle")
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            capture.before_step()
            self.core.capturing = capture.active
            try:
                outs = self.core.step()
            except Exception as e:  # engine must never die silently
                log.exception("engine step failed")
                outs = [StepOutput(sid, 0, 0.0, FinishReason.ERROR,
                                   error=f"engine step failed: {e}")
                        for sid in list(self.core.by_seq)]
                for sid in list(self.core.by_seq):
                    self.core.cancel(sid)
                self.core._reap_cancelled()
            phase.to("housekeeping")
            stage.batch_occupancy.set(str(os.getpid()),
                                      value=self.core.active)
            # goodput gauges: refresh once dispatches have actually been
            # accounted — throttled mid-burst, and ALWAYS at the end of a
            # burst (has_work just drained) so a short request's MFU is
            # visible on /metrics instead of a frozen pre-burst zero
            disp = self.core.goodput.dispatches
            now = time.monotonic()
            if disp != last_disp and (now - last_gauges >= 0.5
                                      or not self.core.has_work):
                last_gauges, last_disp = now, disp
                self._set_goodput_gauges(stage)
            capture.after_step()
            phase.to("deliver")
            try:
                self._hand_off(outs)
            except Exception:  # closed loop etc. must not kill the thread
                log.exception("failed to deliver step outputs")
            if not outs and not self.core.by_seq:
                # waiting requests that can't be admitted yet: don't busy-spin
                phase.to("idle")
                self._wake.wait(timeout=0.02)
                self._wake.clear()
        phase.close()
        capture.stop()      # cut short by shutdown(), which waits for it

    def _set_goodput_gauges(self, stage) -> None:
        pid = str(os.getpid())
        if self.core.win is not None:
            stage.kv_pages_in_use.set("global", value=float(
                self.core.pool.num_pages - 1 - self.core.pool.free_pages))
            stage.kv_pages_in_use.set(
                "window", value=float(self.core.win.pages_in_use))
        snap = self.core.goodput.snapshot()
        stage.mfu.set(pid, value=snap["mfu"])
        stage.mbu.set(pid, value=snap["mbu"])
        stage.hbm_gbps.set(pid, value=snap["hbm_gbps"])
        for d in self.core.mesh.local_devices:   # a peer's are not ours to ask
            stats = d.memory_stats()      # None on backends without it
            if stats and "peak_bytes_in_use" in stats:
                stage.device_peak_bytes.set(
                    pid, str(d.id), value=stats["peak_bytes_in_use"])

    def _ingest_fail(self, seq_id: str, e: Exception) -> None:
        """Engine-thread cleanup of a failed stream inject: release the
        pages (never sealed, never seen) and deliver ONE typed error the
        consumer turns into a local-prefill fallback."""
        self.core.abort_stream_inject(seq_id)
        self._hand_off([StepOutput(
            seq_id, 0, 0.0, FinishReason.ERROR,
            error=f"KV stream inject failed: {e}",
            error_stage="kv_ingest", error_reason="ingest_failed")])

    def _hand_off(self, outs: List[StepOutput]) -> None:
        """Everything an iteration produced crosses to the event loop in
        ONE ``call_soon_threadsafe``, and a queue item is a BURST: the
        consecutive outputs one dispatch gave one sequence (up to
        ``decode_steps`` of a decode record, K + 1 of a speculative round).
        A first token, an ERROR and whatever ends a sequence close their
        burst. The loop thread shares this process's GIL, so what it works
        off per item the engine thread waits for: per lane, not per token."""
        loop = self._loop
        if loop is None:
            return
        puts: List[Tuple[asyncio.Queue, List[StepOutput]]] = []
        open_bursts: Dict[str, List[StepOutput]] = {}
        tokens = 0
        for so in outs:
            q = self._queues.get(so.seq_id)
            if q is None:
                continue
            error = so.finish == FinishReason.ERROR
            burst = None if error else open_bursts.get(so.seq_id)
            if burst is None:
                burst = open_bursts[so.seq_id] = []
                puts.append((q, burst))
            burst.append(so)
            tokens += not error
            if so.finish is not None or so.first_token_at is not None:
                del open_bursts[so.seq_id]
        if not puts:
            return
        stage = self.core.stage
        stage.engine_handoffs.inc()
        stage.engine_handoff_tokens.inc(amount=tokens)
        loop.call_soon_threadsafe(_put_bursts, puts)

    # ------------------------------------------------------------------
    async def generate(self, request: BackendInput,
                       context: Context) -> AsyncIterator[EngineOutput]:
        async for out in self._generate(("submit", request), context):
            yield out

    async def prefill_extract(self, request: BackendInput, context: Context
                              ) -> Tuple[np.ndarray, np.ndarray, int, float]:
        """Prefill-worker entry: compute prompt KV + first token on the
        engine thread, await the result. Returns (k, v, token, logprob)."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("prefill_extract", context.id,
                         (request, loop, fut)))
        self._wake.set()
        return await fut

    async def swap_weights(self, host_params, new_cfg):
        """Model-mobility hot-swap: post the in-place weight overwrite to
        the engine thread and await its :class:`~dynamo_tpu.fleet.
        mobility.swap.SwapOutcome`. The caller must have drained first
        (``has_work`` False); a typed ``SwapError`` propagates here when
        the sibling's shape signature does not match."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._inbox.put(("swap", "", (host_params, new_cfg, loop, fut)))
        self._wake.set()
        return await fut

    async def generate_prefilled(self, request: BackendInput, context: Context,
                                 k, v, first_token: int,
                                 first_logprob: float = 0.0
                                 ) -> AsyncIterator[EngineOutput]:
        """Stream a request whose prompt KV (and first token) arrived from a
        remote prefill worker — enters decode directly."""
        payload = (request, k, v, first_token, first_logprob)
        async for out in self._generate(("inject", payload), context):
            yield out

    # ------------------------------------------------------------------
    # layer-streamed KV ingest (disagg receive path)
    # ------------------------------------------------------------------
    def kv_ingest(self, request: BackendInput, seq_id: str) -> "KvIngest":
        """An asyncio-side handle the :class:`~..llm.kv_transfer.
        KvReceiver` drives to scatter a remote prefill's KV layer-by-
        layer as it arrives. Register it with ``receiver.expect(...,
        ingest=handle)``; consume the entered sequence with
        :meth:`generate_streamed` once the awaited future resolves to
        the handle."""
        return KvIngest(self, request, seq_id)

    async def generate_streamed(self, request: BackendInput,
                                context: Context, ingest: "KvIngest"
                                ) -> AsyncIterator[EngineOutput]:
        """Stream a request whose KV was ingested layer-streamed — the
        inject commands are already queued; this only consumes the output
        queue the ingest registered. Raises
        :class:`~..llm.kv_transfer.RemotePrefillError` (before yielding
        anything) if the engine-side ingest failed, so the caller can
        fall back to local prefill."""
        async for out in self._consume(context.id, context,
                                       ingest_fallback=True):
            yield out

    async def _generate(self, work, context: Context
                        ) -> AsyncIterator[EngineOutput]:
        kind, payload = work
        self._loop = asyncio.get_running_loop()
        seq_id = context.id
        self._queues[seq_id] = asyncio.Queue()
        submitted = time.monotonic()
        received = context.stamps.get("received")
        if received is not None:
            self.core.stage.request_stage.observe(
                "pre_engine", value=submitted - received)
        if kind == "submit":
            # the engine thread has no context: it hangs the request's
            # engine spans under the span that is current here
            payload = (payload, submitted, _tracing.current_span_var.get())
        self._inbox.put((kind, seq_id, payload))
        self._wake.set()
        async for out in self._consume(seq_id, context):
            yield out

    async def _consume(self, seq_id: str, context: Context,
                       ingest_fallback: bool = False
                       ) -> AsyncIterator[EngineOutput]:
        q = self._queues[seq_id]

        async def watch_cancel():
            await context.stopped()
            self._inbox.put(("cancel", seq_id, None))
            self._wake.set()

        cancel_task = asyncio.ensure_future(watch_cancel())
        try:
            while True:
                # one burst: what ONE dispatch gave this sequence (see
                # _hand_off); it travels the frontend as one output
                burst: List[StepOutput] = await q.get()
                so, last = burst[0], burst[-1]
                if so.finish == FinishReason.ERROR:
                    if ingest_fallback and so.error_stage == "kv_ingest":
                        # torn/failed stream inject: the pages are
                        # released; hand control back so the caller
                        # prefills locally instead of erroring the user
                        from ..llm.kv_transfer import RemotePrefillError
                        raise RemotePrefillError(so.error or "kv ingest "
                                                             "failed")
                    yield EngineOutput(token_ids=[],
                                       finish_reason=FinishReason.ERROR,
                                       error=so.error or "engine error",
                                       error_code=so.error_code,
                                       error_stage=so.error_stage,
                                       error_reason=so.error_reason)
                    return
                ingest_fallback = False   # tokens flowed: no fallback
                if so.first_token_at is not None:
                    context.stamps["first_token"] = so.first_token_at
                yield EngineOutput(
                    token_ids=[o.token for o in burst],
                    cum_log_prob=last.logprob,
                    logprobs=[{str(o.token): o.token_logprob}
                              for o in burst],
                    finish_reason=last.finish,
                    # first output only: admission's sealed-prefix restore
                    # length (a resumed stream's re-attach proof)
                    kv_prefix_hit_tokens=so.prefix_hit,
                )
                if last.finish is not None:
                    return
        finally:
            cancel_task.cancel()
            self._queues.pop(seq_id, None)
            self._inbox.put(("cancel", seq_id, None))
            self._wake.set()

    # ------------------------------------------------------------------
    # placement-driven prefetch (asyncio thread)
    # ------------------------------------------------------------------
    def prefetch_tiers(self, request: BackendInput) -> int:
        """Start h2d upload of the request's matched host/disk-tier
        prefix (and touch draft-model state when spec is on) while it
        waits in the slot-gate queue — admission consumes the staged
        device blocks d2d instead of stalling first prefill on the
        upload. Best-effort: any failure just means the legacy
        synchronous restore path."""
        if getattr(request, "images", None) \
                and not getattr(request, "kv_salt", 0):
            # admission will salt this VLM request's chain with the image
            # digest it computes itself; prefetching under the unsalted
            # chain would stage blocks admission never matches (and evict
            # other requests' genuinely matching staged blocks)
            return 0
        try:
            n = self.core.stage_prefetch(
                request.token_ids,
                lora_id=getattr(request, "kv_salt", 0)
                or getattr(request, "lora_id", 0))
        except Exception:  # noqa: BLE001 - prefetch must never fail a req
            log.exception("h2d prefetch failed; admission restores "
                          "synchronously")
            return 0
        prop = self.core.proposer
        if prop is not None and hasattr(prop, "prefetch"):
            # draft-model weight prefetch hook (spec decode): today's
            # proposers load at init, so this is the seam for lazily-
            # loaded drafts, not a transfer
            try:
                prop.prefetch()
            except Exception:  # noqa: BLE001
                log.debug("draft prefetch hook failed", exc_info=True)
        return n

    # ------------------------------------------------------------------
    def shutdown(self) -> None:
        self._running = False
        self._wake.set()
        self._thread.join(timeout=5)
        self._capture.close()
        # disk-tier spill files are scratch state: flush + unlink them
        # with the engine (next to the metrics-key cleanup) instead of
        # leaking two pool-sized memmaps per engine lifetime
        self.core.close()
        # the engine's per-worker gauge series must die with it: a process
        # that outlives its engine (model remove/re-add, shared-runtime
        # tests) would otherwise export ghost occupancy/MFU forever
        from ..utils.prometheus import stage_metrics

        stage_metrics().clear_worker(str(os.getpid()))

class KvIngest:
    """Asyncio-side handle for one layer-streamed KV injection.

    Created by :meth:`JaxEngine.kv_ingest` before the request parks on
    the prefill queue; the :class:`~..llm.kv_transfer.KvReceiver` drives
    it from the ``kv_receive`` handler: :meth:`begin` validates the wire
    geometry against the engine and registers the output queue,
    :meth:`layer` posts one arrived layer's device scatter to the engine
    thread (enqueued while later layers are still on the wire),
    :meth:`finish` posts the finalize (seal + enter decode + first
    token), :meth:`abort` tears everything down with the pool pages
    released unseen. All methods are cheap posts — no device syncs."""

    def __init__(self, engine: JaxEngine, request: BackendInput,
                 seq_id: str):
        self.engine = engine
        self.request = request
        self.seq_id = seq_id
        self.began = False
        self.finished = False

    def _post(self, kind: str, payload) -> None:
        self.engine._inbox.put((kind, self.seq_id, payload))
        self.engine._wake.set()

    def begin(self, meta: dict) -> bool:
        """Validate the stream's geometry and arm the ingest. False =
        decline (mismatched model geometry / tokens): the receiver falls
        back to buffered assembly, which surfaces the mismatch through
        the legacy import path."""
        m = self.engine.core.cfg.model
        if (int(meta.get("layers", -1)) != m.num_layers
                or int(meta.get("kv_heads", -1)) != m.num_kv_heads
                or int(meta.get("head_dim", -1)) != m.head_dim
                or int(meta.get("tokens", -1))
                != len(self.request.token_ids)):
            log.warning("kv stream geometry %s does not match engine "
                        "(%d layers, %d kv heads, %d head_dim); buffering",
                        {k: meta.get(k) for k in
                         ("layers", "kv_heads", "head_dim", "tokens")},
                        m.num_layers, m.num_kv_heads, m.head_dim)
            return False
        self.engine._loop = asyncio.get_running_loop()
        self.engine._queues[self.seq_id] = asyncio.Queue()
        self._post("ingest_begin", self.request)
        self.began = True
        return True

    def layer(self, layer: int, k: np.ndarray, v: np.ndarray) -> None:
        self._post("ingest_layer", (layer, k, v))

    def finish(self, first_token: int, first_logprob: float) -> None:
        self.finished = True
        self._post("ingest_finish", (int(first_token),
                                     float(first_logprob)))

    def abort(self) -> None:
        """Idempotent, and a no-op once :meth:`finish` posted: the waiter
        consumes the finished sequence's queue, so a late abandon (the
        ``await_remote_kv`` finally) must not tear it down. For an
        UNfinished ingest the abort posts through the same FIFO inbox the
        begin rode, so a local-prefill resubmit of the same seq_id is
        processed strictly after the pool pages were released."""
        if self.began and not self.finished:
            self._post("ingest_abort", None)
            self.engine._queues.pop(self.seq_id, None)
            self.began = False

    def discard(self) -> None:
        """The waiter gave up AFTER the ingest finished (its sequence is
        already decoding) and will never consume the outputs: cancel the
        orphaned sequence and drop its queue so the slot and the dict
        entry don't leak until max_tokens."""
        if self.finished:
            self._post("cancel", None)
            self.engine._queues.pop(self.seq_id, None)
            self.finished = False
            self.began = False
        else:
            self.abort()
