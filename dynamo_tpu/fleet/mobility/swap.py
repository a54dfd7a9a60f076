"""In-place engine hot-swap: overwrite donated weight buffers from host.

The compiled bucket programs (prefill/decode/verify) take ``params`` as
an *argument* but close over the engine config, so a sibling checkpoint
whose layer-class shape signature matches can be swapped in by
overwriting the weight buffers — the SAME XLA programs keep serving and
``dyn_compiled_programs`` stays flat. The signature therefore covers
every compute-affecting field: the full model config plus the engine
geometry the programs were bucketed against.

Swap sequencing (engine thread, post-drain):

1. gate — tree structure + shape signature must match, else a typed
   :class:`SwapError` (the agent falls back to a counted full reload);
2. demote — sealed device KV blocks flush to the host tier (the cluster
   plane keeps serving them through the drain window);
3. overwrite — per layer-group h2d uploads feed donated in-place slab
   scatters (``CopyStream.h2d_param_slab``), enqueued async so the
   device streams weights while the host...
4. ...tears down KV state: tiered cache + hash registries clear (block
   hashes carry no model identity — old-model KV must not alias), then
   one barrier on the new params.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import time
from typing import Any, Dict, Optional

import numpy as np

from ...utils.knobs import env_float

log = logging.getLogger("dynamo_tpu.mobility")

#: layers per h2d group (DYN_SWAP_GROUP_LAYERS overrides)
DEFAULT_GROUP_LAYERS = 4


class SwapError(RuntimeError):
    """Typed swap refusal; ``reason`` is one of ``shape_mismatch`` |
    ``not_drained`` | ``weights_unavailable`` | ``unsupported``."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason


@dataclasses.dataclass
class SwapOutcome:
    path: str                 # "swap" (in-place) | "cold" (full reload)
    seconds: float
    model_path: Optional[str]
    groups: int = 0           # layer-group h2d scatters issued
    demoted_blocks: int = 0   # sealed KV blocks demoted to the host tier

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def swap_signature(cfg) -> str:
    """The layer-class shape signature of a :class:`JaxEngineConfig`:
    equal signatures guarantee every compiled bucket program of one
    engine is valid for the other's params. Covers the full model config
    (all fields affect either shapes or the traced compute) and the
    engine geometry the bucket grids were derived from — NOT
    ``params_path``/``preset``/``seed``, which only name the weights."""
    m = dataclasses.asdict(cfg.model)
    geom = {
        "tp": cfg.tp, "sp": cfg.sp, "ep": cfg.ep, "pp": cfg.pp,
        "page_size": cfg.page_size, "max_batch": cfg.max_batch,
        "max_context": cfg.max_context,
        "prefill_chunk": cfg.prefill_chunk,
        "num_pages": cfg.num_pages, "decode_steps": cfg.decode_steps,
        "prefill_lanes": cfg.prefill_lanes, "attn_impl": cfg.attn_impl,
        "spec": cfg.spec or None, "spec_k": cfg.spec_k,
        "spec_draft": cfg.spec_draft,
    }
    return json.dumps({"model": m, "geom": geom}, sort_keys=True,
                      default=str)


def _flat(tree) -> Dict[tuple, Any]:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {tuple(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in leaves}


def hot_swap(core, host_params, new_cfg, group_layers: Optional[int] = None
             ) -> SwapOutcome:
    """Overwrite ``core``'s weight buffers with ``host_params`` (the new
    model's host tree) in place. Engine-thread only, post-drain. Raises
    :class:`SwapError` (typed, never hangs) when the swap cannot reuse
    the compiled programs; the caller then takes the full-reload path."""
    t0 = time.monotonic()
    if core.by_seq or core.waiting or core._inflight \
            or core._stream_injects:
        raise SwapError("not_drained",
                        f"{len(core.by_seq)} active, "
                        f"{len(core.waiting)} waiting")
    if swap_signature(core.cfg) != swap_signature(new_cfg):
        raise SwapError("shape_mismatch",
                        "engine geometry/model config differs")
    if core.cfg.model.vision is not None:
        # the vision tower's params are separate jits keyed off tower
        # weights; swapping only the LM half would serve mismatched
        # encoders — take the full reload path
        raise SwapError("unsupported", "VLM engines reload cold")
    if core.attn_proj == "out_in":
        # the host tree is the published one (the loader's); this engine's
        # has its q / k / v projections a matrix a layer (views here)
        from ...models import llama

        host_params = llama.stored_params(host_params,
                                           cfg=core.cfg.model)
    new_flat = _flat(host_params)
    old_flat = _flat(core.params)
    if set(new_flat) != set(old_flat):
        missing = set(old_flat) ^ set(new_flat)
        raise SwapError("shape_mismatch",
                        f"param tree differs at {sorted(missing)[:4]}")
    for path, leaf in old_flat.items():
        if tuple(new_flat[path].shape) != tuple(leaf.shape):
            raise SwapError(
                "shape_mismatch",
                f"{'/'.join(path)}: {new_flat[path].shape} vs "
                f"{leaf.shape}")

    # ---- demote sealed KV to the host tier (drain-window serving) ----
    demoted = core.pool.flush_reusable()
    core._flush_evictions()

    # ---- enqueue the weight overwrite (async device work) ------------
    if group_layers is None:
        group_layers = max(1, int(env_float(
            "DYN_SWAP_GROUP_LAYERS", DEFAULT_GROUP_LAYERS, minimum=1.0)))
    from ...engine.engine import global_put

    L = core.cfg.model.num_layers
    groups = 0
    slab_bytes = 0
    params = core.params
    layered = (core.cfg.pp == 1)

    def rewrite(old_leaf, path):
        nonlocal groups, slab_bytes
        src = np.asarray(new_flat[path])
        if src.dtype != old_leaf.dtype:
            src = src.astype(old_leaf.dtype)
        slab_bytes += src.nbytes
        if layered and len(path) == 2 and path[0] == "layers" \
                and old_leaf.shape[0] == L and L > group_layers:
            buf = old_leaf
            for g0 in range(0, L, group_layers):
                chunk = global_put(src[g0:g0 + group_layers],
                                   buf.sharding)
                buf = core.copy_stream.h2d_param_slab(buf, g0, chunk)
                groups += 1
            return buf
        # non-stacked leaves (embed/final_norm/lm_head), small stacks,
        # and pp>1 (layer axis sharded across stages — a host-side slab
        # would not line up with one stage's shard): whole-leaf put
        return global_put(src, old_leaf.sharding)

    import jax

    t_enq = time.monotonic()
    flat_old, treedef = jax.tree_util.tree_flatten_with_path(params)
    new_leaves = [
        rewrite(leaf,
                tuple(str(getattr(k, "key", k)) for k in path))
        for path, leaf in flat_old]
    new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)

    # ---- KV teardown on the host while the device streams weights ----
    if core.tiered is not None:
        core.tiered.clear()
    core.pool.flush_reusable()           # anything demotion re-parked
    core._evict_buf.clear()              # post-clear offloads are moot
    core._writethrough_buf.clear()
    core._writethrough_armed.clear()
    core._writethrough_pending.clear()
    with core._h2d_stage_lock:
        core._h2d_stage.clear()
        core._h2d_requested.clear()
    core._pending_prefix_hit.clear()
    core._spec_states.clear()
    core.prefix_hit_tokens = 0
    core.prefix_query_tokens = 0
    core.last_prefix_hit = 0
    core._last_final_tok = None          # chained off old-model logits

    # ---- barrier: weights resident before the first new-model token --
    # dynalint: ok(host-sync) swap cutover barrier — blocks once per
    # model swap (the wake path's h2d stream), never on a request
    jax.block_until_ready(jax.tree.leaves(new_params))
    # one flow for the whole weight stream: the barrier bounds it, so
    # bytes/seconds is the swap's real h2d rate
    from ...obs.flows import record_flow
    record_flow("swap_slab", slab_bytes, time.monotonic() - t_enq)
    core.params = new_params
    core.cfg = dataclasses.replace(
        core.cfg, params_path=getattr(new_cfg, "params_path", None),
        preset=getattr(new_cfg, "preset", None))
    seconds = time.monotonic() - t0
    from ...utils.prometheus import stage_metrics

    stage_metrics().model_swaps.inc("swap")
    log.info("hot-swap to %s: %.2fs, %d layer-group scatters, %d KV "
             "blocks demoted (0 new compiled programs)",
             core.cfg.params_path, seconds, groups, demoted)
    return SwapOutcome("swap", seconds, core.cfg.params_path,
                       groups=groups, demoted_blocks=demoted)
