#!/usr/bin/env python3
"""Builder's tool, on the chip: ``program_memory_kinds.py`` for a
PARALLEL-BLOCK configuration of two cache kinds (the four pools, the copies
of each a program makes, ``bytes_in_use`` with the engine built), the
engine's own programs TIMED on dummy operands (the decode dispatch at the
last context bucket with ``--busy`` of its lanes served; a chunk of each size
in ``--chunks`` at each context in ``--contexts``, its window read full: how
``prefill_chunk`` was chosen), and the routed experts ALONE, dense against
sorted dispatch of the held experts, at decode rows (``--busy`` of
``max_batch`` rows served) and at chunk rows (``--dispatch-rows``): how
``moe.sorted_wins`` was read for this geometry.

    python benchmarks/tests/program_memory_parblock.py <config> \\
        [--chunks 256,512] [--contexts 4096,25088] [--busy 3,8,12] \\
        [--dispatch-rows 32,64,128,256,512] [--reps 5] [--set max_batch=16]

This process imports jax and holds the chip: run it alone. The numbers go
into the configuration file's ``memory`` and ``engine_why`` groups and into
``moe.sorted_wins``' text by hand. Not part of any check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from program_memory import report  # noqa: E402
from program_memory_scmoe import ints, timed  # noqa: E402


def experts_alone(core, rows_list, busy_list, reps):
    """Router and routed experts of the engine's own weights (the shared
    experts left out: ``moe.moe_ffn`` as ``llama._routed_ffn`` calls it),
    one call a layer, dense against sorted: chunk rows (every row real) and
    the decode program's rows (``busy`` of ``max_batch`` served)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama, moe

    m = core.cfg.model
    st = core.params[llama.STACKS]["routed"]
    n = st["wr"].shape[0]
    rule = moe.sorted_wins

    def layers_under(form):
        # a NEW function object a form: jax.jit keeps a trace by function
        # identity, and a patched rule would not be asked again
        def layers(x, st, active):
            moe.sorted_wins = lambda *a: form == "sorted"
            try:
                hit = held = 0
                for l in range(n):
                    y, (h, hd), _ = moe.moe_ffn(
                        x, st["wr"][l], st["wg"], st["wu"], st["wd"],
                        m.experts_per_token, layer=l, router=m.router,
                        first=m.expert_first, active=active)
                    x, hit, held = x + y, hit + h, held + hd
            finally:
                moe.sorted_wins = rule
            return x, hit, held
        return layers

    B = core.cfg.max_batch
    cases = [("chunk", r, None) for r in rows_list] + [
        ("decode", B, b) for b in busy_list]
    for what, rows, busy in cases:
        shape = (1, rows) if what == "chunk" else (rows, 1)
        x = jax.random.normal(jax.random.PRNGKey(rows), (*shape, m.hidden_size),
                              jnp.float32).astype(m.dtype)
        active = None if busy is None else jnp.asarray(np.arange(B) < busy)
        rec = {"experts": what, "rows": rows, "busy": busy,
               "rule_says": ("sorted" if rule(rows, m.experts_per_token,
                                              m.num_experts, core._moe_share)
                             else "dense")}
        for form in ("dense", "sorted"):
            fn = jax.jit(layers_under(form)).lower(x, st, active).compile()
            _, least = timed(lambda: fn(x, st, active), reps)
            y, hit, held = fn(x, st, active)
            rec[form] = {"ms_per_layer": round(1e3 * least / n, 4),
                         "temporaries": fn.memory_analysis().temp_size_in_bytes,
                         "held_assignments_per_layer": float(held) / n,
                         "held_experts_hit_per_layer": float(hit) / n}
            rec[form + "_y"] = y
        rec["max_abs_diff"] = float(jnp.max(jnp.abs(
            rec.pop("dense_y").astype(jnp.float32)
            - rec.pop("sorted_y").astype(jnp.float32))))
        print(json.dumps(rec), flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("config")
    p.add_argument("--chunks", default="256,512")
    p.add_argument("--contexts", default="4096,25088")
    p.add_argument("--busy", default="3,8,12")
    p.add_argument("--dispatch-rows", default="32,64,128,256,512")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--set", action="append", default=[],
                   help="engine key=value over the configuration's")
    a = p.parse_args()
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.cache import WindowPages
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    config = Catalog().data("configs", a.config)
    model = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    chunks = ints(a.chunks)
    eng = {**config["benchmark"]["engine"], "prefill_chunk": chunks[-1],
           **{k: int(v) for k, v in (s.split("=") for s in a.set)}}
    if any(s.startswith("max_batch=") for s in a.set):
        eng.pop("num_pages", None)
    t0 = time.monotonic()
    core = EngineCore(JaxEngineConfig(model=model, seed=1, warmup=False,
                                      **eng))
    built = time.monotonic() - t0
    B, s, S = core.cfg.max_batch, core.sampling, core.s_buckets[-1]
    page = core.page_size
    rng = np.random.default_rng(0)
    pools = {"k": core.k_pool, "v": core.v_pool, "window_k": core.wk_pool,
             "window_v": core.wv_pool}
    stats = jax.devices()[0].memory_stats() or {}
    out = {"config": a.config, "engine": eng, "engine_built_s": round(built, 1),
           "context_buckets": core.s_buckets, "chunk_buckets": core.c_buckets,
           "moe_dispatch": core.moe_dispatch,
           "attn_proj": core.attn_proj,
           "weights_bytes": int(sum(
               x.nbytes for x in jax.tree.leaves(core.params))),
           "pool_shapes": {nm: list(p.shape) for nm, p in pools.items()},
           "global_pools_bytes": int(core.k_pool.nbytes + core.v_pool.nbytes),
           "window_pools_bytes": int(core.wk_pool.nbytes
                                     + core.wv_pool.nbytes),
           "bytes_in_use_engine_built": stats.get("bytes_in_use"),
           "peak_bytes_engine_built": stats.get("peak_bytes_in_use"),
           "bytes_limit": stats.get("bytes_limit")}

    def every(compiled):
        got = {}
        for nm, pool in pools.items():
            r = report(compiled, tuple(pool.shape))
            got.update({k: r[k] for k in ("arguments", "temporaries", "code",
                                          "tpu_custom_calls")})
            got[nm + "_pool_sized_copies"] = r["pool_sized_copies"]
            got[nm + "_layer_pool_copies"] = r["layer_pool_copies"]
        return got

    # ---- the decode dispatch: ``busy`` lanes at a context of S - 8 -------
    P = S // page
    pt = (1 + np.arange(B * P, dtype=np.int32).reshape(B, -1)
          % (core.k_pool.shape[2] - 1))
    # a lane's window table: its last pages alone (the rest scratch page 0)
    held = -(-model.sliding_window // page) + 1
    wt = np.zeros((B, P), np.int32)
    wt[:, P - held:] = 1 + (np.arange(B * held, dtype=np.int32).reshape(B, -1)
                            % (core.wk_pool.shape[2] - 1))
    toks = rng.integers(0, model.vocab_size, B).astype(np.int32)
    flags = np.zeros(B, bool)
    fn = core._decode_fn(S)
    cols = core._decode_cols
    decode = {"S": S, "cols": list(cols)}
    for i, busy in enumerate(ints(a.busy)):
        act = np.arange(B) < busy
        lens = np.where(act, S - 8, 1).astype(np.int32)
        args = lambda: (core.params, toks, core.k_pool, core.v_pool, pt, lens,
                        s.temperature, s.top_p, s.top_k, s.key,
                        core.gen_counts, flags, act, s.freq_pen, s.pres_pen)
        kw = lambda: {**core._idx(), "w_tables": wt}
        if i == 0:
            decode.update(every(fn.jitted.lower(*args(), **kw()).compile()))

        def call():
            packed, _, _, kp, vp, core.gen_counts, *wp = fn(*args(), **kw())
            core._take_pools((kp, vp, *wp))
            return packed
        first, least = timed(call, a.reps)
        packed = np.asarray(call())
        decode[f"busy_{busy}"] = {
            "dispatch_ms": round(1e3 * least, 3),
            "step_ms": round(1e3 * least / core.cfg.decode_steps, 3),
            **{c: float(packed[:, 0, 2 + cols.index(c)].mean()) for c in cols},
            "first_call_s": round(first, 1)}
    out["decode_program"] = decode
    print(json.dumps(out), flush=True)

    # ---- chunks: the last chunk of a prompt that fills the bucket --------
    rows = []
    for S_c in ints(a.contexts):
        S_c = core._bucket(S_c, core.s_buckets)
        if any(r["S"] == S_c for r in rows):
            continue
        for C in chunks:
            if C + 64 > S_c:
                continue
            fn = core._prefill_fn(1, C, S_c)
            start = S_c - C - 64
            pos = (start + np.arange(C, dtype=np.int32))[None]
            slots = (page + np.arange(S_c, dtype=np.int32))[None]
            ids = rng.integers(0, model.vocab_size, (1, C)).astype(np.int32)
            keys = s.key[jnp.asarray(np.zeros(1, np.int32))]
            win = core._win_dummies(1, C)
            Pw = win["w_pages"].shape[1]
            lo = max(0, start - (model.sliding_window - 1)) // page
            win["w_pages"][0] = 1 + np.arange(Pw)
            win["w_pos"][0] = lo * page + np.arange(Pw * page)
            win["w_valid"][0] = win["w_pos"][0] < start + C
            win["w_write"][0] = (page * (1 + start // page - lo)
                                 + start % page + np.arange(C))
            cargs = lambda: (core.params, ids, pos, core.k_pool, core.v_pool,
                             slots[:, start:start + C], slots,
                             np.arange(S_c, dtype=np.int32)[None],
                             (np.arange(S_c) < start + C)[None],
                             np.full(1, C - 1, np.int32),
                             np.zeros(1, np.float32), np.ones(1, np.float32),
                             np.zeros(1, np.int32), keys)
            ckw = lambda: {**core._idx(), **win}
            row = {"C": C, "S": S_c, "form": core._chunk_form(C),
                   "window_read_pages": Pw,
                   "window_lane_pages": WindowPages.lane_pages(
                       model.sliding_window, C, page)}
            if C == chunks[-1] and S_c == core.s_buckets[-1]:
                row.update(every(fn.jitted.lower(*cargs(), **ckw()).compile()))

            def call():
                packed, _, _, *pl = fn(*cargs(), **ckw())
                core._take_pools(pl)
                return packed
            first, least = timed(call, a.reps)
            packed = np.asarray(call())
            pcols = core._packed_cols
            row.update(chunk_ms=round(1e3 * least, 3),
                       us_a_token=round(1e6 * least / C, 2),
                       **{c: float(packed[0, 2 + pcols.index(c)])
                          for c in pcols},
                       first_call_s=round(first, 1))
            rows.append(row)
            print(json.dumps(row), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"bytes_in_use": stats.get("bytes_in_use"),
                      "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "bytes_limit": stats.get("bytes_limit")}), flush=True)
    experts_alone(core, ints(a.dispatch_rows), ints(a.busy), a.reps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
