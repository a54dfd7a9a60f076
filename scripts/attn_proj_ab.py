#!/usr/bin/env python3
"""Builder's tool, on the chip: the prefill chunk program and the decode
dispatch of a benchmark configuration ALONE, handed the q / k / v projection
weights in each form: ``out_in`` (what the engine stores, ``llama.
stored_params``: a matrix [H x Dh, D] a layer), ``published`` ([L, D, H, Dh],
what ``llama.init_params`` returns and the engine stored until PR 50) and
``stack`` ([L, H x Dh, D]: the same matrices, stacked). ms a chunk and ms a
dispatch of each form on the same operands, the device operations of one call
whose RESULT has a weight's dimensions (what a program pays to re-lay a
weight, or to cut a layer out of a stack, before it multiplies by it), and
whether the forms sampled the same tokens.

    python scripts/attn_proj_ab.py <config> ... [--context 2048] [--reps 20]
        [--forms out_in,published,stack] [--out chiprun_out/pr50/ab.json]
        [--tiny]

Both programs are the engine's own (``EngineCore._prefill_fn(1, C, S)``,
``EngineCore._decode_fn(S)``); a form is another tree of arguments, so each
is traced and compiled once. The chunk is ``prefill_chunk`` tokens at the end
of the context bucket; the dispatch serves every lane at a length near the
bucket's end. ``--tiny`` cuts layers, pages and context for a rehearsal on the
CPU. This process imports jax and holds the chip: run it alone. Not part of
any check. PERF.md section 6, PR 50, has the readings.
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def weight_ops(call, dims) -> dict:
    """One traced call: {operation: [events, us in all]} of the device's XLA
    operations whose result has one of ``dims`` (weight-shaped), and the
    call's ten longest under ``top``."""
    import jax
    from jax.profiler import ProfileData

    from benchmarks.harness.xplane import leaves, op_key

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(call())
        pb, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        took = {}
        for plane in ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                    for s, e, n in leaves(evs):
                        took.setdefault(op_key(n), []).append((e - s) * 1e-3)
    row = lambda v: [len(v), round(sum(v), 1)]
    top = sorted(took.items(), key=lambda kv: -sum(kv[1]))[:10]
    return {"weight_shaped": {
                k: row(v) for k, v in sorted(took.items())
                if (m := re.search(r"\[([\d,]+)\]$", k)) and m.group(1) in dims},
            "top": {k: row(v) for k, v in top}}


def forms_of(params, cfg, which):
    """{form: tree} from the engine's stored tree; the leaves that do not
    change are shared."""
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    def published(name, w):
        width = cfg.v_dim if name == "wv" else cfg.head_dim
        w = jnp.stack(w)                                  # [n, H x width, D]
        return w.swapaxes(1, 2).reshape(w.shape[0], w.shape[2], -1, width)

    made = {"out_in": lambda: params,
            "published": lambda: llama.map_attn_in(published, params),
            "stack": lambda: llama.map_attn_in(
                lambda _, w: jnp.stack(w), params)}
    return {f: made[f]() for f in which}


def one(name: str, args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.models import llama

    config = Catalog().data("configs", name)
    hf = {k: v for k, v in config.items() if k != "benchmark"}
    eng = dict(config["benchmark"]["engine"])
    if args.tiny:
        eng.update(num_pages=64, max_context=512, max_batch=2)
        eng["prefill_chunk"] = min(eng["prefill_chunk"], 256)
        hf["num_hidden_layers"] = 2       # a model of uniform layers
    model = llama.LlamaConfig.from_hf_config(hf)
    core = EngineCore(JaxEngineConfig(model=model, seed=1, warmup=False,
                                      **eng))
    C, page, s = core.cfg.prefill_chunk, core.page_size, core.sampling
    B = core.cfg.max_batch
    S = core._bucket(min(args.context, core.s_buckets[-1]), core.s_buckets)
    start = (S - C) // page * page - page
    rng = np.random.default_rng(0)
    ids = rng.integers(0, model.vocab_size, (1, C)).astype(np.int32)
    pos = (start + np.arange(C, dtype=np.int32))[None]
    slots = (page + np.arange(S, dtype=np.int32))[None]   # pages 1 ..
    keys = s.key[jnp.asarray(np.zeros(1, np.int32))]
    win = core._win_dummies(1, C)
    if win:
        win["w_write"] = slots[:, :C].copy()
        n = win["w_pages"].shape[1]
        win["w_pages"][0] = 1 + np.arange(n)
        win["w_pos"][0] = start + np.arange(n * page)
        win["w_valid"][0] = np.arange(n * page) < C
    ssm = core._ssm_rows(1)
    if ssm:
        ssm["s_lanes"][0], ssm["s_valid"][0], ssm["s_reset"][0] = 0, C, True
    prefill, decode = core._prefill_fn(1, C, S), core._decode_fn(S)

    def chunk(p):
        packed, _, _, *pools = prefill(
            p, ids, pos, core.k_pool, core.v_pool,
            slots[:, start:start + C], slots,
            np.arange(S, dtype=np.int32)[None],
            (np.arange(S) < start + C)[None], np.full(1, C - 1, np.int32),
            np.zeros(1, np.float32), np.ones(1, np.float32),
            np.zeros(1, np.int32), keys, **core._idx(), **win, **ssm)
        core._take_pools(pools)
        return packed

    # the dispatch: every lane busy, its pages its own, the lengths spread
    # over the bucket's last quarter (the steps of a dispatch stay inside)
    P = S // page
    per = min(P, (core.pool.num_pages - 1) // B)
    tables = np.zeros((B, P), np.int32)
    tables[:, :per] = 1 + np.arange(B * per).reshape(B, per)
    top = per * page - 2 * core.cfg.decode_steps
    lengths = rng.integers(max(top * 3 // 4, 1), top, B).astype(np.int32)
    first = jax.device_put(
        rng.integers(0, model.vocab_size, B).astype(np.int32),
        core._rep_sharding)
    busy, fresh = np.ones(B, bool), np.zeros(B, bool)
    # a window cache has a pool of its own: any of ITS pages will do
    win_tables = {} if core.win is None else {"w_tables": (
        1 + np.arange(B * P).reshape(B, P) % (core.win_pages - 1)
    ).astype(np.int32)}

    def dispatch(p):
        toks, _, _, kp, vp, core.gen_counts, *ip = decode(
            p, first, core.k_pool, core.v_pool, tables, lengths,
            s.temperature, s.top_p, s.top_k, s.key, core.gen_counts, fresh,
            busy, s.freq_pen, s.pres_pen, **core._idx(), **win_tables)
        core._take_pools((kp, vp, *ip))
        return toks

    dims = set()          # a weight's dimensions, in every form, as a trace

    def note(w_name, w):  # names a result: a layer, a stack of one, the stack
        n, (f, d) = len(w), w[0].shape
        h = f // (model.v_dim if w_name == "wv" else model.head_dim)
        for shape in ((f, d), (d, h, f // h)):
            lay = ",".join(map(str, shape))
            dims.update((lay, "1," + lay, f"{n},{lay}"))
        return w
    llama.map_attn_in(note, core.params)
    out = {"config": name, "C": C, "S": S, "B": B,
           "decode_steps": core.cfg.decode_steps,
           "attn_proj": core.attn_proj, "forms": {}}
    for form, tree in forms_of(core.params, model,
                               args.forms.split(",")).items():
        jax.block_until_ready(jax.tree.leaves(tree))
        row = {}
        for what, call in (("chunk", chunk), ("dispatch", dispatch)):
            t0 = time.perf_counter()
            got = np.asarray(call(tree))
            first_s = time.perf_counter() - t0
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(call(tree))
                times.append(time.perf_counter() - t0)
            row[what] = {"ms_min": round(1e3 * min(times), 3),
                         "ms_median": round(1e3 * sorted(times)[
                             len(times) // 2], 3),
                         "first_call_s": round(first_s, 1),
                         "sampled": [float(x) for x in got.reshape(-1)[:8]],
                         **weight_ops(lambda: call(tree), dims)}
        out["forms"][form] = row
        del tree
        gc.collect()
    ref = out["forms"][next(iter(out["forms"]))]
    out["same_tokens"] = all(
        row[w]["sampled"][0] == ref[w]["sampled"][0]
        for row in out["forms"].values() for w in ("chunk", "dispatch"))
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--context", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--forms", default="out_in,published,stack")
    ap.add_argument("--out", default="")
    ap.add_argument("--tiny", action="store_true",
                    help="few pages, short context: a rehearsal on the CPU")
    args = ap.parse_args(argv)

    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    rows = []
    for name in args.configs:
        try:
            rows.append(one(name, args))
        except Exception as e:             # the next configuration still runs
            import traceback
            rows.append({"config": name, "same_tokens": False,
                         "error": traceback.format_exc()[-3000:]})
        gc.collect()                       # the engine's arrays, off the chip
        print(json.dumps(rows[-1]), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
