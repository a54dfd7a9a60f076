#!/usr/bin/env python3
"""Builder's tool, on the chip: where a DECODE step's routed layer crosses
from sorted to dense dispatch, at the decode program's own shape.

    python scripts/moe_by_hit.py <config> [--rows 32] [--busy 1,2,4,...]

The decode program has ``rows`` rows whatever lanes are busy; ``moe_ffn``
takes the dispatch's ``active`` mask, makes an idle row's assignments absent
ones, and where ``moe.sorted_wins`` says dense it holds both forms and
chooses on the device by the experts the busy rows hit
(``moe.SORTED_UNDER_HIT_SHARE``). For each count of busy rows this times the
configuration's routed layers (stacked weights, the ``layer=`` form inside
ONE ``lax.scan``, as ``llama._state_run`` calls them) with the form forced
``dense``, forced ``sorted`` and as the program has it (``by_hit``), and
prints milliseconds a layer, the experts hit a layer, the share of the calls
that went sorted, and the largest difference of the busy rows' results
between sorted and dense. The readings in ``moe.sorted_wins``' docstring are
this tool's. This process holds the chip: run it alone. Not part of any
check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--busy", default="1,2,4,6,8,12,16,20,24,28,32")
    ap.add_argument("--layers", type=int, default=0,
                    help="routed layers to stack (default: the model's)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true",
                    help="experts of 64 x 32: a rehearsal on the CPU")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.models import llama, moe
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    config = Catalog().data("configs", args.config)
    cfg = llama.LlamaConfig.from_hf_config(
        {k: v for k, v in config.items() if k != "benchmark"})
    L = args.layers or cfg.routed_layers
    E, D, F, K = (cfg.num_experts, cfg.hidden_size, cfg.expert_width,
                  cfg.experts_per_token)
    if args.tiny:
        D, F = 64, 32
    B = args.rows
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    mk = jax.jit(lambda k, shape: (jax.random.normal(k, shape, jnp.float32)
                                   * 0.05).astype(cfg.dtype),
                 static_argnums=1)
    wr = mk(ks[0], (L, D, E)) * 20
    wg, wu, wd = (mk(ks[1], (L, E, D, F)), mk(ks[2], (L, E, D, F)),
                  mk(ks[3], (L, E, F, D)))
    x = mk(ks[4], (B, 1, D)) * 20
    was = moe.dispatch_form

    def layers_under(forced):
        # a function of its own for each form: jit keeps what it traced by
        # the function, and the form is read while tracing
        def layers(x, active, wr, wg, wu, wd):
            moe.dispatch_form = (was if forced is None
                                 else lambda *a, **k: forced)

            def body(carry, l):
                x, hits, took = carry
                stats = {}
                y, hit, _ = moe.moe_ffn(x, wr[l], wg, wu, wd, K, layer=l,
                                        active=active, stats=stats)
                return (x + y * 0.01, hits + hit, took + stats["sorted"]), y

            try:
                (x, hits, took), ys = jax.lax.scan(
                    body, (x, jnp.int32(0), jnp.int32(0)), jnp.arange(L))
            finally:
                moe.dispatch_form = was
            return ys, hits, took
        return layers

    fns = {}
    for form in ("dense", "sorted", None):
        name = form or "by_hit"
        fns[name] = jax.jit(layers_under(form)).lower(
            x, jnp.ones(B, bool), wr, wg, wu, wd).compile()
    print(json.dumps({
        "config": args.config, "rows": B, "layers": L, "experts": E,
        "width": [D, F], "top_k": K, "sorted_under": moe.sorted_under(E),
        "form": was(B, K, E, masked=True),
        "temporaries": {n: f.memory_analysis().temp_size_in_bytes
                        for n, f in fns.items()}}), flush=True)
    out = []
    for b in (int(v) for v in args.busy.split(",")):
        active = jnp.arange(B) < b
        rec = {"busy": b}
        ys = {}
        for name, fn in fns.items():
            y, hits, took = jax.block_until_ready(
                fn(x, active, wr, wg, wu, wd))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, hits, took = fn(x, active, wr, wg, wu, wd)
            jax.block_until_ready(y)
            rec[name + "_ms_per_layer"] = round(
                1e3 * (time.perf_counter() - t0) / args.reps / L, 4)
            rec["experts_hit_per_layer"] = float(hits) / L
            if name == "by_hit":
                rec["sorted_call_share"] = float(took) / L
            ys[name] = np.asarray(y.astype(jnp.float32))[:, :b]
        rec["max_abs_diff_busy_rows"] = float(
            np.abs(ys["dense"] - ys["sorted"]).max())
        out.append(rec)
        print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "moe_by_hit.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
