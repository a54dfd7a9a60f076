#!/usr/bin/env python3
"""Builder's tool, on the chip: the state-space recurrence of ONE decode step
ALONE at the shapes of ``granite-4.0-h-micro.manylanes`` (a pool of 36 layers
x 64 lanes, a lane's state 64 heads x 64 x 128 float32), the state kernel
(``ops/state.py`` ``state_step``) against the ``jax.numpy`` form
(``models/llama.py`` ``ssm_step``'s recurrence: update, read-out,
``where(active, ...)`` over every lane of the pool).

    python scripts/state_kernel_alone.py [--served 64,44,20]
        [--blocks MiB,...] [--out chiprun_out/state_kernel_alone.json]

Each form is one program that passes all 36 layers of the pool (a
``lax.scan`` whose carry is the donated pool, as the decode program's is), so
a reading is microseconds a LAYER-STEP: best of three runs of ten calls, over
36. Beside it the GB/s of what any form must move (a served lane's state once
in and once out: ``benchmarks/harness/state.py`` ``ssm_least``), the largest
difference of the two forms' read-outs and states, and whether the lanes that
are not served kept their state bit for bit. ``--blocks`` times the kernel
with that many MiB of VMEM for its state blocks as well (``ops/state.py``
``_STATE_BLOCKS_BYTES``: 8 is a whole lane a grid step, 4 half the heads, 2 a
quarter). ``REHEARSE=1 JAX_PLATFORMS=cpu`` runs a toy size through the
interpreter (a rehearsal of the script, never a timing). PERF.md section 5,
PR 53.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

SEED = 53
CELL = (36, 64, 64, 64, 128)        # layers, lanes, H, P, N
TOY = (3, 8, 8, 16, 16)


def operands(shape, served):
    import jax
    import jax.numpy as jnp

    L, B, H, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    f = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    # the served lanes spread over the pool, as slots free and fill
    on = np.zeros(B, bool)
    on[np.random.default_rng(SEED).permutation(B)[:served]] = True
    # every layer the same random block: 4.8 GB of distinct draws would need
    # as much again while they are made
    return (jnp.tile(f(ks[0], 1, B, H, P, N), (L, 1, 1, 1, 1)),
            jnp.asarray(on),
            jnp.exp(-jnp.abs(f(ks[1], B, H))), f(ks[2], B, H, P),
            f(ks[3], B, N), f(ks[4], B, N))


def programs(interpret):
    """-> {form: a NEW jitted function (pool, active, a, dtx, Bm, Cm) ->
    (y of the last layer, pool)}."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import state

    def kernel(pool, active, a, dtx, Bm, Cm):
        lanes, count = state.served_lanes(active)

        def layer(pool, l):
            y, pool = state.state_step(pool, l, lanes, count, a, dtx, Bm,
                                       Cm, interpret=interpret)
            return pool, jnp.where(active[:, None, None], y, 0.0)
        pool, ys = jax.lax.scan(layer, pool, jnp.arange(pool.shape[0]))
        return ys[-1], pool

    def fusions(pool, active, a, dtx, Bm, Cm):
        def layer(pool, l):
            st = jax.lax.dynamic_index_in_dim(pool, l, keepdims=False)
            new = (a[..., None, None] * st
                   + dtx[..., None] * Bm[:, None, None, :])
            y = jnp.sum(new * Cm[:, None, None, :], axis=-1)
            st = jnp.where(active[:, None, None, None], new, st)
            pool = jax.lax.dynamic_update_index_in_dim(pool, st, l, 0)
            return pool, jnp.where(active[:, None, None], y, 0.0)
        pool, ys = jax.lax.scan(layer, pool, jnp.arange(pool.shape[0]))
        return ys[-1], pool

    return {"kernel": jax.jit(kernel, donate_argnums=0),
            "fusions": jax.jit(fusions, donate_argnums=0)}


def timed(fn, pool, rest, reps):
    """-> seconds a call of ``fn`` (the pool donated from call to call):
    best of three runs of ``reps``; a rehearsal makes one call and times
    nothing."""
    import jax

    _, pool = jax.block_until_ready(fn(pool, *rest))
    best = float("inf")
    for _ in range(3 if reps > 1 else 0):
        t0 = time.perf_counter()
        for _ in range(reps):
            _, pool = fn(pool, *rest)
        jax.block_until_ready(pool)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--served", default="64,44,20")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax

    from dynamo_tpu.ops import state

    rehearse = bool(os.environ.get("REHEARSE"))
    shape = TOY if rehearse else CELL
    L, B, H, P, N = shape
    reps = 1 if rehearse else 10
    lane_bytes = H * P * N * 4
    print(json.dumps({"device": jax.devices()[0].device_kind, "shape": shape,
                      "head_block": state.head_block(H, P, N)}))
    rows = []
    for served in (int(s) for s in args.served.split(",")):
        served = min(served, B)
        # one step of each form from the SAME pool, compared on the host a
        # layer at a time (two pools do not fit beside each other)
        seen = {}
        for form, fn in programs(rehearse).items():
            pool, *rest = operands(shape, served)
            before = np.asarray(pool[L - 1])
            y, pool = jax.block_until_ready(fn(pool, *rest))
            seen[form] = (np.asarray(y), np.asarray(pool[L - 1]))
            off = ~np.asarray(rest[0])
            kept = bool(np.array_equal(seen[form][1][off], before[off]))
            del pool
            seen[form] += (kept,)
        dy = float(np.abs(seen["kernel"][0] - seen["fusions"][0]).max())
        ds = float(np.abs(seen["kernel"][1] - seen["fusions"][1]).max())
        forms = [("fusions", None), ("kernel", None)] + [
            ("kernel", int(m)) for m in args.blocks.split(",") if m]
        for form, mib in forms:
            default = state._STATE_BLOCKS_BYTES
            if mib is not None:
                state._STATE_BLOCKS_BYTES = mib << 20
            try:
                pool, *rest = operands(shape, served)
                sec = timed(programs(rehearse)[form], pool, rest, reps)
            finally:
                state._STATE_BLOCKS_BYTES = default
            us = sec / L * 1e6
            row = {"form": form, "served": served, "lanes": B,
                   "us_layer_step": round(us, 2) if reps > 1 else None,
                   "served_state_gb_s": round(
                       2 * served * lane_bytes / (sec / L) / 1e9, 1)
                   if reps > 1 else None,
                   "unserved_kept": seen[form][2],
                   "max_dy": dy, "max_dstate": ds}
            if mib is not None:
                row["blocks_mib"] = mib
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
