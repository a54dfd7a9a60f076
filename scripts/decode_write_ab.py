#!/usr/bin/env python3
"""Builder's tool, on the chip: what the decode step pays for its K/V write.

    python scripts/decode_write_ab.py [qwen2-1.5b] [mistral]

The decode scan (4 steps of ``llama.forward_decode`` + argmax) of a dense
preset at full width and the benchmark's depth, lanes and pool, seeded
weights, once with the new rows written by the paged kernel and once with
``kv_write`` in front of it (``llama.kernel_writes`` patched in THIS script
only; each side a NEW function object, since ``jax.jit`` caches a trace by
function identity): greedy tokens compared, ``memory_analysis()`` of both
programs, then ms a step (best of three runs of ten dispatches). One process,
about four minutes for both presets. PERF.md section 6, PR 38, has the
readings. ``JAX_PLATFORMS=cpu ... tiny`` runs a toy size through
the interpreter (a rehearsal of the script, never a timing).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.models import llama  # noqa: E402

PAGE, STEPS = 64, 4


def case(name, layers, lanes, context, pages, shortest, longest, **over):
    cfg = llama.preset(name, num_layers=layers, **over)
    params = jax.jit(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))()
    rng = np.random.default_rng(5)
    lengths = rng.integers(shortest, longest, lanes).astype(np.int32)
    tables = np.zeros((lanes, context // PAGE), np.int32)
    free, used = rng.permutation(np.arange(1, pages)), 0
    for b in range(lanes):
        n = -(-int(lengths[b] + 2 * STEPS) // PAGE)
        tables[b, :n] = free[used:used + n]
        used += n
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    pool = (cfg.num_layers, cfg.num_kv_heads, pages, PAGE, cfg.head_dim)
    first = jnp.asarray(rng.integers(0, cfg.vocab_size, lanes), jnp.int32)

    def scan(p, t, k, v, ln):
        def one(carry, _):
            t, ln, k, v = carry
            lg, k, v = llama.forward_decode(p, cfg, t, k, v, tables, ln,
                                            attn_impl="pallas")
            t = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
            return (t, ln + 1, k, v), t
        (t, ln, k, v), toks = jax.lax.scan(one, (t, ln, k, v), None,
                                           length=STEPS)
        return toks, k, v

    ms, tokens, memory = {}, {}, {}
    real = llama.kernel_writes
    try:
        for how in ("scatter", "kernel"):
            llama.kernel_writes = real if how == "kernel" else (
                lambda *a: False)
            # a NEW function a side: jit's trace cache keys on the function
            step = jax.jit(lambda *a: scan(*a), donate_argnums=(2, 3))
            k = jax.random.normal(jax.random.PRNGKey(1), pool, cfg.dtype) * 0.3
            v = jax.random.normal(jax.random.PRNGKey(2), pool, cfg.dtype) * 0.3
            program = step.lower(params, first, k, v, lengths).compile()
            analysis = program.memory_analysis()
            memory[how] = {"temporaries": analysis.temp_size_in_bytes,
                           "aliased": analysis.alias_size_in_bytes}
            toks, k, v = program(params, first, k, v, lengths)
            tokens[how] = np.asarray(toks)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(10):
                    toks, k, v = program(params, first, k, v, lengths)
                jax.block_until_ready((toks, k, v))
                best = min(best, (time.perf_counter() - t0) / 10)
            ms[how] = best * 1e3 / STEPS
            del k, v
    finally:
        llama.kernel_writes = real
    return {"ms_a_step": ms, "memory": memory,
            "tokens_equal": bool((tokens["scatter"] == tokens["kernel"]).all())}


def main(which) -> int:
    cases = {
        "tiny": ("tiny-qwen", 2, 4, 256, 40, 40, 200, {"head_dim": 128}),
        "qwen2-1.5b": ("qwen2-1.5b", 28, 32, 512, 1089, 40, 480, {}),
        "mistral": ("mistral-7b", 16, 16, 2176, 529, 600, 2100, {}),
    }
    out = {"device": jax.devices()[0].device_kind}
    for name in which or ["qwen2-1.5b", "mistral"]:
        *args, over = cases[name]
        out[name] = case(*args, **over)
        print(name, json.dumps(out[name]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/decode_write_ab.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
