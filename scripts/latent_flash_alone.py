#!/usr/bin/env python3
"""Builder's tool, on the chip: the latent flash call ALONE at the shapes of
``deepseek-v2-5l.longctx`` (one lane, a chunk of 256 tokens x 128 heads, the
rotary key stored 128 wide, compressed rows of 512, five layers a chunk).

    cd <tree> && python <this file> --out <file.npz> [--sweep [TxBS,...]] [case ...]
    python <this file> --compare <a.npz> <b.npz>

Imports ``dynamo_tpu`` from the CURRENT directory, so the same file times the
call of any checkout (the parent's unpacked beside the change's). A case is
the LAST chunk of a prompt of so many tokens in the context bucket the engine
would give it (``cache.read_slots``: the slots past the prompt at position 0,
invalid). Prints milliseconds a chunk (five calls, one a layer, in one
program; best of three runs of ten) and, where the tree has
``latent_flash_fetch``, the key blocks a call copies of those its grid has.
``--sweep`` (a tree whose call takes ``blocks``) times every block shape of
``SWEEP`` as well, or those named (``8x512,16x512``). ``--out`` keeps a
fingerprint of every output's bits for ``--compare``, which holds two trees'
results equal bit for bit. PERF.md
section 6, PR 43. ``REHEARSE=1 JAX_PLATFORMS=cpu ... tiny`` runs a toy size
through the interpreter (a rehearsal of the script, never a timing).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

from paged_kernel_alone import prints  # noqa: E402  (beside this file)

SEED, LAYERS, SCALE = 43, 5, 0.114721
# case -> chunk tokens, heads, K row as stored, compressed row, prompt
# tokens, context bucket
CASES = {
    "tiny": (8, 8, 128, 128, 200, 512),
    "1k": (256, 128, 128, 512, 1024, 1024),
    "2.2k": (256, 128, 128, 512, 2240, 4096),
    "4k": (256, 128, 128, 512, 4096, 4096),
    "9k": (256, 128, 128, 512, 9023, 16384),
    "12k": (256, 128, 128, 512, 12288, 16384),
}
# (tokens a query block, keys a key block)
SWEEP = [(t, bs) for bs in (256, 512, 1024) for t in (1, 2, 4, 8)] + [
    (16, 512)]


def inputs(name):
    import jax
    import jax.numpy as jnp

    T, Hq, Dk, Dv, n, S = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    bf = lambda key, shape: jax.random.normal(
        key, shape, jnp.float32).astype(jnp.bfloat16)
    # the rotary part of a query and of a key is 64 wide, stored 128
    half = (jnp.arange(Dk) < Dk // 2).astype(jnp.bfloat16)
    q = bf(ks[0], (1, T, Hq, Dk)) * half
    lat = bf(ks[1], (1, T, Hq, Dv))
    k = bf(ks[2], (LAYERS, 1, S, 1, Dk)) * half
    v = bf(ks[3], (LAYERS, 1, S, 1, Dv))
    slots = np.arange(S)
    q_pos = (n - T + np.arange(T, dtype=np.int32))[None]
    k_pos = np.where(slots < n, slots, 0).astype(np.int32)[None]
    k_valid = (slots < n)[None]
    return (q, lat, k, v, jnp.asarray(q_pos), jnp.asarray(k_pos),
            jnp.asarray(k_valid))


def timed(name, blocks=None):
    """-> (ms a chunk, the five outputs) of case ``name``."""
    import jax

    from dynamo_tpu.ops import attention

    interpret = bool(os.environ.get("REHEARSE"))
    extra = {} if blocks is None else {"blocks": blocks}

    def chunk(q, lat, k, v, q_pos, k_pos, k_valid):
        return [attention.flash_attention(
            q, k[l], v[l], q_pos, k_pos, k_valid, interpret=interpret,
            scale=SCALE, latent=lat, **extra) for l in range(LAYERS)]

    args = inputs(name)
    fn = jax.jit(chunk)
    outs = jax.block_until_ready(fn(*args))
    best = float("inf")
    runs, reps = (1, 1) if interpret else (3, 10)
    for _ in range(runs):
        t0 = time.perf_counter()
        for _ in range(reps):
            o = fn(*args)
        jax.block_until_ready(o)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3, outs


def copies(name, blocks=None):
    """-> (grid steps, key blocks copied) a call, or None on a tree whose
    call copies them all."""
    from dynamo_tpu.ops import attention

    if not hasattr(attention, "latent_flash_fetch"):
        return None
    T, Hq, _, _, _, S = CASES[name]
    _, _, _, _, q_pos, k_pos, k_valid = inputs(name)
    tokens, BS = blocks or attention.latent_flash_blocks(T, S, Hq)
    return attention.latent_flash_copies(attention.latent_flash_fetch(
        np.asarray(q_pos), np.asarray(k_pos), np.asarray(k_valid), tokens,
        BS, xp=np))


def compare(a, b):
    fa, fb = np.load(a), np.load(b)
    assert sorted(fa.files) == sorted(fb.files), (fa.files, fb.files)
    for key in fa.files:
        assert np.array_equal(fa[key], fb[key]), key
    print(json.dumps({"same_bits": True, "arrays": len(fa.files)}))


def main(argv):
    if argv[:1] == ["--compare"]:
        return compare(*argv[1:3])
    out = sweep = None
    if argv[:1] == ["--out"]:
        out, argv = argv[1], argv[2:]
    if argv[:1] == ["--sweep"]:
        sweep, argv = SWEEP, argv[1:]
        if argv and "x" in argv[0]:
            sweep = [tuple(map(int, b.split("x")))
                     for b in argv[0].split(",")]
            argv = argv[1:]
    names = argv or [n for n in CASES if n != "tiny"]
    kept = {}
    for name in names:
        ms, outs = timed(name)
        T, _, _, _, n, S = CASES[name]
        line = {"case": name, "chunk": T, "prompt": n, "bucket": S,
                "ms_a_chunk": round(ms, 3),
                "us_a_live_key": round(ms * 1e3 / n, 3)}
        seen = copies(name)
        if seen:
            line["key_blocks"], line["copied"] = seen
        print(json.dumps(line), flush=True)
        for l, o in enumerate(outs):
            kept[f"{name}.{l}"] = np.asarray(prints(o))
        for blocks in sweep or ():
            if T % blocks[0] or S % blocks[1]:
                continue
            try:
                ms, _ = timed(name, blocks)
            except Exception as e:   # a shape the compiler refuses
                print(json.dumps({"case": name, "blocks": list(blocks),
                                  "error": repr(e)[:200]}), flush=True)
                continue
            steps, copied = copies(name, blocks)
            print(json.dumps({"case": name, "blocks": list(blocks),
                              "ms_a_chunk": round(ms, 3),
                              "key_blocks": steps, "copied": copied}),
                  flush=True)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        np.savez(out, **kept)


if __name__ == "__main__":
    main(sys.argv[1:])
