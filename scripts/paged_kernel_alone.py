#!/usr/bin/env python3
"""Builder's tool, on the chip: the paged dma kernel ALONE at a cell's shapes.

    cd <tree> && python <this file> --out <file.npz> [chat] [saturate] ...
    python <this file> --compare <a.npz> <b.npz>

Imports ``dynamo_tpu`` from the CURRENT directory, so the same file times the
kernel of any checkout (the parent's unpacked beside the change's). A case is
a cell's decode kernel as its program calls it (lanes, heads, rows, layers,
pool, window, sink; the new rows written by the kernel) under a length mix as
the cell's ``engine.batch_occupancy`` gives it: served lanes drawn from a
seed, the others as the decode program hands them to the kernel since PR 46
(length 0, an all-zero table: the kernel skips them; ``--unserved 1`` hands
them over as the programs before it did, length 1, for a timing of such a
tree as it ran). ``<case>-only`` is the case with ONLY its served lanes in
the batch (``lanes = served``: the same lanes, lengths and pages, no other
grid step), the bound of what skipping a lane can give. One dispatch is 4
steps of one call a layer. ``--profile`` also traces one dispatch and prints
its device operations by total time (events, microseconds an event): what of
a call is the kernel and what the operations around it. Prints
microseconds a call (best of three runs of ten dispatches) and, where the
tree has ``paged_live_pages``, the page copies a call and pool; ``--out``
keeps the served lanes' attention outputs and the pools (scratch page 0
apart: every unserved lane writes there, in no order; large ones as a
fingerprint of their bits) for ``--compare``,
which holds two trees' results equal bit for bit. PERF.md section 6, PR 41.
``JAX_PLATFORMS=cpu ... tiny`` runs a toy size through the
interpreter (a rehearsal of the script, never a timing).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

PAGE, STEPS, SEED = 64, 4, 41

# case -> lanes, kv heads, group, K row as stored, V row, tokens a pool row,
# layers, pool pages, table pages, window, sink, served lanes (the ledger's
# PR 39 ``engine.batch_occupancy``), their lengths
CASES = {
    "tiny": (4, 2, 2, 128, 128, 1, 2, 40, 4, None, False, 2,
             lambda rng, n: rng.integers(40, 200, n)),
    "chat": (32, 2, 6, 128, 128, 1, 28, 1089, 16, None, False, 4,
             lambda rng, n: rng.integers(150, 900, n)),
    "saturate": (32, 2, 6, 128, 128, 1, 28, 1089, 8, None, False, 31,
                 lambda rng, n: rng.integers(40, 450, n)),
    "mistral": (16, 8, 4, 128, 128, 1, 16, 529, 34, None, False, 1,
                lambda rng, n: rng.integers(600, 2100, n)),
    "mimo-full": (32, 4, 16, 256, 128, 1, 2, 1300, 128, None, False, 17,
                  lambda rng, n: np.clip(rng.lognormal(np.log(1024), 1.2, n),
                                         128, 8000).astype(int)),
    "mimo-window": (32, 8, 8, 256, 128, 1, 5, 225, 128, 128, True, 17,
                    lambda rng, n: np.clip(rng.lognormal(np.log(1024), 1.2,
                                                         n),
                                           128, 8000).astype(int)),
    "granite": (64, 8, 4, 64, 64, 2, 4, 2113, 32, None, False, 55,
                lambda rng, n: rng.integers(160, 1800, n)),
}


def prints(a):
    """A fingerprint of a bfloat16 array's BITS, one uint32 an index of its
    two leading dimensions: every element's bits times an odd weight of its
    place, summed modulo 2**32 (any changed bit changes it)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def of(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
        flat = bits.reshape(a.shape[0], a.shape[1], -1)
        place = jnp.arange(flat.shape[-1], dtype=jnp.uint32)
        return jnp.sum(flat * (place * jnp.uint32(2654435761) + 1), axis=-1,
                       dtype=jnp.uint32)
    return of(a)


def device_ops(fn, *args):
    """One traced call of ``fn``: [name, events, us an event] of the device's
    XLA operations, the ten that took longest in all."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(fn(*args))
        pb, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        took = {}
        for plane in ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        took.setdefault(e.name.split(" = ")[0].rstrip(
                            ".0123456789"), []).append(e.duration_ns * 1e-3)
    top = sorted(took.items(), key=lambda kv: -sum(kv[1]))[:10]
    return [[n, len(v), round(sum(v) / len(v), 3)] for n, v in top]


def case(name, unserved=0, profile=False):
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import attention

    only = name.endswith("-only")
    (B, Hkv, G, Dk, Dv, fold, L, n_pages, P, window, sunk, served,
     draw) = CASES[name.removesuffix("-only")]
    rng = np.random.default_rng(SEED)
    lengths = np.full(B, unserved, np.int32)
    lanes = np.sort(rng.permutation(B)[:served])
    lengths[lanes] = draw(rng, served)
    if only:
        B, lengths, lanes = served, lengths[lanes], np.arange(served)
    tables = np.zeros((B, P), np.int32)
    free, used = rng.permutation(np.arange(1, n_pages)), 0
    for b in lanes:
        lo = 0 if window is None else max(lengths[b] - window, 0) // PAGE
        hi = -(-int(lengths[b] + STEPS) // PAGE)
        tables[b, lo:hi] = free[used:used + hi - lo]   # a window lane holds
        used += hi - lo                                # its window's pages
    assert used < n_pages - 1, (name, used)
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(SEED), 6)
    q = jax.random.normal(keys[0], (L, B, Hkv * G, Dk), bf)
    kn = jax.random.normal(keys[1], (STEPS, B, Hkv, Dk), bf)
    vn = jax.random.normal(keys[2], (STEPS, B, Hkv, Dv), bf)
    kw = {"window": window}
    if sunk:
        kw["sink"] = jax.random.normal(keys[3], (Hkv * G,), jnp.float32)
    if fold > 1:
        kw["fold"] = fold
    pt = jnp.asarray(tables)

    def dispatch(k, v, ln):
        def step(carry, new):
            k, v, ln = carry
            outs = []
            for l in range(L):
                o, k, v = attention.paged_attention(
                    q[l], k, v, pt, ln, l, new=new, **kw)
                outs.append(o)
            # a lane the dispatch does not serve stays one: the program
            # hands the kernel 0 for it at every step
            return (k, v, ln + (ln > 0)), jnp.stack(outs)
        (k, v, _), outs = jax.lax.scan(step, (k, v, ln), (kn, vn))
        return outs, k, v

    shape = (L, Hkv, n_pages, PAGE // fold)
    k = jax.random.normal(keys[4], (*shape, fold * Dk), bf) * 0.3
    v = jax.random.normal(keys[5], (*shape, fold * Dv), bf) * 0.3
    ln = jnp.asarray(lengths)
    fn = jax.jit(dispatch, donate_argnums=(0, 1))
    outs, k, v = fn(k, v, ln)
    kept = {"out": np.asarray(outs[:, :, lanes].astype(jnp.float32)),
            "k": np.asarray(prints(k[:, :, 1:])),
            "v": np.asarray(prints(v[:, :, 1:]))}
    finite = bool(np.isfinite(kept["out"]).all())
    if kept["out"].size > 1 << 20:
        kept["out"] = np.asarray(prints(outs[:, :, lanes]))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(10):
            outs, k, v = fn(k, v, ln)
        jax.block_until_ready((outs, k, v))
        best = min(best, (time.perf_counter() - t0) / 10)
    said = {"us_a_call": best * 1e6 / (L * STEPS), "lanes": B,
            "served": served, "finite": finite}
    if profile:
        said["device_ops"] = device_ops(fn, k, v, ln)   # (the pools' last use)
    count = getattr(attention, "paged_live_pages", None)
    if count is not None:
        live, visited = count(
            lengths, P, PAGE, getattr(attention, "PAGES_PER_BLOCK", 8),
            window)
        said["copies_a_call_and_pool"] = int(live.sum())
        said["before"] = int(visited.sum())
    return said, kept


def main(argv) -> int:
    if argv[:1] == ["--compare"]:
        a, b = (np.load(f) for f in argv[1:3])
        same = {n: bool(np.array_equal(a[n], b[n])) for n in a.files}
        print(json.dumps(same))
        return 0 if all(same.values()) and set(a.files) == set(b.files) else 1
    profile = "--profile" in argv
    argv = [a for a in argv if a != "--profile"]
    given = {"--out": None, "--unserved": "0"}
    while argv[:1] and argv[0] in given:
        given[argv[0]], argv = argv[1], argv[2:]
    out, unserved = given["--out"], int(given["--unserved"])
    kept = {}
    for name in argv or [c for c in CASES if c != "tiny"]:
        said, arrays = case(name, unserved, profile)
        print(name, json.dumps(said), flush=True)
        kept.update({f"{name}.{n}": a for n, a in arrays.items()})
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        np.savez_compressed(out, **kept)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
