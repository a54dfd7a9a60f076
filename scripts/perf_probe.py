"""Ablation timing probe for the engine's compiled programs on the attached
accelerator. Times each suspect in isolation to localize the decode/prefill
gap seen in bench.py.

Every measurement here (a) forces completion by fetching one scalar of the
result, (b) runs the op N times inside a lax.scan so the per-op cost is
(wall - RTT) / N, RTT being one trivial dispatch plus scalar fetch.

Run: python scripts/perf_probe.py [--model llama-3.2-1b] [--batch 32]
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama

RTT_MS = 0.0


def fetch(out):
    leaf = jax.tree.leaves(out)[0]
    return np.asarray(jax.tree.leaves(out)[0].ravel()[0])


def timeit(fn, *args, reps=3, warmup=1, **kw):
    """Wall ms per call, forcing real completion via a scalar fetch."""
    for _ in range(warmup):
        fetch(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        fetch(fn(*args, **kw))
    return (time.perf_counter() - t0) / reps * 1e3


def report(name, ms_call, n_inner):
    per = (ms_call - RTT_MS) / n_inner
    print(f"{name:44s} {ms_call:9.2f} ms/call {per:8.3f} ms/op")
    return per


def main():
    global RTT_MS
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-3.2-1b")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=512)
    ap.add_argument("--page", type=int, default=64)
    ap.add_argument("--inner", type=int, default=64)
    args = ap.parse_args()

    m = llama.preset(args.model, max_position=2048)
    B, S, page, N = args.batch, args.ctx, args.page, args.inner
    P = S // page
    n_pages = B * P + 1
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})  B={B} S={S} N={N}")

    # host round-trip: trivial dispatch + scalar fetch
    trivial = jax.jit(lambda x: x + 1)
    x0 = jnp.zeros(())
    fetch(trivial(x0))
    t0 = time.perf_counter()
    for _ in range(10):
        fetch(trivial(x0))
    RTT_MS = (time.perf_counter() - t0) / 10 * 1e3
    print(f"host RTT (dispatch+scalar fetch): {RTT_MS:.1f} ms")

    params = jax.device_put(llama.init_params(m, jax.random.PRNGKey(0)))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    k_pool = jnp.zeros((m.num_layers, m.num_kv_heads, n_pages, page,
                        m.head_dim), m.dtype)
    v_pool = jnp.zeros_like(k_pool)
    print(f"params {nbytes/1e9:.2f} GB; kv pools {2*k_pool.size*2/1e9:.2f} GB;"
          f" weights floor ~{nbytes/819e9*1e3:.2f} ms/step")

    tokens = jnp.ones((B,), jnp.int32)
    lengths = jnp.full((B,), S - N - 1, jnp.int32)
    page_tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)

    # --- matmul-only decode step (floor) ---------------------------------
    @jax.jit
    def matmul_only(params, tokens):
        lp = params["layers"]
        def body(x, _):
            h = x
            for l in range(m.num_layers):
                hn = llama.rms_norm(h, lp["ln1"][l], m.rms_eps)
                q = jnp.einsum("btd,dhk->bthk", hn, lp["wq"][l])
                k = jnp.einsum("btd,dhk->bthk", hn, lp["wk"][l])
                v = jnp.einsum("btd,dhk->bthk", hn, lp["wv"][l])
                h = h + jnp.einsum("bthk,hkd->btd", q + k.mean() + v.mean(),
                                   lp["wo"][l])
                h2 = llama.rms_norm(h, lp["ln2"][l], m.rms_eps)
                g = jnp.einsum("btd,df->btf", h2, lp["wg"][l])
                u = jnp.einsum("btd,df->btf", h2, lp["wu"][l])
                h = h + jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u,
                                   lp["wd"][l])
            hf = llama.rms_norm(h, params["final_norm"], m.rms_eps)
            head = (params["embed"].T if m.tie_embeddings
                    else params["lm_head"])
            logits = jnp.einsum("btd,dv->btv", hf, head.astype(hf.dtype))
            return h + logits.mean().astype(h.dtype), ()
        x = params["embed"][tokens][:, None]
        x, _ = jax.lax.scan(body, x, None, length=N)
        return x
    report("matmul-only step (scan)", timeit(matmul_only, params, tokens), N)

    # --- full forward_decode ---------------------------------------------
    for impl in ("pallas", "xla"):
        @jax.jit
        def run_n(params, tokens, k_pool, v_pool, page_tables, lengths):
            def body(carry, _):
                kp, vp, ln = carry
                logits, kp, vp = llama.forward_decode(
                    params, m, tokens, kp, vp, page_tables, ln,
                    attn_impl=impl)
                return (kp, vp, ln + 1), logits[:, 0, 0]
            (kp, vp, ln), outs = jax.lax.scan(
                body, (k_pool, v_pool, lengths), None, length=N)
            return outs
        report(f"forward_decode step [{impl}]",
               timeit(run_n, params, tokens, k_pool, v_pool, page_tables,
                      lengths), N)

    # --- pieces ----------------------------------------------------------
    @jax.jit
    def scatter_only(k_pool, v_pool):
        pos = lengths - 1
        w_page = jnp.take_along_axis(page_tables, (pos // page)[:, None],
                                     axis=1)[:, 0]
        w_off = pos % page
        kk = jnp.ones((B, m.num_kv_heads, m.head_dim), m.dtype)
        def body(carry, _):
            kp, vp = carry
            for l in range(m.num_layers):
                kp = kp.at[l, :, w_page, w_off].set(kk)
                vp = vp.at[l, :, w_page, w_off].set(kk)
            return (kp, vp), ()
        (kp, vp), _ = jax.lax.scan(body, (k_pool, v_pool), None, length=N)
        return kp
    report("pool scatter, all layers", timeit(scatter_only, k_pool, v_pool), N)

    from dynamo_tpu.ops.attention import paged_attention
    q = jnp.ones((B, m.num_heads, m.head_dim), m.dtype)

    # decode attention must stream the whole ATTENDED KV once per step.
    # The kernels read whole pages, so bytes/op counts the pages actually
    # touched: ceil(attended/page) * page tokens. Effective GB/s against
    # that floor localizes the HBM-bandwidth deficit (round-2 probe: ~9%
    # of the chip's 819 GB/s) per kernel VARIANT.
    attended = int(lengths[0])
    touched_tokens = -(-attended // page) * page
    kv_bytes = (B * touched_tokens * m.num_kv_heads * m.head_dim * 2
                * k_pool.dtype.itemsize * m.num_layers)

    def attn_report(ms_per_op):
        if ms_per_op > 0:
            gbs = kv_bytes / (ms_per_op * 1e-3) / 1e9
            print(f"{'':44s}  -> effective {gbs:7.1f} GB/s "
                  f"({kv_bytes/1e6:.1f} MB KV per step, "
                  f"{attended} of {args.ctx} tokens attended)")

    def paged_probe(label):
        @jax.jit
        def paged_only(q, k_pool, v_pool):
            def body(acc, _):
                for l in range(m.num_layers):
                    acc = acc + paged_attention(q, k_pool[l], v_pool[l],
                                                page_tables, lengths)
                return acc, ()
            acc, _ = jax.lax.scan(body, jnp.zeros_like(q), None, length=N)
            return acc
        per = report(f"paged_attention[{label}], all layers",
                     timeit(paged_only, q, k_pool, v_pool), N)
        attn_report(per)

    saved = os.environ.get("DYNAMO_TPU_PAGED_KERNEL")
    saved_ppb = os.environ.get("DYNAMO_TPU_PAGED_PPB")
    # the baseline runs must use the DEFAULT depth, not an inherited knob
    os.environ.pop("DYNAMO_TPU_PAGED_PPB", None)
    try:
        for variant in ("dma", "simple"):
            os.environ["DYNAMO_TPU_PAGED_KERNEL"] = variant
            paged_probe(variant)
        if dev.platform == "tpu":
            # DMA-depth sweep: pages-per-block trades issue-latency
            # amortization against partial-block waste
            os.environ["DYNAMO_TPU_PAGED_KERNEL"] = "dma"
            for ppb in (2, 4, 16):
                if ppb <= P:
                    os.environ["DYNAMO_TPU_PAGED_PPB"] = str(ppb)
                    paged_probe(f"dma ppb={ppb}")
    finally:
        for var, val in (("DYNAMO_TPU_PAGED_KERNEL", saved),
                         ("DYNAMO_TPU_PAGED_PPB", saved_ppb)):
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val

    @jax.jit
    def gather_attend_only(q, k_pool, v_pool):
        t = jnp.arange(S, dtype=jnp.int32)
        rp = jnp.take_along_axis(
            page_tables, jnp.broadcast_to((t // page)[None], (B, S)), axis=1)
        ro = jnp.broadcast_to((t % page)[None], (B, S))
        mask = (t[None] < lengths[:, None])[:, None, :]
        def body(acc, _):
            for l in range(m.num_layers):
                k_ctx = k_pool[l, :, rp, ro]
                v_ctx = v_pool[l, :, rp, ro]
                acc = acc + llama.attend(q[:, None], k_ctx, v_ctx, mask)[:, 0]
            return acc, ()
        acc, _ = jax.lax.scan(body, jnp.zeros_like(q), None, length=N)
        return acc
    report("gather+dense attend, all layers",
           timeit(gather_attend_only, q, k_pool, v_pool), N)

    from dynamo_tpu.engine.sampling import SamplingState, sample
    s = SamplingState.host_init(B)
    logits = jnp.ones((B, m.vocab_size), jnp.float32)

    @jax.jit
    def sample_n(logits, temp, top_p, top_k, key):
        def body(key, _):
            tok, logp, key2 = sample(logits, temp, top_p, top_k, key)
            return key2, tok
        key, toks = jax.lax.scan(body, key, None, length=N)
        return toks
    report("sample", timeit(sample_n, logits, jnp.asarray(s.temperature),
                            jnp.asarray(s.top_p), jnp.asarray(s.top_k),
                            s.key), N)

    # --- prefill chunks --------------------------------------------------
    C = 128
    Sp = 256
    NP = 8
    positions = jnp.arange(C, dtype=jnp.int32)[None]
    read_pos = jnp.arange(Sp, dtype=jnp.int32)[None]
    read_valid = (jnp.arange(Sp) < C)[None]

    for Bp in (1, 4, 8):
        for impl in ("flash", "xla"):
            tk = jnp.ones((Bp, C), jnp.int32)
            pos = jnp.broadcast_to(positions, (Bp, C))
            wi = (jnp.arange(Bp)[:, None] * Sp
                  + jnp.arange(C)[None]).astype(jnp.int32)
            ri = (jnp.arange(Bp)[:, None] * Sp
                  + jnp.arange(Sp)[None]).astype(jnp.int32)
            rp_ = jnp.broadcast_to(read_pos, (Bp, Sp))
            rv = jnp.broadcast_to(read_valid, (Bp, Sp))

            @jax.jit
            def prefill_n(params, tk, k_pool, v_pool):
                def body(carry, _):
                    kp, vp = carry
                    logits, kp, vp = llama.forward(
                        params, m, tk, pos, kp, vp, wi, ri, rp_, rv,
                        attn_impl=impl)
                    return (kp, vp), logits[:, -1, 0]
                (kp, vp), outs = jax.lax.scan(body, (k_pool, v_pool), None,
                                              length=NP)
                return outs
            per = report(f"prefill C={C} B={Bp} [{impl}]",
                         timeit(prefill_n, params, tk, k_pool, v_pool), NP)
            print(f"{'':44s} -> {Bp*C/per*1e3:10.0f} tok/s")


if __name__ == "__main__":
    main()
