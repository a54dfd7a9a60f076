"""Benchmark: decode throughput, TTFT, prefill throughput and MFU of the
in-tree JAX engine on the attached accelerator (CPU as fallback). Replaced
by the benchmark PR (ROADMAP Speed 1); the chip proof is chip_smoke.py.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "sweep": [...]}

Resilience contract: backend init can fail or hang. The bench therefore
  1. probes backend init in a SUBPROCESS with a timeout (a hang cannot take
     down the bench process), retrying once;
  2. on probe failure forces ``JAX_PLATFORMS=cpu`` before importing jax in
     this process and still emits a JSON line (``tpu: "unavailable"``);
  3. wraps everything so any error yields a JSON error line, never a bare
     traceback with rc=1.

Primary metric: best steady-state decode tokens/sec/chip on Llama-3.2-1B
shapes (bf16, random-init weights — throughput is weight-value independent)
across batch sizes 1/8/32, 128-token prompts, 128 generated tokens. The
reference publishes no absolute numbers (BASELINE.md); ``vs_baseline`` is
measured against a nominal Dynamo+vLLM H100 figure for a 1B-class model
(TARGET_TOK_S). An 8B-shaped sweep runs when the chip's HBM fits bf16 8B
weights (v5e 16G does not; it is recorded as skipped there).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# North-star decode target (BASELINE.md publishes no absolute tok/s table,
# so this is derived, not copied): vLLM-class serving sustains roughly
# 1-1.5% MFU-equivalent per-token bandwidth at 1B-class bf16 decode; on
# H100 (~3.35 TB/s HBM) an 8B model decodes ~2.5k tok/s/GPU and a 1B-class
# model is memory-bound at ~4k with realistic batching — the same arithmetic
# lands near 4k on v5e (819 GB/s HBM, 2.5 GB of 1B-bf16 weights ->
# ~330 tok/s/batch-line * b=16 effective). vs_baseline is this nominal
# constant; `mfu` in the payload is the hardware-normalized truth.
TARGET_TOK_S = 4000.0
PROBE_TIMEOUT_S = float(os.environ.get("DYNAMO_BENCH_PROBE_TIMEOUT", "150"))
BUDGET_S = float(os.environ.get("DYNAMO_BENCH_BUDGET", "1500"))
# Every (model, batch) measurement is flushed here the moment it lands: a
# run cut mid-sweep must leave the points already measured as a real
# artifact
PARTIAL_PATH = os.environ.get(
    "DYNAMO_BENCH_PARTIAL", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "BENCH_PARTIAL.json"))
# Besides the rolling partial, every (model, batch) point gets its OWN
# artifact file the moment it lands — a later wedge (or a corrupted rolling
# write) can never take already-measured points with it.
POINTS_DIR = os.environ.get(
    "DYNAMO_BENCH_POINTS_DIR", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "bench_points"))

def _probe_backend(timeout_s: float):
    """Initialize the jax backend in a subprocess. Returns (platform,
    device_kind) or None. A hung PJRT plugin kills the child, not us."""
    code = ("import jax\n"
            "d = jax.devices()[0]\n"
            "print('PROBE|' + d.platform + '|' + d.device_kind)\n")
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=timeout_s)
    except Exception:
        return None
    if r.returncode != 0:
        return None
    for line in r.stdout.splitlines():
        if line.startswith("PROBE|"):
            _, plat, kind = line.strip().split("|", 2)
            return plat, kind
    return None


def _flush_partial(payload: dict) -> None:
    """Atomically write the in-progress result. Never allowed to fail the
    bench: a read-only FS just loses the hedge, not the run."""
    try:
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, PARTIAL_PATH)
    except Exception:
        pass


def _flush_point(model: str, entry: dict, meta: dict) -> None:
    """One self-contained JSON artifact per (model, batch) point, carrying
    the platform tag so even a single surviving point is attributable."""
    try:
        os.makedirs(POINTS_DIR, exist_ok=True)
        batch = entry.get("batch", "x")
        path = os.path.join(POINTS_DIR, f"{model}_b{batch}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({**meta, "model": model, **entry}, f)
        os.replace(tmp, path)
    except Exception:
        pass


def _run_model(model_cfg, batches, prompt_len, gen_tokens, max_context,
               on_tpu, deadline, flush=None):
    """For each batch size, build an EngineCore sized max_batch=b (decode
    dispatches always run at full engine width, so measuring batch b inside a
    max-sized engine would measure padding, not batch-b performance), run a
    warmup (compile) round then a timed round. Returns (n_params, sweep)."""
    import jax

    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions

    def make_core(b: int) -> EngineCore:
        # decode_steps amortizes the per-dispatch host round-trip at the
        # cost of up to decode_steps-1 wasted steps on the final dispatch
        # of a finished sequence; the values are for the benchmark PR to
        # re-derive on the chip
        return EngineCore(JaxEngineConfig(
            model=model_cfg, tp=1, page_size=64, max_batch=b,
            max_context=max_context, prefill_chunk=min(512, max_context),
            decode_steps=32 if on_tpu else 8))

    core = None
    n_params = None
    # prompt ids must stay inside the model vocab: out-of-range ids clamp in
    # the embedding gather and degenerate every prompt to the same token
    mod = min(997, model_cfg.vocab_size - 1)

    def round_(tag: str, b: int, salt: int):
        # unique prompts per round: the warm round must compile the same
        # (no-prefix-hit) program the timed round runs, and timed TTFT must
        # measure a true prefill, not a prefix-cache hit
        prompt = list(range(1, prompt_len + 1))
        t0 = time.monotonic()
        for i in range(b):
            core.submit(f"{tag}{i}", BackendInput(
                token_ids=[(p * 31 + i * 7 + salt) % mod + 1 for p in prompt],
                stop=StopConditions(max_tokens=gen_tokens, ignore_eos=True)))
        done = 0
        tokens = 0
        post_tokens = 0          # tokens emitted by dispatches after t_first
        first: dict = {}
        t_first = None           # wall time when the last first-token landed
        while done < b:
            outs = core.step()
            now = time.monotonic()
            counted = t_first is not None  # this whole dispatch is post-first
            for so in outs:
                tokens += 1
                if so.seq_id not in first:
                    first[so.seq_id] = now - t0
                if so.finish is not None:
                    done += 1
            if counted:
                post_tokens += len(outs)
            elif len(first) == b:
                t_first = now - t0
        return (tokens, time.monotonic() - t0, sorted(first.values()),
                t_first, post_tokens)

    sweep = []

    def _record(entry):
        sweep.append(entry)
        if flush is not None:
            flush(n_params, sweep, entry)

    for b in batches:
        if time.monotonic() > deadline:
            _record({"batch": b, "skipped": "time budget"})
            continue
        try:
            core = None  # drop the previous core BEFORE building the next
            # one: params + KV pools of two cores resident at once would OOM
            # the 8B sweep on exactly the chips its HBM gate admits
            core = make_core(b)
            if n_params is None:
                n_params = sum(int(a.size)
                               for a in jax.tree.leaves(core.params))
            round_(f"warm{b}_", b, salt=2 * b)       # compile + warm caches
            g0 = core.goodput.lifetime()             # timed-round baseline
            tokens, wall, ttfts, t_first, post_tokens = round_(
                f"bench{b}_", b, salt=2 * b + 1)
            g1 = core.goodput.lifetime()
        except Exception as e:
            # one batch failing (e.g. OOM at the largest size) must not
            # discard the batches already measured for this model
            _record({"batch": b, "error": f"{type(e).__name__}: {e}"})
            continue
        # steady-state decode rate: tokens from dispatches strictly after the
        # one that produced the last first-token, over the time after it —
        # both the prefill and that mixed first dispatch are excluded
        decode_wall = (wall - t_first) if t_first else 0.0
        tok_s = (post_tokens / decode_wall
                 if post_tokens > 0 and decode_wall > 0 else tokens / wall)
        entry = {
            "batch": b,
            "decode_tok_s": round(tok_s, 1),
            "p50_ttft_s": round(ttfts[len(ttfts) // 2], 4),
            "prefill_tok_s": (round(b * prompt_len / ttfts[-1], 1)
                              if ttfts else None),
            "total_tok_s": round(tokens / wall, 1),
        }
        # goodput accounting (utils/roofline.py): analytic FLOPs/bytes of
        # the timed round's dispatches over their measured wall time,
        # against the platform peak (TPU table / calibrated CPU). Non-null
        # on EVERY platform — `mfu: null` is dead.
        busy = g1["busy_s"] - g0["busy_s"]
        if busy > 0:
            d_flops = g1["flops_total"] - g0["flops_total"]
            d_bytes = g1["bytes_total"] - g0["bytes_total"]
            entry["mfu"] = round(d_flops / busy / g1["peak_flops"], 4)
            entry["mbu"] = round(
                d_bytes / busy / (g1["peak_hbm_gbps"] * 1e9), 4)
            entry["hbm_gbps"] = round(d_bytes / busy / 1e9, 2)
            entry["peak_source"] = g1["peak_source"]
        try:
            # prefix-reuse TTFT: the same prompts again — admission matches
            # the cached blocks, so only the last token truly prefills
            # (the KV-aware-routing / prefix-cache serving claim, measured)
            if time.monotonic() < deadline:
                _, _, warm_ttfts, _, _ = round_(
                    f"reuse{b}_", b, salt=2 * b + 1)
                entry["p50_ttft_warm_s"] = round(
                    warm_ttfts[len(warm_ttfts) // 2], 4)
        except Exception:  # noqa: BLE001 - warm pass is optional
            pass
        _record(entry)
    return n_params, sweep


def _spec_ab(on_tpu, deadline, flush_point):
    """Speculative-decoding A/B: the same engine with DYN_SPEC off vs
    ``spec='ngram'`` on a repetitive/structured workload, so the n-gram
    proposer has real hit rate. Random-init weights are scaled toward zero,
    which makes greedy generation collapse into the repetition attractor a
    TRAINED model exhibits on structured prompts (code, JSON, extraction) —
    the token map becomes (near) position-independent, so the stream cycles
    and prompt-lookup drafts verify. Throughput numbers stay honest: weight
    VALUES don't change the math executed per token, and the measured
    ``spec_accept_rate`` is recorded alongside so the win is attributable.

    Emits spec_decode_tok_s / spec_off_decode_tok_s / spec_accept_rate as a
    self-contained bench_points artifact, so the next TPU window measures
    the win unattended."""
    import jax

    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig
    from dynamo_tpu.llm.protocols.common import BackendInput, StopConditions
    from dynamo_tpu.models import llama

    if on_tpu:
        mcfg = llama.preset("llama-3.2-1b", max_position=2048)
        batch, gen, k, steps, ctx = 8, 128, 32, 32, 1024
    else:
        # big enough that bf16 weights (~59 MB) exceed the LLC: CPU decode
        # is then memory-bandwidth-bound over the weight stream, the same
        # regime the TPU win comes from (a cache-resident tiny model would
        # A/B the dispatch overhead instead)
        mcfg = llama.LlamaConfig(
            vocab_size=4096, hidden_size=512, num_layers=8, num_heads=8,
            num_kv_heads=4, head_dim=64, intermediate_size=1536,
            rope_theta=10000.0, max_position=1024)
        batch, gen, k, steps, ctx = 4, 64, 16, 8, 512

    def build(spec):
        core = EngineCore(JaxEngineConfig(
            model=mcfg, tp=1, page_size=64, max_batch=batch,
            max_context=ctx, prefill_chunk=min(128, ctx),
            decode_steps=steps, spec=spec, spec_k=k))
        core.params = jax.jit(
            lambda p: jax.tree.map(lambda a: a * 0.05, p))(core.params)
        return core

    def measure(core, n, tag):
        prompt = [5, 6, 7, 8, 9, 10, 11, 12] * 8
        t0 = time.monotonic()
        for i in range(batch):
            core.submit(f"{tag}{i}", BackendInput(
                token_ids=[p + i for p in prompt],
                stop=StopConditions(max_tokens=n, ignore_eos=True)))
        toks = done = post = 0
        t_first = None
        seen = set()
        while done < batch:
            outs = core.step()
            now = time.monotonic()
            counted = t_first is not None
            for so in outs:
                toks += 1
                seen.add(so.seq_id)
                if so.finish is not None:
                    done += 1
            if counted:
                post += len(outs)
            elif len(seen) == batch:
                t_first = now - t0
        wall = time.monotonic() - t0
        return (post / (wall - t_first)
                if t_first and post and wall > t_first else toks / wall)

    entry = {"batch": batch, "spec_k": k, "gen_tokens": gen,
             "params_m": None}
    prev_adapt = os.environ.get("DYN_SPEC_ADAPT")
    os.environ["DYN_SPEC_ADAPT"] = "0"   # fixed k: one verify bucket to
    try:                                 # compile, stable timed round
        for spec, key in (("off", "spec_off_decode_tok_s"),
                          ("ngram", "spec_decode_tok_s")):
            if time.monotonic() > deadline:
                entry["skipped"] = "time budget"
                break
            core = build(spec)
            if entry["params_m"] is None:
                entry["params_m"] = round(sum(
                    int(a.size) for a in jax.tree.leaves(core.params)) / 1e6,
                    1)
            measure(core, gen // 2, "warm")       # compile + warm caches
            entry[key] = round(measure(core, gen, "bench"), 1)
            if spec == "ngram":
                entry["spec_accept_rate"] = round(
                    core.spec_accepted_total
                    / max(1, core.spec_proposed_total), 3)
                entry["spec_proposed"] = core.spec_proposed_total
            del core
    finally:
        if prev_adapt is None:
            os.environ.pop("DYN_SPEC_ADAPT", None)
        else:
            os.environ["DYN_SPEC_ADAPT"] = prev_adapt
    flush_point(entry)
    return entry


def main() -> None:
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    try:  # a stale partial from a previous run must never be mistaken for
        os.remove(PARTIAL_PATH)  # this run's artifact by the salvage path
    except OSError:
        pass

    probe = _probe_backend(PROBE_TIMEOUT_S)
    if probe is None:
        probe = _probe_backend(PROBE_TIMEOUT_S)  # one retry
    tpu_status = "ok"
    if probe is None or probe[0] == "cpu":
        # accelerator init failed/hung twice (or only CPU exists): force the
        # CPU path before this process ever touches a backend
        from dynamo_tpu.utils.jaxenv import force_cpu

        force_cpu(1)
        if probe is None:
            tpu_status = "unavailable"

    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    on_tpu = platform not in ("cpu",)
    # peak normalization lives in utils/roofline.py now (one table for the
    # engine's goodput plane and this bench); entries carry peak_source

    from dynamo_tpu.models import llama

    notes = []
    if on_tpu:
        runs = [("llama-3.2-1b",
                 llama.preset("llama-3.2-1b", max_position=2048),
                 [1, 8, 32], 128, 128, 1024)]
        try:
            hbm = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        except Exception:
            hbm = 0
        if not hbm:
            # PJRT plugins may expose no memory_stats; fall back to the
            # chip family's known HBM capacity
            kind = dev.device_kind.lower()
            hbm = int(95e9 if "v5p" in kind else 32e9 if "v6" in kind
                      else 32e9 if "v4" in kind else 16e9)
            notes.append(f"hbm from device_kind table: {hbm/1e9:.0f}G")
        if hbm >= 22e9:  # 8B bf16 weights are 16G; need headroom for KV+work
            runs.append(("llama-3-8b",
                         llama.preset("llama-3-8b", max_position=2048),
                         [1, 8], 128, 128, 1024))
        else:
            notes.append(f"8B sweep skipped: HBM {hbm/1e9:.1f}G < 22G "
                         "(bf16 8B weights alone are 16G)")
    else:
        runs = [("tiny-byte", llama.preset("tiny-byte"), [1, 4], 32, 32, 256)]

    sweeps = []

    def assemble(partial: bool):
        best = None
        for sw in sweeps:
            if sw.get("model") == runs[0][0]:
                done = [e for e in sw.get("results", []) if "decode_tok_s" in e]
                if done:
                    best = max(done, key=lambda e: e["decode_tok_s"])
        return {
            "metric": "decode_tok_s_per_chip",
            "value": best["decode_tok_s"] if best else 0.0,
            "unit": "tok/s",
            "vs_baseline": (round(best["decode_tok_s"] / TARGET_TOK_S, 3)
                            if best else 0.0),
            "platform": platform,
            "device_kind": dev.device_kind,
            "tpu": tpu_status,
            "model": runs[0][0],
            "best_batch": best.get("batch") if best else None,
            "p50_ttft_s": best.get("p50_ttft_s") if best else None,
            "mfu": best.get("mfu") if best else None,
            "mbu": best.get("mbu") if best else None,
            "hbm_gbps": best.get("hbm_gbps") if best else None,
            "peak_source": best.get("peak_source") if best else None,
            "paged_kernel": (os.environ.get("DYNAMO_TPU_PAGED_KERNEL", "dma")
                             if platform == "tpu" else "simple[interpret]"),
            "sweep": sweeps,
            "notes": notes,
            "partial": partial,
            "wall_s": round(time.monotonic() - t_start, 1),
        }

    point_meta = {"platform": platform, "device_kind": dev.device_kind,
                  "tpu": tpu_status}
    # an artifact must exist BEFORE the first point: a wedge inside the
    # first warmup/compile round still leaves a platform-tagged record
    _flush_partial(assemble(partial=True))

    for name, mcfg, batches, plen, gen, ctx in runs:
        if time.monotonic() > deadline:
            sweeps.append({"model": name, "skipped": "time budget"})
            continue
        live = {"model": name, "prompt_len": plen, "gen_tokens": gen,
                "results": []}
        sweeps.append(live)

        def flush(n_params, sweep, entry, live=live, name=name):
            live["n_params"] = n_params
            live["results"] = sweep
            _flush_partial(assemble(partial=True))
            _flush_point(name, entry, point_meta)

        try:
            n_params, sweep = _run_model(mcfg, batches, plen, gen, ctx,
                                         on_tpu, deadline, flush=flush)
        except Exception as e:
            # a later run (e.g. the conditional 8B sweep) must never zero an
            # already-measured headline — record and keep going
            live["error"] = f"{type(e).__name__}: {e}"
            continue
        live["n_params"] = n_params
        live["results"] = sweep

    # speculative-decoding A/B (its own engines; never allowed to take the
    # headline sweep down with it)
    spec_ab = None
    try:
        if time.monotonic() < deadline:
            spec_ab = _spec_ab(
                on_tpu, deadline,
                lambda e: _flush_point("spec_ab", e, point_meta))
        else:
            spec_ab = {"skipped": "time budget"}
    except Exception as e:  # noqa: BLE001
        spec_ab = {"error": f"{type(e).__name__}: {e}"}

    # the headline (and vs_baseline, a 1B-class target) is strictly the
    # first model's sweep — a later model must never stand in for it;
    # assemble() enforces that by matching runs[0][0]
    result = assemble(partial=False)
    if spec_ab is not None:
        result["spec_ab"] = spec_ab
    _flush_partial(result)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # never a bare traceback: emit a parseable line
        import traceback

        print(json.dumps({
            "metric": "decode_tok_s_per_chip", "value": 0.0, "unit": "tok/s",
            "vs_baseline": 0.0, "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc(limit=3),
        }), flush=True)
