"""One run of one cell, from the manifest entry to the result line."""

from __future__ import annotations

import asyncio
import glob
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import correct, launch, records, runner
from .catalog import ROOT, BenchError, Catalog
from .modeldir import tokens_of, write_model_dir
from .peaks import peaks_for
from .traffic import Request, RequestSource, completion_body

MODEL_NAME = "bench"
SCRATCH = os.path.join(ROOT, ".bench_scratch")
SAMPLE_TOKENS = 64                  # per prompt, four prompts: 256 positions
MAX_ENGINE_SEED = 2 ** 31 - 1       # PRNGKey takes what 32 signed bits hold


def _engine_info(series: launch.Series) -> Dict[str, str]:
    info = [l for n, l, v in series if n == "dyn_engine_info" and v == 1]
    if len(info) != 1:
        raise BenchError(f"expected one dyn_engine_info series, got {info!r}")
    return info[0]


def _check_what_runs(info: Dict[str, str], chips: int) -> None:
    """No fallback, ever: a TPU from the benchmark's own peak table, the
    compiled Pallas kernels, and as many devices as the cell asks for."""
    if info["platform"] != "tpu":
        raise BenchError(f"engine runs on {info['platform']!r}, not a TPU")
    peaks_for(info["device_kind"])
    paths = (info["attn_impl"], info["decode_attn_impl"], info["paged_kernel"])
    if paths != ("pallas", "pallas", "dma"):
        raise BenchError(f"engine reports attention paths {paths!r}, "
                         f"not the compiled ('pallas', 'pallas', 'dma')")
    if int(info["devices"]) != chips:
        raise BenchError(f"engine holds {info['devices']} device(s); the "
                         f"cell asks for {chips}")


def _quantile_lengths(lengths: np.ndarray, qs: List[float]) -> List[int]:
    xs = np.sort(lengths)
    return [int(xs[min(len(xs) - 1, int(q * (len(xs) - 1)))]) for q in qs]


def _extra_requests(source: RequestSource, seed: int, salt: int,
                    prompt_lengths: List[int], out_tokens: int,
                    **body: Any) -> List[Request]:
    """Requests outside the window (warm set, correctness sample): lengths
    from the mix's own block, token ids from the seed and a salt."""
    rng = np.random.default_rng([int(seed), salt])
    out = []
    for i, n in enumerate(prompt_lengths):
        prompt = rng.integers(0, source.vocab, int(n)).tolist()
        out.append(Request(-1 - i, prompt, out_tokens, completion_body(
            MODEL_NAME, prompt, out_tokens, **body)))
    return out


def _serve_ok(base: str, requests: List[Request], what: str) -> None:
    bad = [r for r in asyncio.run(runner.serve_samples(base, requests))
           if not r.ok()]
    if bad:
        raise BenchError(f"{what} failed: {bad[0]}")


def _warm_burst(base: str, source: RequestSource, seed: int, salt: int,
                n: int, out: int) -> int:
    """n requests at once, prompt lengths spread over the mix's range."""
    _serve_ok(base, _extra_requests(
        source, seed, salt,
        _quantile_lengths(source.prompt_lengths,
                          [i / max(1, n - 1) for i in range(n)]), out),
        "warm burst")
    return n


def _cache_entries() -> Optional[set]:
    """Names in the persistent compile cache the server writes to (the
    machine's ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
    None where there is none (a CPU rehearsal keeps no cache)."""
    d = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
         or os.path.join(ROOT, ".jax_cache"))
    try:
        return set(os.listdir(d))
    except OSError:
        return None


def _warm_set(base: str, source: RequestSource, seed: int,
              engine: Dict[str, Any]) -> Tuple[int, int]:
    """A short warm set: the longest, the shortest and the median prompt one
    after another (every chunk and context bucket the mix can touch is
    between them, and the engine's own warm-up compiled each), then a burst
    that fills several lanes at once. The engine also compiles small helper
    programs lazily, by the number of sequences a step admits or retires.
    With a warm cache they load in milliseconds; in a cold checkout they
    compile, and a window that meets them reads a worse tail (chip, PR 23:
    ttft_p90 4.1 s against 1.2 s). So while a round still adds entries to the
    compile cache, another burst of another size follows, three at most: a
    warm start makes one round and pays nothing more.
    -> (requests sent, rounds)."""
    steps = int(engine.get("decode_steps", 8))
    out = min(int(source.output_lengths.max()), 2 * steps + 1)
    lanes = int(engine.get("max_batch", 8))
    seen = _cache_entries()
    lo, mid, hi = _quantile_lengths(source.prompt_lengths, [0.0, 0.5, 1.0])
    for rq in _extra_requests(source, seed, 0x3a1, [hi, lo, mid], out):
        _serve_ok(base, [rq], "warm request")
    sent = 3 + _warm_burst(base, source, seed, 0x3a2, min(8, lanes), out)
    rounds = 1
    for n in (5, 3, 12):
        now = _cache_entries()
        if seen is None or now is None or not (now - seen):
            break
        seen = now
        sent += _warm_burst(base, source, seed, 0x3b0 + n, min(n, lanes), out)
        rounds += 1
    return sent, rounds


def _run_child(module: str, args: List[str], env: Dict[str, str], log: str,
               timeout: float) -> None:
    cmd = [sys.executable, "-m", module, *args]
    with open(log, "w") as lf:
        p = subprocess.Popen(cmd, cwd=ROOT, env=launch.child_env(env),
                             stdout=lf, stderr=subprocess.STDOUT,
                             start_new_session=True)
    try:
        rc = p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise BenchError(f"{module} did not end in {timeout:.0f}s: "
                         f"{launch.tail(log)[-2000:]}") from None
    finally:
        launch.stop(p, grace=5)
    if rc != 0:
        raise BenchError(f"{module} exited {rc}: {launch.tail(log)[-3000:]}")


class Setup:
    """Everything of one cell that is decided before the system starts."""

    def __init__(self, **kw: Any):
        self.__dict__.update(kw)


def prepare(cat: Catalog, workload: str, seed: int, trace: bool,
            rehearsal: bool) -> Setup:
    cell = cat.cell(workload)
    config = cat.data("configs", cell["config"])
    mix = cat.data("traffic", cell["traffic"])
    gen = cat.module("generators", mix["generator"])
    topo = cat.module("topologies", mix.get("topology", "single"))
    reference = config["benchmark"].get("reference")
    if not reference:
        # no default: a model scored by another model's mathematics would
        # read as incorrect, or worse, as correct
        raise BenchError(f"configs/{cell['config']}.json names no "
                         f"benchmark.reference (references/<name>.py)")
    cat.find("references", reference, ".py")
    if not os.path.isdir(os.path.join(ROOT, "dynamo_tpu")):
        raise BenchError(f"the system under test is not in {ROOT}")
    held_to = os.environ.get("JAX_PLATFORMS", "tpu").split(",")[0]
    if held_to != "tpu" and not rehearsal:
        # fail now, not after a CPU start-up of a full-size model; a machine
        # that sets nothing is asked through dyn_engine_info once it is up
        raise BenchError(f"JAX_PLATFORMS holds jax to {held_to!r}: no TPU, "
                         f"no result")
    scratch = os.path.join(SCRATCH, workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    model_dir = os.path.join(scratch, MODEL_NAME)
    write_model_dir(model_dir, config)
    engine_seed = int(seed) % MAX_ENGINE_SEED
    engine = {**config["benchmark"]["engine"], "seed": engine_seed,
              "warmup": True}
    env: Dict[str, str] = {"JAX_PLATFORMS": "cpu"} if rehearsal else {}
    profile_dir = os.path.join(scratch, "profile")
    if trace:
        env["DYN_PROFILE_DIR"] = profile_dir
        env["DYN_PROFILE_STEPS"] = str(int(mix.get("trace_steps", 128)))
    return Setup(cell=cell, config=config, mix=mix, gen=gen, topo=topo,
                 reference=reference, scratch=scratch, model_dir=model_dir,
                 engine=engine, engine_seed=engine_seed, env=env,
                 profile_dir=profile_dir)


def bring_up(su: Setup, rehearsal: bool):
    """Start the system through the cell's topology and ask it what runs.
    -> (handle, engine info); the caller stops the handle."""
    handle = su.topo.start({
        "model_dir": su.model_dir, "model_name": MODEL_NAME,
        "engine": su.engine, "env": su.env, "scratch": su.scratch,
        "chips": su.cell["chips"], "ready_s": 1100.0})
    try:
        info = _engine_info(launch.scrape(handle.base))
        if not rehearsal:
            _check_what_runs(info, su.cell["chips"])
    except BaseException:
        handle.stop()
        raise
    return handle, info


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             started: float, catalog: Optional[Catalog] = None,
             rehearsal: bool = False,
             probe: bool = False) -> Tuple[int, Dict[str, Any]]:
    """-> (exit code, result line). Raises BenchError when there is no
    result to print. ``rehearsal`` (tests only, never the command line) runs
    the children on the CPU, skips the what-runs check and can not pass."""
    cat = catalog or Catalog()
    su = prepare(cat, workload, seed, trace, rehearsal)
    cell, config, mix, gen = su.cell, su.config, su.mix, su.gen
    engine, env, scratch = su.engine, su.env, su.scratch
    engine_seed, profile_dir = su.engine_seed, su.profile_dir
    plan_g = gen.plan(mix["arrivals"], seconds)
    source = RequestSource(mix, config["vocab_size"], MODEL_NAME, seed,
                           plan_g["block"])
    source.prepare(plan_g["blocks"])
    sample_lengths = _quantile_lengths(source.prompt_lengths,
                                       [0.0, 0.3, 0.6, 1.0])
    samples_rq = _extra_requests(
        source, seed, 0x5a9, sample_lengths,
        min(SAMPLE_TOKENS, int(engine["max_context"]) - max(sample_lengths)),
        logprobs=1)

    t_spawn = time.monotonic()
    handle, info = bring_up(su, rehearsal)
    try:
        t_warm = time.monotonic()
        # a traced run has no warm set: the profiler hook takes the FIRST
        # working iterations, and they have to be the window's
        warm_n, warm_rounds = ((0, 0) if trace else
                               _warm_set(handle.base, source, seed, engine))
        before = launch.scrape(handle.base)
        cache_before = _cache_entries()
        setup_s = time.monotonic() - started
        split = {"harness_before_spawn_s": t_spawn - started,
                 "spawn_to_health_s": t_warm - t_spawn,
                 "engine_bucket_programs_s": launch.metric_sum(
                     before, "dyn_compile_seconds_total"),
                 "warm_set_s": time.monotonic() - t_warm,
                 "warm_requests": warm_n, "warm_rounds": warm_rounds}
        window = asyncio.run(runner.drive_window(
            gen, handle.base, source, mix["arrivals"], seconds, seed,
            int(mix.get("lengths_seed", 0)),
            float(mix.get("trace_drain_s" if trace else "drain_s", 30)),
            sample_every=0.5 if trace else None))
        after = launch.scrape(handle.base)
        cache_after = _cache_entries()
        records.keep(os.path.join(scratch, "window_records.json"), window,
                     before, after)
        served = asyncio.run(runner.serve_samples(handle.base, samples_rq))
        last = launch.scrape(handle.base)
    finally:
        handle.stop()

    # ---- (b) the sample against the configuration's float32 reference
    # (references/<name>.py, run by harness/reference.py), chip now free ----
    bad = [r for r in served if not r.ok()]
    if bad:
        raise BenchError(f"correctness sample failed to serve: {bad[0]}")
    job = {"config": {k: v for k, v in config.items() if k != "benchmark"},
           "reference": su.reference,
           "catalog": {"manifest": cat.manifest_path, "roots": cat.roots[:-1]},
           "seed": engine_seed, "probe": bool(probe),
           "samples": [{"prompt": rq.prompt,
                        "served": tokens_of("".join(rs.text))}
                       for rq, rs in zip(samples_rq, served)]}
    job_path = os.path.join(scratch, "reference_in.json")
    ref_path = os.path.join(scratch, "reference_out.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    t_ref = time.monotonic()
    _run_child("benchmarks.harness.reference", [job_path, ref_path], env,
               os.path.join(scratch, "reference.log"),
               float(config["benchmark"].get("reference_timeout_s", 600.0)))
    with open(ref_path) as f:
        ref = json.load(f)
    served_cmp = [{"tokens": s["served"], "logprobs": rs.logprobs}
                  for s, rs in zip(job["samples"], served)]
    rms_tol = float(config["benchmark"].get("reference_tolerance", {}).get(
        "rel_rms", correct.REL_RMS_TOL))
    sample = correct.compare(served_cmp, ref["full"], rms_tol)
    sample["reference_device"] = ref["device"]
    sample["reference_s"] = time.monotonic() - t_ref
    sample["reference_timing"] = ref["timing"]
    if probe:
        for variant in ("dropped_layer", "int8"):
            sample[variant] = {
                k: v for k, v in correct.compare(
                    served_cmp, ref[variant], rms_tol).items()
                if k.startswith("rel_") or k == "ok"}

    # ---- (a), (c) --------------------------------------------------------
    results = window["results"]
    failed = [r for r in results if not r.ok()]
    compiled = (launch.delta(before, after, "dyn_compiled_programs"),
                launch.delta(before, after, "dyn_compile_seconds_total"))
    checks = {"requests_ok": not failed, "sample": sample,
              "compiled_in_window": compiled[0],
              "compile_seconds_in_window": compiled[1],
              # small helper programs are not in those counters; what the
              # compile cache gained in the window is (informative only)
              "cache_entries_added_in_window": (
                  None if cache_before is None or cache_after is None
                  else len(cache_after - cache_before))}
    is_correct = bool(not failed and results and sample["ok"]
                      and compiled == (0.0, 0.0))

    # ---- the trace, reduced by a child that may import jax ---------------
    trace_summary = None
    if trace:
        found = sorted(glob.glob(os.path.join(
            profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise BenchError(f"traced run left no .xplane.pb under "
                             f"{profile_dir}: {launch.tail(handle.log)[-1500:]}")
        summary_path = os.path.join(scratch, "trace_summary.json")
        _run_child("benchmarks.harness.xplane", [found[-1], summary_path],
                   {"JAX_PLATFORMS": "cpu"},
                   os.path.join(scratch, "xplane.log"), 300.0)
        with open(summary_path) as f:
            trace_summary = json.load(f)
        trace_summary["path"] = found[-1]

    run = {"cell": cell, "config": config, "mix": mix, "engine": engine,
           "results": results, "t0": window["t0"], "seconds": float(seconds),
           "setup_s": setup_s, "seed": seed}
    scrapes = {"before": before, "after": after, "last": last,
               "samples": window["samples"]}
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cat.metrics("per_layer", workload):
            value = cat.module("layer_metrics", m["name"]).reduce(
                scrapes, trace_summary, run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cat.metrics("end_to_end", workload):
            value = cat.module("e2e_metrics", m["name"]).reduce(run)
            if value is None:
                raise BenchError(f"end-to-end metric {m['name']} has no "
                                 f"value in {workload}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": info["platform"], "kind": info["device_kind"],
              "count": int(info["devices"]),
              "memory_peak_bytes": int(launch.metric_max(
                  last, "dyn_device_peak_bytes_in_use"))}
    line: Dict[str, Any] = {
        "correct": is_correct and not rehearsal, "attempted": len(results),
        "failed": len(failed), "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        line["breakdown"] = trace_summary["breakdown"]
    line.update({
        "workload": workload, "seed": seed, "seconds": float(seconds),
        "trace": int(trace), "checks": checks, "setup_split": split,
        "sizes": source.sizes(), "window_ended_s": window["ended_s"],
        "in_flight_at_close": window["in_flight_at_close"],
        "first_failure": str(failed[0])[:400] if failed else None})
    if rehearsal:
        line["rehearsal"] = True
        return 2, line
    return 0, line
