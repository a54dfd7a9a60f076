#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dynamo_tpu still serves on the chip.

    python3 chip_smoke.py              # one TPU chip; what the driver runs
    python3 chip_smoke.py --chips 4    # the four-chip phases only (builder)
    python3 chip_smoke.py --rehearse [--chips 4]   # CPU rehearsal, tiny model

Drives the serving main path once, through the entry points a user calls, at
the full width AND depth of ``llama-3.2-1b`` (16 layers, hidden 2048, 32/8
heads, vocab 128256, bf16) with seeded random-init weights: no checkpoint
exists in the repo and the chip machine has no network.

Process model: THIS process never imports jax. A chip belongs to one process
at a time, so every role that needs it is a child that exits before the next
starts: ``--phase device``, ``--phase kernels``, the server
(``python -m dynamo_tpu.cli.run in=http out=jax``), ``--phase agreement``.
The parent only orchestrates, speaks HTTP and checks.

Default phases, in order (any failure stops the run, prints ``"ok": false``
and exits 1):

  device     jax.devices() in a short child: must be a TPU whose device_kind
             is in roofline.PEAKS_BY_DEVICE_KIND; versions, cache dir.
  build      ``make -C native`` from native/*.cpp (nothing pre-built is
             trusted); the one-chip serve path is single-process and uses
             neither the store nor the data plane — the line says so.
  kernels    flash + paged numerics, compiled, at llama-3.2-1b
             head geometry, B up to 32, windowed/softcapped variants, against
             a dense float32 reference (max abs err < KERNEL_TOL).
  serve      the HTTP server at ENGINE_ARGS with warm-up; /v1/models, one
             non-streamed and one streamed completion, 8 concurrent requests
             of mixed length (some longer than prefill_chunk), an over-length
             prompt (typed 400), /metrics. Asserts on usage and finish
             reasons, never on text (byte tokenizer vs 128k-id sampling).
  what_ran   read from the live engine over /metrics: attention paths, how the
             paged kernel runs, programs compiled before/after the requests (no
             growth after warm-up), dyn_mfu / dyn_hbm_gbps > 0 against
             table:* peaks, peak HBM, cold start-up seconds.
  agreement  in one child, EngineCore at library level: first the SAME
             pallas engine again with warm-up (every program must come from
             the persistent compile cache: warm start-up seconds, hits),
             then an ``attn_impl="xla"`` engine — the dense path is the
             repo's plain reference. Same seed, same prompts, greedy.
             Tolerance (bf16 activations, f32 logits):
               * probe logits of one prefill chunk and one decode step agree
                 within LOGIT_TOL * max|reference logit|;
               * served tokens: up to and including the first divergence the
                 chosen tokens' logprobs agree within the same bound, i.e.
                 greedy may only part ways at a tie inside the tolerance
                 (random weights make near-ties common; identical long
                 tails are not demanded).

``--chips 4`` runs ONLY: device (must see 4), build, (a) ``tp4``: a tp=4
engine over four chips against a tp=1 engine on one, agreement as above,
per-device bytes of params and KV, collectives in the compiled decode
program; (b) ``replicas``: store + frontend + KV router + four one-chip
workers through ``python -m dynamo_tpu.cli.serve`` (one process per chip via
the allocator's environment), 16 requests in 4 prefix families.

Last stdout line, and nothing else on it:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
``--rehearse`` runs the same code on the CPU at a tiny size, prints
``"rehearsal": true`` earlier, never prints the passing last line and exits
2 when every rehearsed phase passed (1 on a failure): a rehearsal must not
look like a pass. Logs go to chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
RESULT_MARK = "CHIP_SMOKE_RESULT "

MODEL = "llama-3.2-1b"
# The decode side is a deployment's: 32 lanes, 2048-token contexts, the full
# KV pool that implies. The prefill grid is cut (one lane, 64-token chunks):
# warm-up compiles |lanes| x |chunks| x |contexts| prefill programs at 13-25 s
# each (measured, PERF.md), so the deployment grid (6 x 5 x 5) alone would
# take most of an hour, and the machine's compile cache (192 MiB) holds about
# 25 such programs. 1 x 2 x 5 prefill + 5 decode programs fit both limits.
ENGINE_ARGS = {"preset": MODEL, "max_batch": 32, "max_context": 2048,
               "prefill_chunk": 64, "prefill_lanes": 1, "decode_steps": 8,
               "warmup": True}
TP_ENGINE_ARGS = {"max_batch": 8, "max_context": 512, "prefill_chunk": 128,
                  "prefill_lanes": 2, "decode_steps": 8}
REPLICA_ENGINE_ARGS = {"preset": MODEL, "max_batch": 8, "max_context": 1024,
                       "prefill_chunk": 256, "prefill_lanes": 1,
                       "decode_steps": 8}
REHEARSE_ENGINE_ARGS = {"preset": "tiny-byte", "max_batch": 4,
                        "max_context": 256, "prefill_chunk": 64,
                        "prefill_lanes": 1, "decode_steps": 4, "warmup": True}

KERNEL_TOL = 0.05          # max abs error vs dense f32, unit-normal inputs
LOGIT_TOL = 2.0 ** -3      # of the largest reference logit (32 bf16 ulps)
SERVER_READY_S = 1000.0
CHILD_TIMEOUT_S = 1100.0


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# parent-side helpers (no jax)
# ---------------------------------------------------------------------------

def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def child_env(rehearse: bool, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{devices}")
    return env


def run_child(phase: str, args, devices: int = 1) -> dict:
    """Run ``chip_smoke.py --phase X`` to its end; its result is the last
    stdout line that starts with the marker."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips)]
    if args.rehearse:
        cmd.append("--rehearse")
    log = os.path.join(OUT, f"{phase}.log")
    with open(log, "w") as lf:
        p = subprocess.run(cmd, cwd=HERE, env=child_env(args.rehearse, devices),
                           stdout=subprocess.PIPE, stderr=lf, text=True,
                           timeout=CHILD_TIMEOUT_S)
    result = None
    for line in p.stdout.splitlines():
        if line.startswith(RESULT_MARK):
            result = json.loads(line[len(RESULT_MARK):])
    if p.returncode != 0 or result is None:
        raise SmokeFailure(
            f"phase {phase} child exited {p.returncode}; stdout tail: "
            f"{p.stdout[-1500:]!r}; stderr tail: {tail(log)[-3000:]!r}")
    return result


def http(method: str, url: str, body=None, timeout: float = 600.0):
    """-> (status, parsed json or text). HTTP errors are returned, not
    raised: the over-length request expects one."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw, status = r.read().decode(), r.status
    except urllib.error.HTTPError as e:
        raw, status = e.read().decode(), e.code
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def wait_ready(url: str, proc: subprocess.Popen, log: str,
               timeout: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise SmokeFailure(f"server exited {proc.returncode} during "
                               f"start-up: {tail(log)[-3000:]!r}")
        try:
            status, _ = http("GET", url, timeout=2.0)
            if status == 200:
                return time.monotonic() - t0
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    raise SmokeFailure(f"server not ready after {timeout:.0f}s: "
                       f"{tail(log)[-3000:]!r}")


def stop(proc: subprocess.Popen) -> None:
    """Stop a child and everything it started (it leads its own session)."""
    if proc.poll() is None:
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)     # stragglers of the group
    except ProcessLookupError:
        pass
    proc.wait()


def parse_metrics(text: str) -> list:
    """Prometheus text -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$", line)
        if not m or line.startswith("#"):
            continue
        labels = dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', m.group(2) or ""))
        try:
            out.append((m.group(1), labels, float(m.group(3))))
        except ValueError:
            pass
    return out


def metric_sum(metrics, name: str, **match) -> float:
    return sum(v for n, l, v in metrics if n == name
               and all(l.get(k) == w for k, w in match.items()))


def cache_entries() -> int:
    from dynamo_tpu.utils.jaxenv import compile_cache_dir   # jax-free

    d = compile_cache_dir()
    return len(os.listdir(d)) if os.path.isdir(d) else 0


def prompt_text(n: int, salt: int) -> str:
    """n ASCII bytes = n prompt tokens under the byte tokenizer."""
    words = ["route", "cache", "prefill", "decode", "page", "token", "chip"]
    s = " ".join(words[(salt + i) % len(words)] for i in range(n // 4 + 2))
    return s[:n]


# ---------------------------------------------------------------------------
# phase: build (parent)
# ---------------------------------------------------------------------------

def phase_build(args) -> dict:
    t0 = time.monotonic()
    r = subprocess.run(["make", "-C", os.path.join(HERE, "native")],
                       capture_output=True, text=True, timeout=300)
    check(r.returncode == 0, f"native build failed: {r.stderr[-2000:]!r}")
    built = sorted(os.listdir(os.path.join(HERE, "native", "build")))
    for want in ("dynstore", "libdynamo_dataplane.so", "libdynamo_kv.so"):
        check(want in built, f"native build produced no {want}")
    return {"phase": "build", "ok": True, "built": built,
            "seconds": round(time.monotonic() - t0, 1)}


# ---------------------------------------------------------------------------
# phase: serve + what_ran (parent, over HTTP)
# ---------------------------------------------------------------------------

def completion(base: str, prompt: str, max_tokens: int, **extra):
    return http("POST", base + "/v1/completions", {
        "model": "jax", "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, **extra})


def check_completion(status, body, n_prompt: int, n_out: int, what: str):
    check(status == 200, f"{what}: HTTP {status}: {body!r}")
    u, ch = body["usage"], body["choices"][0]
    check(u["prompt_tokens"] == n_prompt,
          f"{what}: prompt_tokens {u['prompt_tokens']} != {n_prompt}")
    check(u["completion_tokens"] == n_out and ch["finish_reason"] == "length",
          f"{what}: {u['completion_tokens']} tokens, finish "
          f"{ch['finish_reason']!r}; wanted {n_out}, 'length'")


def streamed_completion(base: str, prompt: str, max_tokens: int) -> dict:
    req = urllib.request.Request(
        base + "/v1/completions", method="POST",
        data=json.dumps({"model": "jax", "prompt": prompt, "stream": True,
                         "max_tokens": max_tokens, "temperature": 0,
                         "ignore_eos": True}).encode(),
        headers={"Content-Type": "application/json"})
    chunks, done, last = 0, False, None
    with urllib.request.urlopen(req, timeout=600.0) as r:
        check(r.status == 200, f"streamed completion: HTTP {r.status}")
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            last = json.loads(payload)
            check("error" not in last, f"streamed error chunk: {last!r}")
            chunks += 1
    check(done and last is not None, "stream ended without [DONE]")
    return {"chunks": chunks, "last": last}


def phase_serve(args) -> dict:
    ea = REHEARSE_ENGINE_ARGS if args.rehearse else ENGINE_ARGS
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    log = os.path.join(OUT, "server.log")
    cache_before = cache_entries()
    cmd = [sys.executable, "-m", "dynamo_tpu.cli.run", "in=http", "out=jax",
           "--http-host", "127.0.0.1", "--http-port", str(port),
           "--extra-engine-args", json.dumps(ea)]
    with open(log, "w") as lf:
        proc = subprocess.Popen(cmd, cwd=HERE, env=child_env(args.rehearse),
                                stdout=lf, stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        cold_s = wait_ready(base + "/health", proc, log, SERVER_READY_S)
        status, models = http("GET", base + "/v1/models")
        check(status == 200 and [m["id"] for m in models["data"]] == ["jax"],
              f"/v1/models: {status} {models!r}")
        status, text = http("GET", base + "/metrics")
        check(status == 200, f"/metrics: HTTP {status}")
        before = parse_metrics(text)
        programs_before = metric_sum(before, "dyn_compiled_programs")
        check(programs_before > 0, "warm-up compiled no program")

        chunk, ctx = ea["prefill_chunk"], ea["max_context"]
        # non-streamed, streamed
        p = prompt_text(23, 0)
        check_completion(*completion(base, p, 16, ignore_eos=True),
                         n_prompt=23, n_out=16, what="non-streamed")
        st = streamed_completion(base, prompt_text(41, 1), 12)
        check(st["last"]["choices"][0]["finish_reason"] == "length"
              and st["last"]["usage"]["completion_tokens"] == 12
              and st["last"]["usage"]["prompt_tokens"] == 41,
              f"streamed completion ended with {st['last']!r}")
        # a request that may meet a stray EOS id (257) among sampled ids:
        # it must finish cleanly, early or not, never error
        status, body = completion(base, prompt_text(17, 2), 24)
        check(status == 200 and body["choices"][0]["finish_reason"]
              in ("length", "stop")
              and 1 <= body["usage"]["completion_tokens"] <= 24,
              f"default-stop completion: {status} {body!r}")
        # 8 concurrent, mixed lengths, several longer than prefill_chunk
        # (chunked prefill) and enough tokens for several decode dispatches
        n_out = 5 * ea["decode_steps"]
        lengths = [5, chunk // 3, chunk - 1, chunk + 1, 2 * chunk + 7,
                   min(3 * chunk + 50, ctx - n_out - 70), ctx // 2,
                   ctx - n_out - 20]
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            futs = [ex.submit(completion, base, prompt_text(n, i), n_out,
                              ignore_eos=True)
                    for i, n in enumerate(lengths)]
            for n, f in zip(lengths, futs):
                check_completion(*f.result(), n_prompt=n, n_out=n_out,
                                 what=f"concurrent[{n}]")
        # over-length prompt: the typed error, not a 500
        status, body = completion(base, prompt_text(ctx + 200, 3), 4)
        err = body.get("error", {}) if isinstance(body, dict) else {}
        check(status == 400 and err.get("reason") == "context_exceeded"
              and err.get("stage") == "engine_admission",
              f"over-length prompt: {status} {body!r}")

        time.sleep(1.0)     # goodput gauges refresh at the end of a burst
        status, text = http("GET", base + "/metrics")
        check(status == 200, f"/metrics: HTTP {status}")
        after = parse_metrics(text)
    finally:
        stop(proc)
    emit({"phase": "serve", "ok": True, "engine_args": ea,
          "requests": {"non_streamed": 1, "streamed": 1, "default_stop": 1,
                       "concurrent": len(lengths), "over_length_400": 1},
          "concurrent_prompt_tokens": lengths, "completion_tokens": n_out,
          "stream_chunks": st["chunks"]})

    # ---- what ran: from the live engine, not from the platform string ----
    info = [l for n, l, v in after if n == "dyn_engine_info" and v == 1]
    check(len(info) == 1, f"expected one dyn_engine_info series: {info!r}")
    info = info[0]
    programs_after = metric_sum(after, "dyn_compiled_programs")
    mfu = metric_sum(after, "dyn_mfu")
    hbm_gbps = metric_sum(after, "dyn_hbm_gbps")
    peak = [v for n, l, v in after if n == "dyn_device_peak_bytes_in_use"]
    what = {
        "phase": "what_ran", "ok": True,
        "attn_impl": info["attn_impl"],
        "decode_attn_impl": info["decode_attn_impl"],
        "paged_kernel": info["paged_kernel"],
        "engine_device": {k: info[k] for k in ("platform", "device_kind",
                                               "devices")},
        "peak_source": info["peak_source"],
        "programs_after_warmup": {
            k: metric_sum(before, "dyn_compiled_programs", kind=k)
            for k in ("prefill", "decode", "verify")},
        "programs_after_requests": programs_after,
        "compile_seconds_total": round(
            metric_sum(after, "dyn_compile_seconds_total"), 1),
        "cold_start_seconds": round(cold_s, 1),
        "compile_cache_entries": {"before": cache_before,
                                  "after": cache_entries()},
        "dyn_mfu": mfu, "dyn_hbm_gbps": hbm_gbps,
        "peak_hbm_bytes": max(peak) if peak else None,
        "store": "none: in=http out=jax is one process",
        "data_plane": "none: in=http out=jax is one process",
    }
    check(programs_after == programs_before,
          f"programs compiled after warm-up: {programs_before} -> "
          f"{programs_after}")
    check(mfu > 0 and hbm_gbps > 0, f"dyn_mfu={mfu} dyn_hbm_gbps={hbm_gbps}")
    if not args.rehearse:
        check(what["attn_impl"] == what["decode_attn_impl"] == "pallas"
              and what["paged_kernel"] == "dma",
              f"engine did not run the compiled kernels: {info!r}")
        check(info["platform"] == "tpu"
              and what["peak_source"].startswith("table:"),
              f"engine device/peaks: {info!r}")
        check(peak and max(peak) > 0, "no peak HBM reported")
    return what


# ---------------------------------------------------------------------------
# phase: replicas (parent) — four one-chip workers behind the KV router
# ---------------------------------------------------------------------------

def phase_replicas(args) -> dict:
    import yaml

    ea = ({**REHEARSE_ENGINE_ARGS, "warmup": False, "max_context": 1024}
          if args.rehearse else REPLICA_ENGINE_ARGS)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    cfg = {
        "Frontend": {"port": port, "host": "127.0.0.1",
                     "router_component": "router"},
        "Router": {"worker_component": "backend", "block_size": 64},
        "Worker": {"workers": 4, "engine": "jax", "register_model": True,
                   "model_name": "jax", "extra_engine_args": json.dumps(ea)},
    }
    cfg_path = os.path.join(OUT, "agg_router.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(cfg, f)
    log = os.path.join(OUT, "replicas.log")
    env = child_env(args.rehearse)
    # pinned, not auto: a native store or data plane that fails to build or
    # load is an error here, never a quiet downgrade to the asyncio fixtures
    env["DYNAMO_TPU_STORE"] = "native"
    env["DYNAMO_TPU_DATAPLANE"] = "native"
    # four TPU runtimes starting at once stall every process on the host
    # for ~10 s (measured: frontend and router event loops wedged 10.6 s),
    # which outlasts the default 10 s store lease; the router then vanishes
    # from discovery and the frontend routes without it. A deployment
    # setting, like the addresses: give the leases room.
    env["DYN_LEASE_TTL"] = "60"
    cmd = [sys.executable, "-m", "dynamo_tpu.cli.serve",
           "examples.llm_graphs:AggRouterGraph", "--config", cfg_path,
           "--total-chips", "4",
           "--platform", "cpu" if args.rehearse else "auto"]
    with open(log, "w") as lf:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=lf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        ready_s = wait_ready(base + "/health", proc, log, 300.0)
        t0 = time.monotonic()
        while True:     # discovery: the frontend learns the model from the store
            status, models = http("GET", base + "/v1/models")
            if status == 200 and [m["id"] for m in models["data"]] == ["jax"]:
                break
            check(time.monotonic() - t0 < 120, f"model never listed: {models!r}")
            time.sleep(0.5)
        n_prefix = 3 * 64 if not args.rehearse else 2 * 64
        families = [prompt_text(n_prefix, 10 * g) for g in range(4)]

        def ask(g: int, i: int, n_out: int):
            body = families[g] + f" q{g}{i} " + prompt_text(12, g + i)
            return completion(base, body, n_out, ignore_eos=True)

        statuses = []
        with concurrent.futures.ThreadPoolExecutor(16) as ex:
            # wave 1: one LONG request per family, staggered past a metrics
            # publish: the router breaks ties at random and only load tells
            # idle workers apart, so each family's first request must find
            # the earlier families' workers still busy to land on a new one
            wave = []
            for g in range(4):
                wave.append(ex.submit(ask, g, 0, 700))
                time.sleep(3.0)
            statuses += [f.result()[0] for f in wave]
            # wave 2: the other three of each family, all at once
            wave = [ex.submit(ask, g, i, 8)
                    for g in range(4) for i in (1, 2, 3)]
            results = [f.result() for f in wave]
            statuses += [s for s, _ in results]
        check(statuses == [200] * 16,
              f"replica requests: statuses {statuses}, "
              f"{[b for s, b in results if s != 200][:2]!r}")
        status, dec = http("GET", base + "/v1/router/decisions")
        check(status == 200 and dec["count"] >= 16,
              f"/v1/router/decisions: {status} {str(dec)[:300]!r}")
        per_worker = {}
        for d in dec["decisions"]:
            if d.get("worker_id") is not None:
                w = str(d["worker_id"])
                per_worker[w] = per_worker.get(w, 0) + 1
        overlapped = sum(1 for d in dec["decisions"]
                         if d.get("overlap_blocks", 0) > 0)
        time.sleep(2.5)     # workers publish stage metrics every ~2 s
        _, text = http("GET", base + "/metrics")
        infos = [l for n, l, v in parse_metrics(text)
                 if n == "dyn_engine_info" and v == 1]
    finally:
        stop(proc)
    grants = sorted(set(re.findall(r"granted TPU chips ([\d,]+)", tail(log, 100000))))
    out = {"phase": "replicas", "ok": True, "ready_seconds": round(ready_s, 1),
           "requests_ok": 16, "per_worker_requests": per_worker,
           "decisions_with_prefix_overlap": overlapped,
           "worker_engines": infos, "chip_grants": grants,
           "store": "native dynstore (DYNAMO_TPU_STORE=native)",
           "data_plane": "native (DYNAMO_TPU_DATAPLANE=native)"}
    # (the tiny CPU model finishes a long request before the next arrives,
    # so a rehearsal only shows that routing spreads at all)
    want = 2 if args.rehearse else 4
    check(len(per_worker) >= want, f"router used {len(per_worker)} of 4 "
                                   f"workers: {per_worker}")
    check(len({i["worker"] for i in infos}) == 4,
          f"expected 4 worker engines on /metrics: {infos!r}")
    if not args.rehearse:
        check(all(i["platform"] == "tpu" and i["attn_impl"] == "pallas"
                  for i in infos), f"a worker is not on a TPU: {infos!r}")
        check(len(grants) == 4, f"expected 4 distinct chip grants: {grants}")
    return out


# ---------------------------------------------------------------------------
# child phases (these import jax)
# ---------------------------------------------------------------------------

def child_device(args) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from dynamo_tpu.sdk.serve import host_has_tpu
    from dynamo_tpu.utils import roofline
    from dynamo_tpu.utils.jaxenv import compile_cache_dir

    devs = jax.devices()
    d = devs[0]
    stats = d.memory_stats() or {}
    out = {"phase": "device", "platform": d.platform, "kind": d.device_kind,
           "count": len(devs), "jax": jax.__version__,
           "jaxlib": jaxlib.__version__, "libtpu": md.version("libtpu"),
           "python": sys.version.split()[0],
           "hbm_bytes_limit": stats.get("bytes_limit"),
           "compile_cache_dir": compile_cache_dir(),
           "compile_cache_from_env": bool(
               os.environ.get("JAX_COMPILATION_CACHE_DIR")),
           "compile_cache_entries": cache_entries(),
           "host_has_tpu_device_nodes": host_has_tpu()}
    if not args.rehearse:
        check(d.platform == "tpu", f"no TPU: jax found {d.platform!r} "
                                   f"({d.device_kind!r})")
        # raises for a kind that is not in the table
        peaks = roofline.detect_peaks(d.device_kind, d.platform)
        check(peaks.source.startswith("table:"), f"peaks from {peaks.source}")
        out["peaks"] = {"source": peaks.source, "flops": peaks.flops,
                        "hbm_bytes_per_s": peaks.hbm_bytes}
        check(len(devs) >= args.chips,
              f"--chips {args.chips} but jax sees {len(devs)} device(s)")
        check(host_has_tpu(), "sdk.serve.host_has_tpu() sees no accelerator "
                              "device node on a machine with a TPU")
    out["ok"] = True
    return out


def _dense_ref(q, k, v, q_pos, k_pos, k_valid, scale=None, softcap=None,
               window=None):
    """Dense float32 attention — the kernels' plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    g = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, g, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, g, axis=2).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST     # a TPU's default f32 matmul is bf16
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k,
                   precision=hi) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    mask = ((k_pos[:, None, None, :] <= q_pos[:, None, :, None])
            & k_valid[:, None, None, :])
    if window is not None:
        mask = mask & (k_pos[:, None, None, :]
                       > q_pos[:, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhts,bshd->bthd", p, v, precision=hi)


def child_kernels(args) -> dict:
    """The flash and the paged kernel, compiled, against the dense f32
    reference at llama-3.2-1b head geometry (the numerics queue of the
    former scripts/tpu_smoke.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.attention import flash_attention, paged_attention

    interpret = args.rehearse           # the CPU has only the interpreter
    Hq, Hkv, Dh = (32, 8, 64) if not args.rehearse else (8, 2, 64)
    batches = (1, 4, 8, 32) if not args.rehearse else (1, 4)
    gem = dict(scale=1.0 / np.sqrt(24.0), softcap=50.0, window=96)
    page, P = 64, 8
    cases = []

    def record(name, out, ref):
        err = float(np.abs(np.asarray(out, np.float32)
                           - np.asarray(ref, np.float32)).max())
        cases.append({"case": name, "max_err": round(err, 5)})
        check(np.isfinite(err) and err < KERNEL_TOL,
              f"kernel {name}: max_err {err} >= {KERNEL_TOL}")

    def flash_case(B, T, S, kw, name):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(B + T), 3)
        q = jax.random.normal(kq, (B, T, Hq, Dh), jnp.bfloat16)
        k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.bfloat16)
        v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.bfloat16)
        q_pos = jnp.broadcast_to(jnp.arange(T), (B, T)) + 16
        k_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        k_valid = k_pos < (T + 16)
        record(name,
               flash_attention(q, k, v, q_pos, k_pos, k_valid,
                               interpret=interpret, **kw),
               _dense_ref(q, k, v, q_pos, k_pos, k_valid, **kw))

    def paged_case(B, kw, name):
        n_pages = B * P + 1
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(100 + B), 3)
        q = jax.random.normal(kq, (B, Hq, Dh), jnp.bfloat16)
        k_pages = jax.random.normal(kk, (Hkv, n_pages, page, Dh), jnp.bfloat16)
        v_pages = jax.random.normal(kv, (Hkv, n_pages, page, Dh), jnp.bfloat16)
        pt = (np.arange(P)[None] + np.arange(B)[:, None] * P + 1
              ).astype(np.int32)
        # lengths straddle pages and, for the windowed variant, the window
        lengths = jnp.asarray(
            np.random.RandomState(B).randint(1, P * page, B), jnp.int32)
        out = paged_attention(q, k_pages, v_pages, jnp.asarray(pt), lengths,
                              interpret=interpret, **kw)
        # gather the pages into a dense context and reuse the flash reference
        kg = jnp.transpose(k_pages[:, pt], (1, 2, 3, 0, 4)).reshape(
            B, P * page, Hkv, Dh)
        vg = jnp.transpose(v_pages[:, pt], (1, 2, 3, 0, 4)).reshape(
            B, P * page, Hkv, Dh)
        kp = jnp.broadcast_to(jnp.arange(P * page), (B, P * page))
        ref = _dense_ref(q[:, None], kg, vg, (lengths - 1)[:, None], kp,
                         kp < lengths[:, None], **kw)[:, 0]
        record(name, out, ref)

    for B in batches:
        flash_case(B, 128, 256, {}, f"flash B={B}")
    # the last context bucket (2048 + pad, a multiple of 128 only) and a
    # spec-verify sized chunk the kernel takes as one whole-axis block
    flash_case(2, 128, 2176 if not args.rehearse else 384, {},
               "flash last-bucket")
    flash_case(2, 5, 256, {}, "flash T=5")
    for B in batches[:3:2]:
        flash_case(B, 128, 256, gem, f"flash[window,softcap] B={B}")
    for B in batches[::2] + batches[-1:]:
        paged_case(B, {}, f"paged B={B}")
    for B in batches[:3:2]:
        paged_case(B, gem, f"paged[window,softcap] B={B}")
    return {"phase": "kernels", "ok": True, "compiled": not interpret,
            "geometry": {"Hq": Hq, "Hkv": Hkv, "Dh": Dh, "page": page},
            "tolerance": KERNEL_TOL, "cases": cases,
            "max_err": max(c["max_err"] for c in cases)}


# ---- engine-level helpers shared by agreement and tp4 ----------------------

class CacheEvents:
    """Persistent-compile-cache hits and misses, from jax's own events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def build_core(model, engine_args: dict, attn_impl: str, tp: int = 1,
               devices=None):
    from dynamo_tpu.engine.engine import EngineCore, JaxEngineConfig

    ea = {k: v for k, v in engine_args.items() if k != "preset"}
    t0 = time.monotonic()
    core = EngineCore(JaxEngineConfig(model=model, tp=tp, attn_impl=attn_impl,
                                      **ea), devices)
    return core, time.monotonic() - t0


def serve_greedy(core, prompts, n_out: int):
    """Greedy tokens and their logprobs per prompt, through the engine's own
    scheduler (submit/step): admission, chunked prefill, chained decode."""
    from dynamo_tpu.llm.protocols.common import (BackendInput, FinishReason,
                                                 StopConditions)

    for i, p in enumerate(prompts):
        core.submit(f"p{i}", BackendInput(
            token_ids=list(p),
            stop=StopConditions(max_tokens=n_out, ignore_eos=True)))
    toks = {f"p{i}": [] for i in range(len(prompts))}
    lps = {f"p{i}": [] for i in range(len(prompts))}
    for _ in range(100000):
        if not core.has_work:
            break
        for so in core.step():
            check(so.finish != FinishReason.ERROR,
                  f"engine error on {so.seq_id}: {so.error}")
            toks[so.seq_id].append(int(so.token))
            lps[so.seq_id].append(float(so.token_logprob))
    check(all(len(t) == n_out for t in toks.values()),
          f"engine produced {[len(t) for t in toks.values()]} tokens, "
          f"wanted {n_out} each")
    return ([toks[f"p{i}"] for i in range(len(prompts))],
            [lps[f"p{i}"] for i in range(len(prompts))])


def probe_logits(core):
    """Full-vocabulary logits of one prefill chunk and one decode step on
    this engine's params, mesh and attention paths, against a small fresh
    pool: the numbers the two arms are compared on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama

    m, page = core.cfg.model, core.page_size
    B, T, S = 2, 128, 256
    ppl = S // page
    pool_shape = (m.num_layers, m.num_kv_heads, B * ppl + 1, page, m.head_dim)
    zeros = jax.jit(lambda: jnp.zeros(pool_shape, m.dtype),
                    out_shardings=core.kv_sharding)
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, m.vocab_size, (B, T)).astype(np.int32)
    next_tok = rng.randint(0, m.vocab_size, (B,)).astype(np.int32)
    pt = (np.arange(ppl)[None] + np.arange(B)[:, None] * ppl + 1
          ).astype(np.int32)
    t = np.arange(S)
    slots = (pt[:, t // page] * page + t % page).astype(np.int32)   # [B,S]
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    read_pos = np.broadcast_to(t.astype(np.int32), (B, S))
    read_valid = np.broadcast_to(t < T, (B, S))
    pre_impl = {"pallas": "flash", "xla": "xla"}[core.attn_impl]
    dec_impl, mesh = core.decode_attn_impl, core.mesh

    @jax.jit
    def prefill(params, k, v):
        lg, k, v = llama.forward(
            params, m, tokens, pos, k, v, slots[:, :T], slots, read_pos,
            read_valid, attn_impl=pre_impl, mesh=mesh,
            logits_idx=np.full((B,), T - 1, np.int32))
        return lg[:, 0], k, v

    @jax.jit
    def decode(params, k, v):
        lg, _, _ = llama.forward_decode(
            params, m, next_tok, k, v, pt,
            np.full((B,), T + 1, np.int32), attn_impl=dec_impl, mesh=mesh)
        return lg[:, 0]

    lg0, k, v = prefill(core.params, zeros(), zeros())
    lg1 = decode(core.params, k, v)
    return np.asarray(lg0, np.float32), np.asarray(lg1, np.float32)


def compare_arms(name_a, name_b, logits_a, logits_b, toks_a, toks_b,
                 lps_a, lps_b) -> dict:
    """Arm A is the reference. See the module docstring for the tolerance."""
    import numpy as np

    scale = float(max(np.abs(l).max() for l in logits_a))
    bound = LOGIT_TOL * scale
    out = {"arms": [name_a, name_b], "tolerance": {
        "rule": "LOGIT_TOL * max|reference logit|",
        "LOGIT_TOL": LOGIT_TOL, "max_abs_reference_logit": round(scale, 4),
        "bound": round(bound, 5)}}
    for what, la, lb in zip(("prefill", "decode"), logits_a, logits_b):
        check(np.isfinite(la).all() and np.isfinite(lb).all(),
              f"{what} probe logits not finite")
        err = float(np.abs(la - lb).max())
        out[f"{what}_logits_max_abs_diff"] = round(err, 5)
        out[f"{what}_logits_rel_rms_diff"] = round(float(
            np.sqrt(np.mean((la - lb) ** 2) / np.mean(la ** 2))), 5)
        out[f"{what}_logits_top1_equal"] = bool(
            (la.argmax(-1) == lb.argmax(-1)).all())
        check(err <= bound, f"{what} probe logits of {name_b} differ from "
                            f"{name_a} by {err} > {bound}")
    first_div, worst_lp = [], 0.0
    for ta, tb, pa, pb in zip(toks_a, toks_b, lps_a, lps_b):
        div = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                   len(ta))
        first_div.append(div)
        upto = min(div + 1, len(ta))
        d = float(np.abs(np.asarray(pa[:upto]) - np.asarray(pb[:upto])).max())
        worst_lp = max(worst_lp, d)
    out["tokens_compared"] = len(toks_a[0])
    out["first_divergence"] = first_div
    out["logprob_max_abs_diff_through_first_divergence"] = round(worst_lp, 5)
    check(worst_lp <= bound,
          f"served tokens of {name_b} part from {name_a} at a step whose "
          f"logprobs differ by {worst_lp} > {bound} (not a tie)")
    return out


def program_report(core, S: int) -> dict:
    """memory_analysis() and what the compiler put into the engine's decode
    program for context bucket S (a recompile served by the cache)."""
    import numpy as np

    B, s = core.cfg.max_batch, core.sampling
    zb, ones = np.zeros(B, np.int32), np.ones(B, np.int32)
    flags = np.zeros(B, bool)
    compiled = core._decode_fn(S).jitted.lower(
        core.params, zb, core.k_pool, core.v_pool,
        np.zeros((B, S // core.page_size), np.int32), ones, s.temperature,
        s.top_p, s.top_k, s.key, core.gen_counts, flags, flags, s.freq_pen,
        s.pres_pen).compile()
    txt = compiled.as_text()
    ma = compiled.memory_analysis()
    check(ma is not None, "the backend reports no memory_analysis()")
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {
        "S": S,
        "memory_analysis_bytes": {
            "arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "aliased": ma.alias_size_in_bytes,
            "temporaries": ma.temp_size_in_bytes,
            "code": ma.generated_code_size_in_bytes},
        "tpu_custom_calls": txt.count('custom_call_target="tpu_custom_call"'),
        "pool_sized_copies": len(re.findall(      # per-device pool shape
            r"= \w+\[%d,%d,%d,%d,%d\]\S* copy\("
            % core.k_pool.addressable_shards[0].data.shape, txt)),
        "collectives": {op: n for op in ops
                        if (n := len(re.findall(
                            rf"\b{op}(?:-start)?\(", txt)))},
    }


def per_device_bytes(core) -> list:
    import jax

    held = {}
    for name, tree in (("params", core.params),
                       ("kv", (core.k_pool, core.v_pool))):
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                d = held.setdefault(sh.device.id, {"params": 0, "kv": 0})
                d[name] += sh.data.nbytes
    out = []
    for d in core.mesh.devices.flat:
        stats = d.memory_stats() or {}
        out.append({"device": d.id, **held.get(d.id, {"params": 0, "kv": 0}),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def agreement_prompts(vocab: int, lengths) -> list:
    import numpy as np

    rng = np.random.RandomState(11)
    return [rng.randint(0, min(vocab, 50000), n).tolist() for n in lengths]


def child_agreement(args) -> dict:
    import gc

    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache
    from dynamo_tpu.utils.prometheus import stage_metrics

    cache_dir = init_compile_cache()
    events = CacheEvents()
    ea = REHEARSE_ENGINE_ARGS if args.rehearse else ENGINE_ARGS
    model = llama.preset(ea["preset"])
    chunk = ea["prefill_chunk"]
    prompts = agreement_prompts(model.vocab_size,
                                [12, chunk // 2 + 3, chunk - 1, 2 * chunk + 9])
    n_out = 3 * ea["decode_steps"]

    # arm A: the served configuration again — a warm start from the cache
    entries0 = cache_entries()
    core, warm_s = build_core(model, ea, "pallas")
    sm = stage_metrics()
    programs = {k: sm.compiled_programs.get(k)
                for k in ("prefill", "decode")}
    warm = {"seconds": round(warm_s, 1), "programs": programs,
            "cache": events.take(),
            "cache_entries_added": cache_entries() - entries0}
    check(cache_dir is not None and warm["cache"]["hits"]
          >= sum(programs.values()),
          f"second engine start did not come from the compile cache "
          f"({cache_dir}): {warm}")
    report = program_report(core, core.s_buckets[-1])
    logits_a = probe_logits(core)
    toks_a, lps_a = serve_greedy(core, prompts, n_out)
    del core
    gc.collect()

    # arm B: dense XLA attention, the plain reference
    core, _ = build_core(model, {**ea, "warmup": False}, "xla")
    logits_b = probe_logits(core)
    toks_b, lps_b = serve_greedy(core, prompts, n_out)
    cmp_ = compare_arms("xla", "pallas", logits_b, logits_a, toks_b, toks_a,
                        lps_b, lps_a)
    return {"phase": "agreement", "ok": True, "warm_start": warm,
            "compile_cache_dir": cache_dir, "decode_program": report,
            "prompt_tokens": [len(p) for p in prompts], **cmp_}


def child_tp4(args) -> dict:
    import gc

    import jax

    from dynamo_tpu.models import llama
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    devs = jax.devices()
    check(len(devs) >= 4, f"tp4 needs 4 devices, jax sees {len(devs)}")
    if args.rehearse:
        model = llama.preset("tiny-byte", num_heads=8, num_kv_heads=4)
        ea = {**TP_ENGINE_ARGS, "max_batch": 4, "max_context": 256,
              "prefill_chunk": 64, "decode_steps": 4}
    else:
        model, ea = llama.preset(MODEL), TP_ENGINE_ARGS
    check(llama.pallas_tp_ok(model, 4), "pallas_tp_ok(model, 4) is false")
    impl = "auto"      # pallas under shard_map on the chips, dense on the CPU
    prompts = agreement_prompts(model.vocab_size, [20, ea["prefill_chunk"] - 8])
    n_out = 3 * ea["decode_steps"]

    core, start_s = build_core(model, ea, impl, tp=4, devices=devs[:4])
    held = per_device_bytes(core)
    check(all(h["params"] > 0 and h["kv"] > 0 for h in held),
          f"a device holds nothing: {held}")
    total = sum(h["params"] + h["kv"] for h in held)
    check(held[0]["params"] + held[0]["kv"] < 0.5 * total,
          f"the first device holds most of the state: {held}")
    logits_4 = probe_logits(core)
    toks_4, lps_4 = serve_greedy(core, prompts, n_out)
    report = program_report(core, core.s_buckets[0])
    attn = [core.attn_impl, core.decode_attn_impl, core.paged_kernel]
    if not args.rehearse:
        check(attn == ["pallas", "pallas", "dma"], f"tp=4 engine ran {attn}")
        check(report["tpu_custom_calls"] >= model.num_layers,
              f"no compiled kernel under shard_map: {report}")
    check(report["collectives"], f"tp=4 decode has no collective: {report}")
    del core
    gc.collect()

    core, _ = build_core(model, ea, impl, tp=1, devices=devs[:1])
    logits_1 = probe_logits(core)
    toks_1, lps_1 = serve_greedy(core, prompts, n_out)
    cmp_ = compare_arms("tp1", "tp4", logits_1, logits_4, toks_1, toks_4,
                        lps_1, lps_4)
    return {"phase": "tp4", "ok": True, "engine_args": ea,
            "tp4_start_seconds": round(start_s, 1), "attention": attn,
            "per_device_bytes": held, "decode_program": report, **cmp_}


# ``--phase X`` runs one role alone: the children that may touch jax, and —
# while finding a fault — the parent's own phases, which never do
PHASES = {"device": child_device, "kernels": child_kernels,
          "agreement": child_agreement, "tp4": child_tp4,
          "build": phase_build, "serve": phase_serve,
          "replicas": phase_replicas}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny model; never prints the passing line")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)       # internal: one role alone
    args = ap.parse_args()
    sys.path.insert(0, HERE)

    if args.phase:              # one role alone; a child role may touch jax
        os.makedirs(OUT, exist_ok=True)
        print(RESULT_MARK + json.dumps(PHASES[args.phase](args)), flush=True)
        return 0

    device, done = None, []
    try:
        check(os.path.isdir(os.path.join(HERE, "dynamo_tpu")),
              "chip_smoke.py must sit at the root of the dynamo_tpu checkout")
        os.makedirs(OUT, exist_ok=True)
        if args.rehearse:
            emit({"rehearsal": True, "note": "CPU, tiny model; not a pass"})
            # the CPU rig keeps no persistent cache by default; the rehearsal
            # places one from outside, the way a chip machine may
            os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                OUT, "rehearse_cache")
        n_dev = args.chips if args.rehearse else 1
        phases = [("device", lambda: run_child("device", args, n_dev)),
                  ("build", lambda: phase_build(args))]
        if args.chips == 4:
            phases += [("tp4", lambda: run_child("tp4", args, 4)),
                       ("replicas", lambda: phase_replicas(args))]
        else:
            phases += [("kernels", lambda: run_child("kernels", args)),
                       ("serve", lambda: phase_serve(args)),
                       ("agreement", lambda: run_child("agreement", args))]
        for name, fn in phases:
            t0 = time.monotonic()
            result = fn()
            result["phase_seconds"] = round(time.monotonic() - t0, 1)
            emit(result)
            done.append(name)
            if name == "device":
                device = {k: result[k] for k in ("platform", "kind", "count")}
    except (SmokeFailure, subprocess.TimeoutExpired, OSError, KeyError,
            ValueError) as e:
        emit({"ok": False, "failed_after": done,
              "error": f"{type(e).__name__}: {e}", "device": device})
        return 1
    if args.rehearse:
        emit({"ok": False, "rehearsal": True, "phases_passed": done,
              "device": device})
        return 2
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
