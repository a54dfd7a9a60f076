"""A run fails, and prints no passing line, off the TPU, on a device the peak
table does not know, or without the compiled Pallas kernels."""

import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell
from benchmarks.harness.catalog import ROOT, BenchError

GOOD = {"platform": "tpu", "device_kind": "TPU v5 lite", "devices": "1",
        "attn_impl": "pallas", "decode_attn_impl": "pallas",
        "paged_kernel": "dma"}


def test_the_expected_engine_passes():
    cell._check_what_runs(GOOD, 1)


@pytest.mark.parametrize("change", [
    {"platform": "cpu", "device_kind": "cpu"},
    {"device_kind": "TPU vNext"},
    {"attn_impl": "xla"}, {"decode_attn_impl": "xla"},
    {"paged_kernel": "simple"}, {"paged_kernel": "simple[interpret]"},
    {"devices": "4"},
])
def test_anything_else_is_refused(change):
    with pytest.raises(BenchError):
        cell._check_what_runs({**GOOD, **change}, 1)


def test_one_engine_info_series_or_none():
    with pytest.raises(BenchError):
        cell._engine_info([])
    assert cell._engine_info([("dyn_engine_info", GOOD, 1.0)]) == GOOD


def test_the_engine_seed_fits_what_prngkey_takes():
    assert (2 ** 31 + 12) % cell.MAX_ENGINE_SEED < 2 ** 31


def test_the_command_prints_no_result_without_the_manifests_cell():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_the_command_fails_fast_where_jax_is_held_off_the_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env=env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("reference", [None, "no-such-reference"])
def test_the_command_fails_before_any_server_without_a_reference(
        tmp_path, reference):
    """A checkout of its own (the manifest, ``benchmarks/`` and a program
    directory that holds nothing to start) whose configuration names no
    reference, or one that is not there."""
    import json
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.makedirs(tmp_path / "dynamo_tpu")
    path = tmp_path / "benchmarks" / "configs" / "qwen2-1.5b.json"
    with open(path) as f:
        config = json.load(f)
    assert config["benchmark"].pop("reference") == "llama"
    if reference:
        config["benchmark"]["reference"] = reference
    with open(path, "w") as f:
        json.dump(config, f)
    env = {**os.environ, "JAX_PLATFORMS": "tpu"}   # the parent imports no jax
    r = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env=env, cwd=tmp_path)
    assert r.returncode == 1 and r.stdout.strip() == ""
    assert r.stderr.startswith("benchmark run failed: ")
    assert "reference" in r.stderr
    assert not os.path.exists(tmp_path / ".bench_scratch")   # nothing began


def test_warm_rounds_go_on_only_while_the_compile_cache_grows(monkeypatch):
    import numpy as np

    class Src:
        prompt_lengths = np.array([16, 100, 800])
        output_lengths = np.array([8, 160])
        vocab = 1000

    sent = []
    monkeypatch.setattr(cell, "_serve_ok",
                        lambda base, rqs, what: sent.append(len(rqs)))
    grows = iter([{"a"}, {"a", "b"}, {"a", "b", "c"}, {"a", "b", "c"}])
    monkeypatch.setattr(cell, "_cache_entries", lambda: next(grows))
    n, rounds = cell._warm_set("http://x", Src(), 1, {"max_batch": 32,
                                                      "decode_steps": 4})
    assert rounds == 3 and sent == [1, 1, 1, 8, 5, 3] and n == 19
    sent.clear()
    monkeypatch.setattr(cell, "_cache_entries", lambda: {"a"})   # warm cache
    assert cell._warm_set("http://x", Src(), 1, {"max_batch": 4}) == (7, 1)
    sent.clear()
    monkeypatch.setattr(cell, "_cache_entries", lambda: None)    # no cache
    assert cell._warm_set("http://x", Src(), 1, {"max_batch": 4}) == (7, 1)


def test_every_number_compared_is_printed_beside_its_limit():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    line = {"correct": False, "failed": 2, "checks": {
        "compiled_in_window": 0.0, "compile_seconds_in_window": 0.0,
        "sample": {"rel_rms_diff": 0.31, "rel_max_diff": 1.2,
                   "rel_tie_gap": 3.4,
                   "tolerances": {"rel_rms": 0.275, "rel_max": 3.0,
                                  "rel_tie": 3.0}}}}
    out = run.compared(line).splitlines()
    assert out[-1] == "correct: false"
    assert "compared failed requests: 2 (limit 0)" in out
    assert "compared rel_rms_diff: 0.31 (limit 0.275)" in out
    assert "compared rel_tie_gap: 3.4 (limit 3)" in out
    assert len(out) == 7
