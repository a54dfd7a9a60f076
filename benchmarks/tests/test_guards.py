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
