"""Committed bench records: the gate validates a recorded measurement, it
never re-times."""

import json
import os


def test_long_context_batch_artifact_verdicts():
    """The committed batched-paged-decode artifact proves the ISSUE-19
    acceptance bars: a B>=4 backlog of contexts far beyond the device
    budget decodes at >=3x the serial lane's aggregate tok/s, BOTH paged
    arms token-exact vs the dense forward, and a sliding-window model
    (the lifted per-layer-class exclusion) served paged+batched exactly.
    The gate validates the recorded measurement, it never re-times."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "bench_points",
                           "long_context_batch.json")) as f:
        art = json.load(f)
    assert art["checks"]["all_exact"]
    assert art["checks"]["batch_ok"] and art["batch"] >= 4
    assert art["checks"]["speedup_ok"]
    assert art["decode_tok_s_speedup"] >= 3.0
    assert art["decode_tok_s_speedup"] == round(
        art["batched"]["decode_tok_s"] / art["serial"]["decode_tok_s"], 2)
    # the backlog really exceeded the device budget: contexts are a
    # multiple of what the paged lane may keep resident
    assert art["context_tokens"] >= 2 * art["budget_pages"] * art["page_size"]
    assert art["checks"]["sliding_exact"] and art["sliding"]["exact"]
    assert art["sliding"]["batch"] >= 2 and art["sliding"]["pageins"] > 0
    # kernel provenance: the numbers say which paged backend made them
    # (a record made before PR 47 may name the one-page kernel)
    assert art["paged_kernel"] in ("dma", "dma[interpret]", "none",
                                   "simple", "simple[interpret]")
    assert art["platform"]
