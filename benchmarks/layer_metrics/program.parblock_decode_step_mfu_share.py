"""Layer: bucket programs. ``program.decode_step_mfu_share`` for a
parallel-block model of two cache kinds and a chip's share of the experts
beside shared ones, which ``harness/step.py`` cannot count (``layer_types``
window layers, shared experts, a tied head): the same bound with its own
count (``harness/parblock.py`` ``program_least``: every fixed matrix once a
step — four layers' projections, shared experts and router, the tied head —,
of the held experts only those hit, 2 operations a weight a real token, plus
both kinds' decode attention: a window layer's query reads ``min(length,
sliding_window)`` keys) over the device seconds of the traced ``jit_step``
runs (totals, not medians), by ``roofline_share`` and ``harness/peaks.json``.
Never over 100: the least leaves work out and invents none. A program
without the counters, or another model, reads as no value."""
from benchmarks.harness.parblock import program_share


def reduce(scrapes, trace, run):
    return program_share(scrapes, trace, run, "decode")
