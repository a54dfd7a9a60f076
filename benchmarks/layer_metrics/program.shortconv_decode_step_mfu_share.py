"""Layer: bucket programs. ``program.decode_step_mfu_share`` for a
configuration with gated short-convolution layers, which ``harness/step.py``
calls ``unknown`` (layer types ``conv``, ``full_attention``): the same bound
with its own count (``harness/shortconv.py`` ``decode_step_least``: every
matrix a step multiplies by read once a step, of routed experts only those
hit, 2 operations a weight a real token, plus the conv recurrence's least)
over the device seconds of the traced ``jit_step`` runs (totals, not
medians), by ``roofline_share`` and ``harness/peaks.json``. Never over 100:
the least leaves work out and invents none. A program without the counters,
or another model, reads as no value."""
from benchmarks.harness.routed import device_peaks, roofline_share
from benchmarks.harness.shortconv import decode_step_least


def reduce(scrapes, trace, run):
    least = decode_step_least(scrapes, trace, run)
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    return roofline_share(*least, peaks)
