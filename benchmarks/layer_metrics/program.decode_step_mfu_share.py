"""Layer: bucket programs. The whole decode step's share of the chip's
peak: 100 x the least time the chip needs for the steps of the traced decode
dispatches (``harness/step.py`` ``decode_step_least``: every matrix a step
multiplies by read once a step, of routed experts only those hit, 2
operations a weight a real token, plus the least work of the cell's own
scopes; a LOWER bound from the configuration's published keys) over the
device seconds of their ``jit_step`` runs (totals, not medians), by
``roofline_share`` and ``harness/peaks.json``. It bounds what the kernels'
own rooflines cannot: a kernel taken off the path leaves its roofline
silent, and the step's share still says how far the step is from the chip.
Never over 100: the least leaves work out and invents none. Read in the
cells its ``workloads`` list names; a configuration whose weight structure
``harness/step.py`` does not know reads as no value."""
from benchmarks.harness.routed import device_peaks, roofline_share
from benchmarks.harness.step import decode_step_least


def reduce(scrapes, trace, run):
    least = decode_step_least(scrapes, trace, run)
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    return roofline_share(*least, peaks)
