"""Layer: bucket programs. ``program.decode_step_mfu_share`` for a
configuration whose layer is two latent-attention sublayers with a routed
branch across them, which ``harness/step.py`` calls ``unknown`` (no
``num_hidden_layers``, low-rank projections): the same bound with its own
count (``harness/scmoe.py`` ``decode_step_least``: every matrix a step
multiplies by read once a step — two sublayers' projections and two dense
feed-forwards a published layer, the router, the head —, of the held experts
only those hit, 2 operations a weight a real token, an identity assignment D
multiply-adds and no bytes, plus the latent attention's least over ``2 x
num_layers`` sublayers) over the device seconds of the traced ``jit_step``
runs (totals, not medians), by ``roofline_share`` and ``harness/peaks.json``.
Never over 100: the least leaves work out and invents none. A program
without the counters, or another model, reads as no value."""
from benchmarks.harness.routed import device_peaks, roofline_share
from benchmarks.harness.scmoe import decode_step_least


def reduce(scrapes, trace, run):
    least = decode_step_least(scrapes, trace, run)
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    return roofline_share(*least, peaks)
