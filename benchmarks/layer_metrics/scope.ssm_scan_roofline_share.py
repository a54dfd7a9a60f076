"""Layer: kernels. Roofline share of the state-space layers' recurrence in
PREFILL: the least time the chip needs for the traced chunks' own work
(``harness/state.py`` ``ssm_least``: a served row's state once in and once
out per chunk, each token's activations, the recurrence's own operations,
whatever form computes them) over the device seconds of the traced operations
of the prefill programs whose ``tf_op`` names the scope ``dynamo.ssm_scan``
(``harness/scopes.py``: a chunk's convolution, recurrence and gated norm; not
the two projections), in percent of ``harness/peaks.json``. An operation is
under the scope because the program says so, whatever XLA fuses and however
it names the fusion or the kernel. Work of a kind with no second under the
scope RAISES (the scope left the program); a capture without a device plane,
a run off a TPU and a program older than its scopes or its counters read as
no value."""
from benchmarks.harness.scopes import twin_share
from benchmarks.harness.state import ssm_least


def reduce(scrapes, trace, run):
    least = ssm_least(scrapes, trace, run, "prefill")
    return twin_share(least, "dynamo.ssm_scan", scrapes, trace)
