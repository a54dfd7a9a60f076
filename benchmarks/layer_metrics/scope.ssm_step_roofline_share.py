"""Layer: kernels. Roofline share of the state-space layers' recurrence in
DECODE: the least time the chip needs for the traced decode dispatches' own
work (``harness/state.py`` ``ssm_least``: a served lane's state once in and
once out per STEP, which is the floor: no kernel holds a layer's states on
the chip from one step to the next; each token's activations, the
recurrence's own operations) over the device seconds of the traced operations
of the decode programs whose ``tf_op`` names the scope ``dynamo.ssm_step``
(``harness/scopes.py``: convolution, state update, read-out and gated norm;
not the two projections), in percent of ``harness/peaks.json``. A kernel at
the peak reads 100; counted a dispatch, as until PR 37, it would have read
25. An operation is under the scope because the program says so, whatever XLA
fuses and however it names the fusion or the kernel. Work of a kind with no
second under the scope RAISES (the scope left the program); a capture without
a device plane, a run off a TPU and a program older than its scopes or its
counters read as no value."""
from benchmarks.harness.scopes import twin_share
from benchmarks.harness.state import ssm_least


def reduce(scrapes, trace, run):
    least = ssm_least(scrapes, trace, run, "decode")
    return twin_share(least, "dynamo.ssm_step", scrapes, trace)
