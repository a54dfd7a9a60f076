"""Layer: kernels. Roofline share of the state-space layers' recurrence in
DECODE: the least time the chip needs for the traced decode dispatches'
own work (``harness/state.py`` ``ssm_least``: a served lane's state once in
and once out per STEP, which is the floor: no kernel holds a layer's states
on the chip from one step to the next; each token's activations, the
recurrence's own operations) over the device time of the operations under
the program's scope ``dynamo.ssm_step`` in the trace (convolution, state
update, read-out and gated norm; not the two projections), in percent of
``harness/peaks.json``. A kernel at the peak reads 100; counted a dispatch,
as until PR 37, it would have read 25. The operations are listed by name
and result type in ``kernel.ssm_step_roofline_share.ops.json``
(``benchmarks/tests/scope_ops_state.py``)."""
from benchmarks.harness.kinds import scope_share
from benchmarks.harness.state import ssm_least


def reduce(scrapes, trace, run):
    return scope_share(__file__, ssm_least(scrapes, trace, run, "decode"),
                       scrapes, trace)
