"""Layer: kernels. Roofline share of the state-space layers' recurrence in
PREFILL: the least time the chip needs for the traced chunks' own work
(``harness/state.py`` ``ssm_least``: a served row's state once in and once
out per chunk, each token's activations, the recurrence's own operations,
whatever form computes them) over the device time of the operations under
the program's scope ``dynamo.ssm_scan`` in the trace (a chunk's
convolution, recurrence and gated norm; not the two projections), in
percent of ``harness/peaks.json``. The operations are listed by name and
result type in ``kernel.ssm_scan_roofline_share.ops.json``
(``benchmarks/tests/scope_ops_state.py``)."""
from benchmarks.harness.kinds import scope_share
from benchmarks.harness.state import ssm_least


def reduce(scrapes, trace, run):
    return scope_share(__file__, ssm_least(scrapes, trace, run, "prefill"),
                       scrapes, trace)
