"""Layer: kernels. Roofline share of the window layers' attention of a
per-kind model: the least time the chip needs for the keys the traced
dispatches had to read and the (query, key) pairs they had to multiply
(``harness/kinds.py`` ``attn_least``) over the device time of the
operations under the program's scope ``dynamo.attn_window`` in the trace
(the Pallas kernel and the transposes around it), in percent of
``harness/peaks.json``. The operations are listed by name and result type
in ``kernel.attn_window_roofline_share.ops.json``
(``benchmarks/tests/scope_ops_kinds.py``)."""
from benchmarks.harness.kinds import attn_least, scope_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], window=True)
    return scope_share(__file__, least, scrapes, trace)
