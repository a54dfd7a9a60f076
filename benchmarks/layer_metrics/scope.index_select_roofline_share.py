"""Layer: kernels. Roofline share of the indexer's scoring and top-k
selection: the least time the chip needs for the index scores that the traced
programs computed (``harness/routed.py`` ``index_select_least``: the visible
index keys read once a query or chunk, the scores' multiply-adds; the
selection itself counted as free; only dispatches whose program scores) over
the device seconds of the traced operations of the decode and prefill
programs whose ``tf_op`` names the scope ``dynamo.index_select``
(``harness/scopes.py``), in percent of ``harness/peaks.json``. An operation
is under the scope because the program says so, whatever XLA fuses and
however it names the fusion or the kernel. Work of a kind with no second
under the scope RAISES (the scope left the program); a capture without a
device plane, a run off a TPU and a program older than its scopes or its
counters read as no value."""
from benchmarks.harness.routed import index_select_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = index_select_least(scrapes, trace, run["config"])
    return twin_share(least, "dynamo.index_select", scrapes, trace)
