"""Layer: kernels. Roofline share of the routed experts' feed-forward: the
least time the chip needs for what the traced programs' routing asked for
(``harness/routed.py`` ``moe_least``: the three matrices of every expert HIT
read once a layer and step, the rows' multiply-adds; the router left out)
over the device seconds of the traced operations of the decode and prefill
programs whose ``tf_op`` names the scope ``dynamo.moe_ffn``
(``harness/scopes.py``; ``lax.ragged_dot``'s own custom calls, which XLA
names itself, go to it: ``EXPANDED``), in percent of ``harness/peaks.json``.
An operation is under the scope because the program says so, whatever XLA
fuses and however it names the fusion or the kernel. Work of a kind with no
second under the scope RAISES (the scope left the program); a capture without
a device plane, a run off a TPU and a program older than its scopes or its
counters read as no value."""
from benchmarks.harness.routed import moe_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = moe_least(scrapes, trace, run["config"])
    return twin_share(least, "dynamo.moe_ffn", scrapes, trace)
