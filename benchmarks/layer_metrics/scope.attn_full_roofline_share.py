"""Layer: kernels. Roofline share of the FULL layers' attention of a per-kind
model: the least time the chip needs for the keys the traced dispatches had
to read and the (query, key) pairs they had to multiply (``harness/kinds.py``
``attn_least``: a K row and a V row a key as the model defines them, the
pairs' multiply-adds) over the device seconds of the traced operations of the
decode and prefill programs whose ``tf_op`` names the scope
``dynamo.attn_full`` (``harness/scopes.py``: the Pallas kernel, the context
gather, masks and transposes around it; not the projections, rotary or the
cache writes), in percent of ``harness/peaks.json``. An operation is under
the scope because the program says so, whatever XLA fuses and however it
names the fusion or the kernel. Work of a kind with no second under the scope
RAISES (the scope left the program); a capture without a device plane, a run
off a TPU and a program older than its scopes or its counters read as no
value."""
from benchmarks.harness.kinds import attn_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], window=False)
    return twin_share(least, "dynamo.attn_full", scrapes, trace)
