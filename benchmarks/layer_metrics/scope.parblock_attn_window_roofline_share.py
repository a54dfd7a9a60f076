"""Layer: kernels. The WINDOW layers' attention of a parallel-block model
whose kinds come from ``layer_types``, by INSTANCE, in the traced dispatches
of both kinds (prefill chunks and decode steps): its least (``harness/
parblock.py`` ``attn_least``: a key read costs its K and V row once, a
(query, visible key) pair ``Hq x 2 Dh`` multiply-adds; a decode query reads
the window's own ``sliding_window`` keys at most, a chunk the window behind
it and itself) over the device seconds under the scope
``dynamo.attn_window`` (context gather and kernel), in percent of
``harness/peaks.json``. Work with no second under the scope RAISES; a
program without the counters, or another model, reads as no value."""
from benchmarks.harness.parblock import attn_share


def reduce(scrapes, trace, run):
    return attn_share(scrapes, trace, run["config"], True)
