"""Layer: kernels. A chunk's latent attention's share of its roofline, by
INSTANCE: the least the traced prefill dispatches' attention needs
(``harness/latent.py`` ``attn_least``: a lane's rows read once a chunk,
1,152 B a layer; a (query, key) pair the PUBLISHED per-head form's 128 x
(192 + 128) multiply-adds, its expansion left out: the least any form needs;
the absorbed form the program runs does 3.4 times that a pair) over the
device seconds under the scope ``dynamo.attn`` in the PREFILL programs
(context gather and the kernel), in percent
of ``harness/peaks.json``. Work with no second under the scope RAISES; a
program without the counters reads as no value."""
from benchmarks.harness.latent import attn_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], "prefill")
    return twin_share(least, "dynamo.attn", scrapes, trace)
