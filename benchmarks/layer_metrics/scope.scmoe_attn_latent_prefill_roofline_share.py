"""Layer: kernels. ``scope.attn_latent_prefill_roofline_share`` for a model
that keeps TWO latent rows a token a published layer: the least the traced
prefill dispatches' attention needs (``harness/scmoe.py`` ``attn_least``: a
lane's rows read once a chunk, 1,152 B a SUBLAYER; a (query, key) pair the
PUBLISHED per-head form's 64 x (192 + 128) multiply-adds, its expansion left
out; x ``2 x num_layers`` sublayers) over the device seconds under the scope
``dynamo.attn`` in the PREFILL programs (context gather and the kernel), in
percent of ``harness/peaks.json``. Work with no second under the scope
RAISES; a program without the counters, or another model, reads as no
value."""
from benchmarks.harness.scmoe import attn_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], "prefill")
    return twin_share(least, "dynamo.attn", scrapes, trace)
