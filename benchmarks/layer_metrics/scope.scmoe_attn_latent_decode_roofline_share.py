"""Layer: kernels. ``scope.attn_latent_decode_roofline_share`` for a model
that keeps TWO latent rows a token a published layer: the least the traced
decode dispatches' attention needs (``harness/scmoe.py`` ``attn_least``: a
row read is 1,152 B a SUBLAYER, a (query, key) pair the absorbed form's 64 x
(576 + 512) multiply-adds, x ``2 x num_layers`` sublayers; the program's
counters are one sublayer's worth) over the device seconds of the traced
operations whose ``tf_op`` names the scope ``dynamo.attn`` in the DECODE
programs (``harness/scopes.py``), in percent of ``harness/peaks.json``. Work
with no second under the scope RAISES; a program without the counters, or
another model, reads as no value."""
from benchmarks.harness.scmoe import attn_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], "decode")
    return twin_share(least, "dynamo.attn", scrapes, trace)
