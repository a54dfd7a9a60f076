"""Layer: kernels. The latent decode kernel's share of its roofline, by
INSTANCE: the least the traced decode dispatches' attention needs
(``harness/latent.py`` ``attn_least``: a row read is 1,152 B a layer, a
(query, key) pair the absorbed form's 128 x (576 + 512) multiply-adds) over
the device seconds of the traced operations whose ``tf_op`` names the scope
``dynamo.attn`` in the DECODE programs (``harness/scopes.py``), in percent
of ``harness/peaks.json``. At 242 operations a byte the kernel sits at the
v5e's ridge: the bound is whichever of the two is larger. Work with no
second under the scope RAISES; a program without the counters, or older
than its scopes, reads as no value."""
from benchmarks.harness.latent import attn_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = attn_least(scrapes, trace, run["config"], "decode")
    return twin_share(least, "dynamo.attn", scrapes, trace)
