"""Layer: kernels. The feed-forward branch of a parallel block in the traced
DECODE dispatches, by INSTANCE: router, held experts hit and the shared
experts (``harness/parblock.py`` ``ffn_least``: the router's matrix and the
four shared experts' twelve read once a layer and step, the three matrices
of every held expert hit, 2 operations a router or shared weight a real
token, ``2 x 3 x D x F`` a held assignment) over the device seconds under
the scopes ``dynamo.moe_ffn`` (router and routed experts) and ``dynamo.ffn``
(the shared experts and the block's one residual add) together, in percent
of ``harness/peaks.json``. Work with no second under a scope RAISES; a
program without the counters, or another model, reads as no value."""
from benchmarks.harness.parblock import ffn_share


def reduce(scrapes, trace, run):
    return ffn_share(scrapes, trace, run)
