"""Layer: kernels. The routed branch of a layer of two sublayers (router,
held experts hit, identity part) in the traced DECODE dispatches, by
INSTANCE: its least work (``harness/scmoe.py`` ``moe_least``: the router's
matrix once a published layer and step, the three matrices of every held
expert hit, 2 operations a router weight a token, ``2 x 3 x D x Fe`` a held
assignment, ``2 x D`` an identity assignment and no bytes) over the device
seconds of the traced operations whose ``tf_op`` names the scope
``dynamo.moe_ffn`` in the decode programs (``harness/scopes.py``; the dense
feed-forward beside the branch lies under ``dynamo.ffn`` and is not in it),
in percent of ``harness/peaks.json``. Work with no second under the scope
RAISES; a program without the counters, or another model, reads as no
value."""
from benchmarks.harness.scmoe import moe_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = moe_least(scrapes, trace, run["config"],
                      int(run["engine"]["decode_steps"]), kinds=("decode",))
    return twin_share(least, "dynamo.moe_ffn", scrapes, trace)
