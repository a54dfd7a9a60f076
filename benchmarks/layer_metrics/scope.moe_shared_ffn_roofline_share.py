"""Layer: kernels. The routed layers' feed-forward of a model with a SHARED
expert beside a chip's share of group-limited routed ones, by INSTANCE: the
least the traced dispatches need (``harness/latent.py``
``moe_shared_least``: router and shared expert read once a routed layer a
step or chunk, every held expert hit once, 3 x D x (Fs a token + Fe a held
assignment) multiply-adds) over the device seconds under the scope
``dynamo.moe_ffn``, in percent of ``harness/peaks.json``. Work with no
second under the scope RAISES; a program without the counters, or another
model, reads as no value."""
from benchmarks.harness.latent import moe_shared_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = moe_shared_least(scrapes, trace, run["config"],
                             int(run["engine"]["decode_steps"]))
    return twin_share(least, "dynamo.moe_ffn", scrapes, trace)
