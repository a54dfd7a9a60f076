"""Layer: kernels. The FULL layers' attention (no rotary, the whole context)
of a parallel-block model, by INSTANCE, in the traced dispatches of both
kinds: its least (``harness/parblock.py`` ``attn_least``) over the device
seconds under the scope ``dynamo.attn_full``, in percent of
``harness/peaks.json``. Work with no second under the scope RAISES; a
program without the counters, or another model, reads as no value."""
from benchmarks.harness.parblock import attn_share


def reduce(scrapes, trace, run):
    return attn_share(scrapes, trace, run["config"], False)
