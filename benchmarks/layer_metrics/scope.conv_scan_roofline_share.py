"""Layer: kernels. The gated short convolution's recurrence in the traced
prefill CHUNKS, by INSTANCE: its least work (``harness/shortconv.py``
``conv_least``: a chunk's tail once in and once out a served row of the
chunk program, a real token's B, C, z in and y out, 8 operations a channel a
token, x the conv layers) over the device seconds of the traced operations
whose ``tf_op`` names the scope ``dynamo.ssm_scan``
(``harness/scopes.py``), in percent of ``harness/peaks.json``. No list of
operations. Work with no second under the scope RAISES; a program without
the counters, or another model, reads as no value."""
from benchmarks.harness.scopes import twin_share
from benchmarks.harness.shortconv import conv_least


def reduce(scrapes, trace, run):
    least = conv_least(scrapes, trace, run, "prefill")
    return twin_share(least, "dynamo.ssm_scan", scrapes, trace)
