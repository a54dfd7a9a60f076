"""Layer: kernels. The gated short convolution's recurrence in the traced
DECODE dispatches, by INSTANCE: its least work (``harness/shortconv.py``
``conv_least``: a served lane-step's tail once in and once out, a real
token's B, C, z in and y out, 8 operations a channel a token, x the conv
layers) over the device seconds of the traced operations whose ``tf_op``
names the scope ``dynamo.ssm_step`` (``harness/scopes.py``), in percent of
``harness/peaks.json``. No list of operations. Expect a LOW reading: the
least is 32 KB a lane-step and layer, and the time under the scope is a
handful of small fusions whose cost is their launch, not their bytes. Work
with no second under the scope RAISES; a program without the counters, or
another model, reads as no value."""
from benchmarks.harness.scopes import twin_share
from benchmarks.harness.shortconv import conv_least


def reduce(scrapes, trace, run):
    least = conv_least(scrapes, trace, run, "decode")
    return twin_share(least, "dynamo.ssm_step", scrapes, trace)
