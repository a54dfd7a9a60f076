"""Layer: kernels. Roofline share of the routed experts' feed-forward: the
least time the chip needs for what the traced programs' routing asked for
(``harness/routed.py`` ``moe_least``: the three matrices of every expert HIT
read once a layer and step, the rows' multiply-adds) over the device time
of the operations that did it in the trace, in percent of
``harness/peaks.json``. The operations are those under the program's scope
``dynamo.moe_ffn``, listed by name and result type in
``kernel.moe_ffn_roofline_share.ops.json`` (``routed.scope_ops``), and
``lax.ragged_dot``'s own custom calls by prefix."""
from benchmarks.harness.routed import (device_peaks, moe_least, op_seconds,
                                       roofline_share, scope_ops)


def reduce(scrapes, trace, run):
    least = moe_least(scrapes, trace, run["config"])
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    bytes_, flops, work = least
    return roofline_share(bytes_, flops,
                          op_seconds(trace, scope_ops(__file__), work), peaks)
