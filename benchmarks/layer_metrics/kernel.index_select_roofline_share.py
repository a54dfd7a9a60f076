"""Layer: kernels. Roofline share of the indexer's scoring and top-k
selection: the least time the chip needs for the index scores that the
traced programs computed (``harness/routed.py`` ``index_select_least``: the
visible index keys read once a query or chunk, the scores' multiply-adds;
the selection itself counted as free) over the device time of the
operations that did it in the trace, in percent of ``harness/peaks.json``.
The operations are those under the program's scope ``dynamo.index_select``,
listed by name and result type in
``kernel.index_select_roofline_share.ops.json`` (``routed.scope_ops``)."""
from benchmarks.harness.routed import (device_peaks, index_select_least,
                                       op_seconds, roofline_share, scope_ops)


def reduce(scrapes, trace, run):
    least = index_select_least(scrapes, trace, run["config"])
    peaks = device_peaks(scrapes)
    if not least or not peaks:
        return None
    bytes_, flops, work = least
    return roofline_share(bytes_, flops,
                          op_seconds(trace, scope_ops(__file__), work), peaks)
