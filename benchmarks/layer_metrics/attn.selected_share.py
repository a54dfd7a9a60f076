"""Layer: kernels. Keys a query of a model with an indexer attends to, as a
share of the keys it could see, in percent: delta
``dyn_sparse_attn_selected_tokens_total`` / delta
``dyn_sparse_attn_context_tokens_total`` over the window, prefill and decode
together. 100 where no context is longer than ``topk``; the lower it reads,
the more of the cache the selection leaves out (and the more a kernel that
gathers the selected keys could save over one that reads every page)."""
from benchmarks.harness.routed import CONTEXT, SELECTED, window


def reduce(scrapes, trace, run):
    seen = window(scrapes, CONTEXT)
    if seen <= 0:
        return None
    return 100.0 * window(scrapes, SELECTED) / seen
