"""Layer: bucket programs. Device time of one run of a prefill program
(module ``jit_fn``: one lane, one chunk of up to ``prefill_chunk`` tokens
against one context bucket); median over the runs in the trace."""

MODULE = "jit_fn"


def reduce(scrapes, trace, run):
    m = (trace or {}).get("modules", {}).get(MODULE)
    if not m or not m["runs"]:
        return None
    return 1e3 * m["median_s"]
