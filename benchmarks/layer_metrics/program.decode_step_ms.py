"""Layer: bucket programs. Device time of one run of the decode program
(module ``jit_step``: ``decode_steps`` token steps in one ``lax.scan``)
divided by ``decode_steps``; median over the runs in the trace."""

MODULE = "jit_step"


def reduce(scrapes, trace, run):
    m = (trace or {}).get("modules", {}).get(MODULE)
    if not m or not m["runs"]:
        return None
    return 1e3 * m["median_s"] / int(run["engine"]["decode_steps"])
