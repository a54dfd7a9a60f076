"""Layer: kernels. Device time of the Pallas custom calls (flash prefill,
paged decode: every ``tpu_custom_call`` operation in the trace) as a share of
device busy time, in percent. A share of busy time, not a roofline share: the
trace gives the kernels' time, but nothing sound gives their per-dispatch
shapes yet (PERF.md, Open questions)."""


def reduce(scrapes, trace, run):
    if not trace or not trace.get("busy_s") or not trace.get("kernel_s"):
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
