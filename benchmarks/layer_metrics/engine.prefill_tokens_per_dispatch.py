"""Layer: engine scheduler. Prompt tokens computed per prefill dispatch:
delta ``dyn_engine_dispatch_tokens_total{kind="prefill"}`` / delta
``dyn_engine_dispatches_total{kind="prefill"}``. Against ``prefill_chunk`` x
``prefill_lanes`` it says how full the prefill programs run."""
from benchmarks.harness.launch import delta
from benchmarks.harness.stages import DISPATCHES


def reduce(scrapes, trace, run):
    b, a = scrapes["before"], scrapes["after"]
    n = delta(b, a, DISPATCHES, kind="prefill")
    if n <= 0:
        return None
    return delta(b, a, "dyn_engine_dispatch_tokens_total", kind="prefill") / n
