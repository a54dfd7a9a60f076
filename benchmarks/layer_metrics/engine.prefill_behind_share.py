"""Layer: engine scheduler. Share of the prefill dispatches that were
enqueued while an earlier dispatch's result was still unfetched, so that the
host built them while the device ran: delta
``dyn_engine_dispatches_behind_total{kind="prefill"}`` / delta
``dyn_engine_dispatches_total{kind="prefill"}``, in percent. Near 0 = every
chunk waited for the in-flight window to drain; near 100 = only the chunks
of a cold start did. A program without the counter reads as no value."""
from benchmarks.harness.launch import delta
from benchmarks.harness.stages import DISPATCHES

BEHIND = "dyn_engine_dispatches_behind_total"


def reduce(scrapes, trace, run):
    b, a = scrapes["before"], scrapes["after"]
    if not any(name == BEHIND for name, _, _ in a):
        return None
    n = delta(b, a, DISPATCHES, kind="prefill")
    if n <= 0:
        return None
    return 100.0 * delta(b, a, BEHIND, kind="prefill") / n
