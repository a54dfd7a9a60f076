"""Layer: bucket programs. Share of the decode dispatches whose active lanes
were all at temperature 0, so that the decode program skipped the sampler's
top-k window (``lax.top_k`` over the whole vocabulary, once a step): delta
``dyn_engine_greedy_dispatches_total{kind="decode"}`` / delta
``dyn_engine_dispatches_total{kind="decode"}``, in percent. 100 = no step of
the window issued ``TopK``; a cell that sends sampled traffic reads the share
of its dispatches that held none. A program without the counter reads as no
value."""
from benchmarks.harness.launch import delta
from benchmarks.harness.stages import DISPATCHES

GREEDY = "dyn_engine_greedy_dispatches_total"


def reduce(scrapes, trace, run):
    b, a = scrapes["before"], scrapes["after"]
    if not any(name == GREEDY for name, _, _ in a):
        return None
    n = delta(b, a, DISPATCHES, kind="decode")
    if n <= 0:
        return None
    return 100.0 * delta(b, a, GREEDY, kind="decode") / n
