"""Layer: engine scheduler. Engine-thread time at work on the host per device
dispatch: delta ``dyn_engine_phase_seconds_total`` over every phase but the
two that wait for the device (``prefill_fetch``, ``decode_fetch``) and
``idle``, / delta ``dyn_engine_dispatches_total`` of all kinds."""
from benchmarks.harness.launch import delta
from benchmarks.harness.stages import (DISPATCHES, IDLE, WAITING,
                                       phase_seconds)


def reduce(scrapes, trace, run):
    n = delta(scrapes["before"], scrapes["after"], DISPATCHES)
    phases = phase_seconds(scrapes)
    if n <= 0 or not phases:
        return None
    host = sum(s for p, s in phases.items() if p not in (*WAITING, IDLE))
    return 1e3 * host / n
