"""Layer: engine scheduler. Share of the engine thread's working time spent
blocked on the device: delta (``prefill_fetch`` + ``decode_fetch``) / delta
(all phases - ``idle``) of ``dyn_engine_phase_seconds_total``, in percent.
Higher = the host keeps ahead of the chip; the rest is host work the device
may be waiting for."""
from benchmarks.harness.stages import IDLE, WAITING, phase_seconds


def reduce(scrapes, trace, run):
    phases = phase_seconds(scrapes)
    busy = sum(s for p, s in phases.items() if p != IDLE)
    if busy <= 0:
        return None
    return 100.0 * sum(phases.get(p, 0.0) for p in WAITING) / busy
