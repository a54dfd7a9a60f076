"""Layer: engine scheduler. Handed to the engine -> admitted to a slot, less
the part that was a wait for a prefill lane (``engine.lane_wait_ms``): the
inbox, the drain of the in-flight decode window, a free slot, KV capacity, the
prefix restore. Mean of stage ``queue`` of ``llm_request_stage_seconds``."""
from benchmarks.harness.stages import stage_mean_ms


def reduce(scrapes, trace, run):
    return stage_mean_ms(scrapes, "queue")
