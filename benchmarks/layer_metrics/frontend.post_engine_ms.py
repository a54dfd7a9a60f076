"""Layer: HTTP frontend, pre/post-processing. First token on the host (engine
thread) -> first chunk about to be written (hand-over to the event loop,
detokenising, the response pipeline): mean of stage ``post_engine`` of
``llm_request_stage_seconds`` over the window's requests."""
from benchmarks.harness.stages import stage_mean_ms


def reduce(scrapes, trace, run):
    return stage_mean_ms(scrapes, "post_engine")
