"""Layer: HTTP frontend, pre/post-processing. Request received by the HTTP
handler -> handed to the engine (parsing, chat template, tokenising, the
pipeline down to ``JaxEngine``): mean of stage ``pre_engine`` of
``llm_request_stage_seconds`` over the window's requests."""
from benchmarks.harness.stages import stage_mean_ms


def reduce(scrapes, trace, run):
    return stage_mean_ms(scrapes, "pre_engine")
