"""Layer: engine scheduler. Admitted to a slot -> first token on the host:
every chunk of the prompt, the host build of each, and whatever the scheduler
puts between two of them. Mean of stage ``prefill`` of
``llm_request_stage_seconds``."""
from benchmarks.harness.stages import stage_mean_ms


def reduce(scrapes, trace, run):
    return stage_mean_ms(scrapes, "prefill")
