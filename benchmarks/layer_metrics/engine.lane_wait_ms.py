"""Layer: engine scheduler. The part of a request's wait for admission during
which a slot was free and every prefill lane was taken (behind other prompts'
chunks and the decode dispatches between them), which is what more lanes would
take away: mean of stage ``lane_wait`` of ``llm_request_stage_seconds``."""
from benchmarks.harness.stages import stage_mean_ms


def reduce(scrapes, trace, run):
    return stage_mean_ms(scrapes, "lane_wait")
