"""Layer: engine scheduler. The engine's own host-clock time per decode token
step: delta sum / delta count of ``llm_decode_step_seconds`` between the two
scrapes. The engine observes one value a dispatch, the dispatch's wall time
from enqueue to harvest divided by its ``decode_steps``, so this is the
dispatch as the scheduler sees it, per token: device time plus what the host
adds around it."""
from benchmarks.harness.launch import delta


def reduce(scrapes, trace, run):
    n = delta(scrapes["before"], scrapes["after"],
              "llm_decode_step_seconds_count")
    if n <= 0:
        return None
    return 1e3 * delta(scrapes["before"], scrapes["after"],
                       "llm_decode_step_seconds_sum") / n
