"""Layer: engine scheduler. ``llm_batch_occupancy`` (lanes in use) sampled
every 0.5 s inside the window, mean."""
from benchmarks.harness.launch import metric_sum


def reduce(scrapes, trace, run):
    inside = [metric_sum(series, "llm_batch_occupancy")
              for t, series in scrapes["samples"] if t <= run["seconds"]]
    return sum(inside) / len(inside) if inside else None
