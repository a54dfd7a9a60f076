"""Due time -> first token, 90th percentile over the window's completed
requests (the highest percentile a hundred requests support)."""
from benchmarks.harness.measures import ttft_ms
from benchmarks.harness.stats import percentile


def reduce(run):
    return percentile(ttft_ms(run["results"]), 90)
