"""Due time -> first token, median over the window's completed requests."""
from benchmarks.harness.measures import ttft_ms
from benchmarks.harness.stats import percentile


def reduce(run):
    return percentile(ttft_ms(run["results"]), 50)
