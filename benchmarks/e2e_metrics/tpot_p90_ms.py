"""Per request (last token - first token) / (tokens - 1); 90th percentile
over the window's completed requests."""
from benchmarks.harness.measures import tpot_ms
from benchmarks.harness.stats import percentile


def reduce(run):
    return percentile(tpot_ms(run["results"]), 90)
