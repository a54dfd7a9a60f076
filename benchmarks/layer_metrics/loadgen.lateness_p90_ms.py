"""Layer: load generator (benchmark). Send time - due time, 90th percentile:
how late the generator itself ran. A starved generator must not be read as a
fast server."""
from benchmarks.harness.measures import lateness_ms
from benchmarks.harness.stats import percentile


def reduce(scrapes, trace, run):
    return percentile(lateness_ms(run["results"]), 90)
