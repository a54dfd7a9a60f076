"""A throw-away end-to-end metric: requests completed correctly."""
from benchmarks.harness.measures import completed


def reduce(run):
    return len(completed(run["results"]))
