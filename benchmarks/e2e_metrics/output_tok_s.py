"""Output tokens that reached the client inside the window, of requests
that completed correctly, over the window's length."""
from benchmarks.harness.measures import tokens_in_window


def reduce(run):
    t0 = run["t0"]
    return tokens_in_window(run["results"], t0, t0 + run["seconds"]) \
        / run["seconds"]
