"""Layer: engine scheduler. Due time -> first token on the client's clock,
90th percentile over the window's completed requests, in the cells where
that tail is no end-to-end metric: in ``qwen2-1.5b.chat`` at 0.8 x knee the
queue re-deals itself from run to run by 3.7-4.8 % of this number (two sets of
four runs, PR 27), more than half of the widest bound there is, so it is read
here, unbounded, beside the stages that make it up."""
from benchmarks.harness.measures import ttft_ms
from benchmarks.harness.stats import percentile


def reduce(scrapes, trace, run):
    return percentile(ttft_ms(run["results"]), 90)
