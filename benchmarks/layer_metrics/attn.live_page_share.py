"""Layer: kernels. Pages the paged decode kernel copied, as a share of the
pages of the blocks it was in for them, in percent: delta
``dyn_attn_pages_live_total`` / delta ``dyn_attn_pages_visited_total`` over
the window, full and window layers together (``docs/observability.md``). A
block is 8 pages (``ops/attention.py`` ``PAGES_PER_BLOCK``, a constant); a
page is live if it holds a token the lane's query sees. The kernel copies
only a block's live pages (since PR 41), so this is what is left of a
block's copies: low where most lanes of the decode program are not served (a
lane of length 1 copies 1 page of 8), where contexts end early in their last
block, and under a window narrower than a block. A program without the
counters (a parent commit from before them) reads as no value."""
from benchmarks.harness.launch import delta

LIVE = "dyn_attn_pages_live_total"
VISITED = "dyn_attn_pages_visited_total"


def reduce(scrapes, trace, run):
    visited = delta(scrapes["before"], scrapes["after"], VISITED)
    if visited <= 0:
        return None
    return 100.0 * delta(scrapes["before"], scrapes["after"], LIVE) / visited
