"""Layer: KV cache. Of the attention keys the traced dispatches had to read,
the percent that WINDOW layers read: ``attn_window_keys`` x window layers
over that + ``attn_full_keys`` x full layers, of the captured work
(``harness/parblock.py`` ``window_key_share``). The witness that the second
page pool does its work: 75 % if three of four layers read the whole
context, about 55 % at a 10k context with a window of 4,096 honoured. Lower
is better only in that sense: it moves with the traffic's lengths and with
nothing else. A program without the counters, or another model, reads as no
value."""
from benchmarks.harness.parblock import window_key_share


def reduce(scrapes, trace, run):
    return window_key_share(scrapes, trace, run)
