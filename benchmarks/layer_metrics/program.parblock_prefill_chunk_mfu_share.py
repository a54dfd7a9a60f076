"""Layer: bucket programs. The whole PREFILL CHUNK of a parallel-block model
against its least time: the larger of the least byte time (the layers' fixed
matrices read once a chunk, the held experts hit) and the least operation
time (2 operations a weight a token met, ``2 x Hq x 2 Dh`` a (query, visible
key) pair by kind of layer: a window layer's pairs stop at the window)
(``harness/parblock.py`` ``program_least``; the head and the embedding left
out), over the device seconds of the traced ``jit_fn`` runs (totals), in
percent of ``harness/peaks.json``. The cell's chunks do most of its work.
Never over 100. A program without the counters, or another model, reads as
no value."""
from benchmarks.harness.parblock import program_share


def reduce(scrapes, trace, run):
    return program_share(scrapes, trace, run, "prefill")
