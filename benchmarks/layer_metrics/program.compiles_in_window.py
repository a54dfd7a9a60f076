"""Layer: bucket programs. XLA backend compiles between the two scrapes:
delta ``dyn_xla_compiles_total``, which counts every program the process
compiles (the lazily built helper programs too, which
``dyn_compiled_programs`` does not see; a load from the persistent cache is
not a compile). 0 in a warm run; anything else lands in the window's tail."""
from benchmarks.harness.launch import delta

COMPILES = "dyn_xla_compiles_total"


def reduce(scrapes, trace, run):
    if not any(n == COMPILES for n, _, _ in scrapes["after"]):
        return None     # the program does not count them
    return delta(scrapes["before"], scrapes["after"], COMPILES)
