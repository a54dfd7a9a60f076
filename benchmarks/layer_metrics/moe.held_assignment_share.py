"""Layer: kernels. Token x expert pairs computed on this chip as a share of
those the router chose, in percent: delta ``dyn_moe_assignments_total`` /
delta ``dyn_moe_routed_assignments_total``. A chip that holds 16 of 256
experts under even routing reads 6.25: the share is honest. Far from it, the
routing is uneven over the shards (seeded weights: it is not trained to be
even) or the share is not what the configuration says."""
from benchmarks.harness.kinds import ROUTED
from benchmarks.harness.routed import ASSIGNMENTS, window


def reduce(scrapes, trace, run):
    routed = window(scrapes, ROUTED)
    if routed <= 0:
        return None
    return 100.0 * window(scrapes, ASSIGNMENTS) / routed
