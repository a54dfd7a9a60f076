"""Layer: kernels. Percent of the router's choices that went to IDENTITY
experts (router outputs without weights: gate x input, no expert read, D
multiply-adds) over the whole window: delta
``dyn_moe_zero_assignments_total`` / delta
``dyn_moe_routed_assignments_total`` (``harness/scmoe.py``). With seeded
weights, 256 of the router's 768 outputs identity experts and a selection
bias that favours neither kind it reads about a third. A WITNESS, not a
score: it moves only if the router's law, its width or the seeded bias is
changed, so its direction in the manifest, which wants one of every metric,
says nothing. A program without the counter, or another model, reads as no
value."""
from benchmarks.harness.scmoe import zero_assignment_share


def reduce(scrapes, trace, run):
    return zero_assignment_share(scrapes, run)
