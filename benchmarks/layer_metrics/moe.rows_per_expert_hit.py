"""Layer: kernels. Rows an expert's three matrices are read for: delta
``dyn_moe_assignments_total`` (token x expert pairs of real tokens) / delta
``dyn_moe_experts_hit_total`` (experts with at least one row, per layer and
step, counted on the device over every row of the program: padded decode
lanes and padded chunk positions all carry the same token and so add at
most ``num_experts_per_tok`` experts a layer and step, 8 beside ~68 in a
decode step here: this reads a little low, never high). 1 at a batch that
never shares an expert; the chunk's rows x experts per token / experts
where every expert is hit."""
from benchmarks.harness.routed import ASSIGNMENTS, EXPERTS_HIT, window


def reduce(scrapes, trace, run):
    hit = window(scrapes, EXPERTS_HIT)
    if hit <= 0:
        return None
    return window(scrapes, ASSIGNMENTS) / hit
