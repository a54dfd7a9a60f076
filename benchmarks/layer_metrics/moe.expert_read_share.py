"""Layer: kernels. Percent of a routed layer's experts that a decode step's
rows were routed to, over the whole window: delta
``dyn_moe_experts_hit_total{kind="decode"}`` / (delta
``dyn_moe_layer_calls_total{kind="decode"}`` x ``num_experts``): how much of
the expert weights a step HAD to read (a sorted dispatch reads just that; a
dense one reads them all, ``dyn_engine_info{moe_dispatch}`` says which). What
``program.shortconv_decode_step_mfu_share`` and
``scope.moe_ffn_roofline_share`` are shares OF. A DENOMINATOR, not a score:
it moves with the occupancy alone (the lanes in decode from step to step),
so its direction in the manifest, which wants one of every metric, says
nothing: never read a change of it as a gain or a loss."""
from benchmarks.harness.shortconv import expert_read_share


def reduce(scrapes, trace, run):
    return expert_read_share(scrapes, run)
