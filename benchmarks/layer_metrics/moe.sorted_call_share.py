"""Layer: kernels. Percent of the decode dispatches' calls of a routed layer
that were dispatched SORTED (only the experts the busy rows hit are read),
over the whole window: delta ``dyn_moe_sorted_calls_total{kind="decode"}`` /
delta ``dyn_moe_layer_calls_total{kind="decode"}`` x 100. A decode program
whose form is fixed reads 100 (sorted) or 0 (dense); one that holds both and
chooses each call on the device from the experts its busy rows hit
(``dyn_engine_info{moe_dispatch}`` ``decode:by_hit``) reads how often the
choice fell on sorted: beside ``moe.expert_read_share`` (what a step HAD to
read) it says whether the step read just that. A program without the counter
(a parent commit from before it existed) reads as no value."""
from benchmarks.harness.launch import delta
from benchmarks.harness.shortconv import LAYER_CALLS

SORTED_CALLS = "dyn_moe_sorted_calls_total"


def reduce(scrapes, trace, run):
    b, a = scrapes["before"], scrapes["after"]
    if not any(name == SORTED_CALLS for name, _, _ in a):
        return None
    calls = delta(b, a, LAYER_CALLS, kind="decode")
    if calls <= 0:
        return None
    return 100.0 * delta(b, a, SORTED_CALLS, kind="decode") / calls
