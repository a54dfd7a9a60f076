"""Layer: kernels. Roofline share of the HELD experts' feed-forward of a
model served as a chip's share of its experts: the least time the chip needs
for what the traced programs' routing sent to experts held here
(``harness/kinds.py`` ``moe_share_least``: the three matrices of every held
expert HIT read once a layer and step, the held assignments' multiply-adds)
over the device seconds of the traced operations of the decode and prefill
programs whose ``tf_op`` names the scope ``dynamo.moe_ffn``
(``harness/scopes.py``: router and held experts), in percent of
``harness/peaks.json``. ``scope.moe_ffn_roofline_share`` cannot read this
cell: its least-work function reads ``num_experts``. An operation is under
the scope because the program says so, whatever XLA fuses and however it
names the fusion or the kernel. Work of a kind with no second under the scope
RAISES (the scope left the program); a capture without a device plane, a run
off a TPU and a program older than its scopes or its counters read as no
value."""
from benchmarks.harness.kinds import moe_share_least
from benchmarks.harness.scopes import twin_share


def reduce(scrapes, trace, run):
    least = moe_share_least(scrapes, trace, run["config"])
    return twin_share(least, "dynamo.moe_ffn", scrapes, trace)
