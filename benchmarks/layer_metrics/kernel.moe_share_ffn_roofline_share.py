"""Layer: kernels. Roofline share of the HELD experts' feed-forward of a
model served as a chip's share of its experts: the least time the chip needs
for what the traced programs' routing sent to experts held here
(``harness/kinds.py`` ``moe_share_least``: the three matrices of every held
expert HIT read once a layer and step, the held assignments' multiply-adds)
over the device time of the operations under the program's scope
``dynamo.moe_ffn`` in the trace, in percent of ``harness/peaks.json``. The
operations are listed in ``kernel.moe_share_ffn_roofline_share.ops.json``
(``benchmarks/tests/scope_ops_kinds.py``). ``kernel.moe_ffn_roofline_share``
cannot read this cell: its list names another configuration's operations and
raises where a decode trace holds no ``ragged-dot``, and its least-work
function reads ``num_experts``."""
from benchmarks.harness.kinds import moe_share_least, scope_share


def reduce(scrapes, trace, run):
    least = moe_share_least(scrapes, trace, run["config"])
    return scope_share(__file__, least, scrapes, trace)
