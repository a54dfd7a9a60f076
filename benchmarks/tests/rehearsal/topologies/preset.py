"""Topology ``preset``: ``single``, for a model that the program builds from
a preset of its own and not from a published ``config.json``.

The program maps no published key onto routed experts
(``LlamaConfig.from_hf_config`` reads neither ``num_local_experts`` nor
``num_experts_per_tok``), and with a ``config.json`` in the model directory
it would serve the dense model of the same widths. So the directory keeps the
generated tokenizer alone and the engine block's ``preset`` names the model.
A throw-away for the rehearsal: a published configuration is served from its
``config.json`` once the program reads those keys.
"""

import os

from benchmarks.harness.catalog import BenchError, Catalog


def start(plan: dict):
    if "preset" not in plan["engine"]:
        raise BenchError("topology 'preset': the engine block names no preset")
    os.remove(os.path.join(plan["model_dir"], "config.json"))
    return Catalog().module("topologies", "single").start(plan)
