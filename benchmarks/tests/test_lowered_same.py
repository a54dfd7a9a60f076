"""The three configurations the benchmark had at PR 31 still trace and lower
to the programs they lowered to THEN: decode step and prefill chunk of each,
for a described v5e, Mosaic kernels included (``lowered_same.py``; the
fixture holds that commit's digests). A PR that adds a model to the shared
decoder layer, the pool access or the kernels owes the others exactly this:
PR 32 added 50-65 ms a layer a program to every cell's ``setup_s`` and was
refused for it. A change that is MEANT to alter these programs renews the
fixture from its own parent commit and says what the difference costs."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def device():
    import jax
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", True)


with open(os.path.join(HERE, "fixtures", "lowered_pr31.json")) as f:
    FIXTURE = json.load(f)


@pytest.mark.parametrize("config", sorted(FIXTURE["programs"]))
def test_an_existing_configuration_lowers_to_what_it_did(device, config):
    import sys
    sys.path.insert(0, HERE)
    from lowered_same import digests

    assert digests(config, FIXTURE["layers"], device) == \
        FIXTURE["programs"][config]
