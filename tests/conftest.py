"""Test fixtures.

All tests run on a virtual 8-device CPU mesh (no TPU needed) and fully
offline. The real-TPU path is exercised by chip_smoke.py; the kernels and one
decode step are also compiled for a described v5e in test_chip_compile.py.
"""

import os
import sys

os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
# Echo engines: no artificial delay in tests.
os.environ.setdefault("DYN_TOKEN_ECHO_DELAY_MS", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual CPU devices for sharding tests, forced before any backend init.
# (A machine with a chip defaults to it, so this must override, not
# setdefault: tests are CPU-only by design.)
from dynamo_tpu.utils.jaxenv import force_cpu  # noqa: E402

assert force_cpu(8), "expected 8 virtual CPU devices for tests"

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio in image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture
def byte_card():
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    return ModelDeploymentCard.synthetic("echo-test")
