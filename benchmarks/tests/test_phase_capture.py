"""One real capture on the CPU: a few iterations of the tiny engine under
``DYN_PROFILE_DIR``, read back by the harness's own reader. Every loop phase
is a host span, the spans never overlap (so "the last ``dynamo.*`` span begun
before a gap" names one phase), and the two clock anchors bracket them.
About ten seconds; the times mean nothing, only the structure does."""

import asyncio
import glob
import os
import re

import pytest


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    directory = str(tmp_path_factory.mktemp("profile"))
    os.environ["DYN_PROFILE_DIR"] = directory
    os.environ["DYN_PROFILE_STEPS"] = "1000"    # cut short by shutdown
    try:
        from dynamo_tpu.engine.engine import JaxEngine, JaxEngineConfig
        from dynamo_tpu.llm.protocols.common import (BackendInput,
                                                     StopConditions)
        from dynamo_tpu.models import llama
        from dynamo_tpu.runtime.engine import Context

        engine = JaxEngine(JaxEngineConfig(
            model=llama.preset("tiny-byte"), tp=1, page_size=8, max_batch=4,
            max_context=128, prefill_chunk=32))

        async def one(n, out):
            rq = BackendInput(token_ids=list(range(1, n + 1)),
                              stop=StopConditions(max_tokens=out))
            return [o async for o in engine.generate(rq, Context())]

        async def drive():
            await asyncio.gather(one(70, 12), one(5, 20))
            await asyncio.sleep(0.2)            # a few idle iterations
            await one(40, 6)

        try:
            asyncio.run(drive())
        finally:
            engine.shutdown()                   # stops and writes the capture
    finally:
        del os.environ["DYN_PROFILE_DIR"], os.environ["DYN_PROFILE_STEPS"]
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1
    return found[0]


def test_every_phase_is_a_host_span_and_none_overlap(capture):
    from benchmarks.harness import xplane
    from dynamo_tpu.engine.engine import PHASES

    spans = sorted(xplane.read(capture)["host_spans"])
    seen = {xplane.base_bucket(name) for _, _, name in spans}
    # the paged lane and speculative decoding are off in this engine
    assert seen == {"dynamo." + p for p in PHASES
                    if p not in ("paged", "verify")}
    for (_, end, name), (start, _, nxt) in zip(spans, spans[1:]):
        assert start >= end - 1e-9, (name, nxt)
    covered = sum(e - s for s, e, _ in spans)
    assert covered > 0.99 * (spans[-1][1] - spans[0][0])
    assert any(n.startswith("dynamo.prefill[B") for _, _, n in spans)
    assert any(n.startswith("dynamo.decode[S") for _, _, n in spans)


def test_two_clock_anchors_bracket_the_phases(capture):
    from jax.profiler import ProfileData

    from benchmarks.harness import xplane

    anchors = []
    for plane in ProfileData.from_file(capture).planes:
        for line in plane.lines:
            for e in line.events:
                m = re.fullmatch(r"dyn\.clock\[epoch_ns=(\d+),mono_ns=(\d+)\]",
                                 e.name)
                if m:
                    anchors.append((e.start_ns, int(m[1]), int(m[2])))
    assert len(anchors) == 2
    (t0, epoch0, mono0), (t1, epoch1, mono1) = sorted(anchors)
    # both program clocks advance with the capture's, to within a millisecond
    assert epoch1 - epoch0 == pytest.approx(t1 - t0, abs=1e6)
    assert mono1 - mono0 == pytest.approx(t1 - t0, abs=1e6)
    spans = xplane.read(capture)["host_spans"]
    # the stop anchor comes when the loop has ended; the start anchor
    # inside its first working iteration
    assert max(e for _, e, _ in spans) * 1e9 <= t1 + 1e6
