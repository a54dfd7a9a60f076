import asyncio
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks.harness.catalog import BENCH, Catalog
from benchmarks.harness.traffic import RequestSource, draw_lengths

MIX = {"prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                         "min": 16, "max": 832},
       "output_tokens": {"dist": "uniform", "min": 8, "max": 160},
       "lengths_seed": 23}


def _gen(name):
    return Catalog().module("generators", name)


def test_lengths_respect_the_clip_and_the_distribution():
    rng = np.random.default_rng(0)
    x = draw_lengths(MIX["prompt_tokens"], 4000, rng)
    assert x.min() >= 16 and x.max() <= 832
    assert 230 <= np.median(x) <= 285
    u = draw_lengths(MIX["output_tokens"], 4000, rng)
    assert u.min() == 8 and u.max() == 160
    assert list(draw_lengths({"dist": "fixed", "value": 12, "min": 1,
                              "max": 99}, 3, rng)) == [12, 12, 12]


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    def run(seed):
        src = RequestSource(MIX, 1000, "m", seed, block=50)
        src.prepare(1)
        reqs = [src.next() for _ in range(50)]
        return [(len(r.prompt), r.out_tokens) for r in reqs], \
            [r.prompt for r in reqs], src
    a, ids_a, sa = run(1)
    b, ids_b, sb = run(2 ** 31 + 11)
    assert a == b and ids_a != ids_b        # same work, other inputs
    assert sa.sizes() == sb.sizes()
    again, ids_again, _ = run(1)
    assert ids_a == ids_again               # the same seed: the same inputs


def test_request_bodies_are_exact_length_greedy_streams():
    src = RequestSource(MIX, 1000, "m", 5, block=4)
    rq = src.next()
    body = json.loads(rq.body)
    assert body["prompt"] == rq.prompt and body["max_tokens"] == rq.out_tokens
    assert body["ignore_eos"] and body["stream"] and body["temperature"] == 0
    assert all(0 <= t < 1000 for t in rq.prompt)


def test_closed_loop_blocks_repeat_the_same_sizes():
    src = RequestSource(MIX, 1000, "m", 9, block=8)
    one = [len(src.next().prompt) for _ in range(8)]
    two = [len(src.next().prompt) for _ in range(8)]
    assert one == two


def test_open_poisson_arrivals_fill_the_window_and_are_fixed_by_the_mix():
    g = _gen("open_poisson")
    params = {"rate_per_s": 7.3}
    a = g.due_times(params, 20.0, 23)
    assert len(a) == round(7.3 * 20)
    assert a[0] > 0 and a[-1] < 20.0 and np.all(np.diff(a) > 0)
    assert np.array_equal(a, g.due_times(params, 20.0, 23))
    assert not np.allclose(a, g.due_times(params, 20.0, 24))
    gaps = np.diff(np.r_[0.0, a])
    assert 0.7 < gaps.std() / gaps.mean() < 1.3      # exponential: cv ~ 1
    assert g.plan(params, 20.0) == {"block": 146, "blocks": 1}


class _FakeLoad:
    """What a generator sees, with a virtual clock: records (due, sent)."""

    def __init__(self, params, seconds, service_s=0.0):
        self.params, self.seconds = params, seconds
        self.seed, self.fixed_seed = 3, 23
        self.t, self.sent, self.service_s, self.n = 0.0, [], service_s, 0

    def now(self):
        return self.t

    async def sleep_until(self, t):
        self.t = max(self.t, t)

    def take(self):
        self.n += 1
        return self.n

    def send(self, req, due):
        self.sent.append((due, self.t))
        fut = asyncio.get_event_loop().create_future()
        self.t += self.service_s          # closed loop: time passes per send
        fut.set_result(None)
        return fut


def test_open_loop_sends_at_the_due_time_whatever_happens_to_replies():
    g = _gen("open_poisson")
    load = _FakeLoad({"rate_per_s": 5.0}, 10.0)
    asyncio.run(g.run(load))
    assert len(load.sent) == 50
    assert all(sent == pytest.approx(due) for due, sent in load.sent)


def test_closed_loop_stops_starting_requests_when_the_window_closes():
    g = _gen("closed_loop")
    load = _FakeLoad({"clients": 4}, 2.0, service_s=0.1)
    asyncio.run(g.run(load))
    assert load.sent and all(due < 2.0 for due, _ in load.sent)
    assert g.plan({"clients": 4}, 2.0)["block"] == 4
