import statistics

import pytest

from benchmarks.harness.client import Result
from benchmarks.harness import measures
from benchmarks.harness.stats import percentile, spread, supports


def test_percentile_interpolates_and_handles_small_samples():
    assert percentile([], 50) is None
    assert percentile([7.0], 90) == 7.0
    xs = list(range(1, 102))            # 1..101
    assert percentile(xs, 50) == 51
    assert percentile(xs, 90) == 91
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([4, 1, 3, 2], 100) == 4


@pytest.mark.parametrize("n,q,ok", [(100, 90, True), (99, 90, False),
                                    (100, 95, False), (200, 95, True),
                                    (1000, 99, True)])
def test_a_tail_needs_ten_samples_beyond_it(n, q, ok):
    assert supports(n, q) is ok


def test_spread_is_the_contracts_quartile_distance():
    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))
    assert spread([5.0]) is None


def _res(due, sent, first, last, chunks, want=None, **kw):
    n = sum(c for _, c in chunks)
    r = Result(0, due, sent=sent, first=first, last=last, chunks=chunks,
               status=200, prompt_tokens=5, completion_tokens=n,
               finish="length", want_prompt=5,
               want_out=n if want is None else want)
    for k, v in kw.items():
        setattr(r, k, v)
    return r


def test_times_count_from_the_due_time_not_the_send_time():
    r = _res(due=10.0, sent=10.3, first=10.5, last=11.5,
             chunks=[(10.5, 1), (11.0, 4), (11.5, 4)])
    assert measures.ttft_ms([r]) == [pytest.approx(500.0)]
    assert measures.lateness_ms([r]) == [pytest.approx(300.0)]
    assert measures.tpot_ms([r]) == [pytest.approx(1000.0 / 8)]


def test_failed_requests_give_no_latency_and_no_tokens():
    good = _res(0.0, 0.0, 1.0, 2.0, [(1.0, 1), (2.0, 1)])
    short = _res(0.0, 0.0, 1.0, 2.0, [(1.0, 1), (2.0, 1)], want=3)
    refused = _res(0.0, 0.0, None, None, [], status=429, error="busy")
    assert good.ok() and not short.ok() and not refused.ok()
    assert len(measures.ttft_ms([good, short, refused])) == 1
    assert measures.tokens_in_window([good, short, refused], 0.0, 5.0) == 2


def test_tokens_are_counted_inside_the_window_only():
    r = _res(0.0, 0.0, 1.0, 9.0, [(1.0, 2), (4.0, 8), (9.0, 8)])
    assert measures.tokens_in_window([r], 0.0, 5.0) == 10
    assert measures.tokens_in_window([r], 4.0, 9.0) == 16
