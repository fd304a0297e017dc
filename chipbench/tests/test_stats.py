"""Percentile, due-time and spread arithmetic on hand-made samples."""

import math

import pytest

from chipbench import stats


def test_percentile_nearest_rank():
    v = [10, 20, 30, 40, 50, 60, 70, 80, 90, 100]
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([5], 90) == 5


def test_failures_rank_above_every_measured_value():
    v = [1.0] * 8 + [stats.FAILED] * 2
    assert stats.percentile(v, 50) == 1.0
    assert stats.percentile(v, 80) == 1.0
    assert math.isinf(stats.percentile(v, 90))


def test_ttft_is_timed_from_when_the_request_was_due():
    # due at t=100.0, sent late at 100.2 (not used), first token at 100.5
    assert stats.ttft_ms(100.0, [100.5, 100.6]) == pytest.approx(500.0)
    assert stats.ttft_ms(100.0, []) == stats.FAILED


def test_tpot_is_per_request():
    # 5 tokens in groups: last - first = 0.4 s over 4 gaps
    assert stats.tpot_ms([1.0, 1.0, 1.2, 1.2, 1.4]) == pytest.approx(100.0)
    assert stats.tpot_ms([1.0]) == stats.FAILED


def test_tpot_mean_is_over_all_streams_together():
    # 0.4 s over 4 gaps and 3.0 s over 6 gaps: 3.4 s / 10 gaps, not the
    # mean of 100 and 500; a one-token stream adds no gap
    a, b = [1.0, 1.0, 1.2, 1.2, 1.4], [2.0] + [3.5] * 3 + [5.0] * 3
    assert stats.tpot_mean_ms([a, b, [7.0], []]) == pytest.approx(340.0)
    assert stats.tpot_mean_ms([[7.0], []]) == stats.FAILED


def test_client_tpot_percentile_ranks_failures_last():
    from types import SimpleNamespace as NS

    from chipbench.reducers import client_tpot_percentile as red

    def rec(times, error=None, ok=True):
        return NS(res=NS(token_times=times, error=error), ok=ok, cut=False)

    good = [rec([0.0, 0.1 * k]) for k in range(1, 10)]  # 100..900 ms
    ctx = {"window": good + [rec([], error="http 500", ok=False)]}
    assert red.reduce(ctx, {"p": 50}) == pytest.approx(500.0)
    assert red.reduce(ctx, {"p": 90}) == pytest.approx(900.0)
    assert red.reduce(ctx, {"p": 100}) is None  # reaches into the failed
    assert red.reduce({"window": []}, {"p": 90}) is None


def test_client_token_pace_takes_groups_apart():
    from types import SimpleNamespace as NS

    from chipbench.reducers import client_token_pace_percentile as red

    # first token, then groups of 4 every 1.2 s (tokens 0.3 ms apart),
    # one of them after a 4.8 s stall: 300 ms a token but for 4 of 16
    times, t = [0.0], 0.0
    for gap in (1.2, 1.2, 4.8, 1.2):
        t += gap
        times += [t + 0.0003 * k for k in range(4)]
    ctx = {"window": [NS(res=NS(token_times=times)),
                      NS(res=NS(token_times=[]))]}
    sel = {"p": 50, "merge_ms": 2}
    assert red.reduce(ctx, sel) == pytest.approx(300.0)
    assert red.reduce(ctx, dict(sel, p=100)) == pytest.approx(1200.0)
    assert red.reduce({"window": []}, sel) is None


def test_tokens_between_spreads_a_streams_groups_evenly():
    # 9 tokens: the first at 1.0, then two groups of 4 at 2.0 and 3.0
    t = [1.0] + [2.0] * 4 + [3.0] * 4
    assert stats.tokens_between(t, 0.0, 10.0) == pytest.approx(9.0)
    assert stats.tokens_between(t, 1.5, 2.5) == pytest.approx(4.0)
    assert stats.tokens_between(t, 2.1, 2.9) == pytest.approx(3.2)
    assert stats.tokens_between(t, 0.0, 1.0) == 0.0  # [a, b)
    assert stats.tokens_between(t, 3.0, 4.0) == 0.0
    assert stats.tokens_between([], 0.0, 1.0) == 0.0
    assert stats.tokens_between([1.0], 0.5, 1.5) == 1.0


def test_spread_is_the_contracts():
    import statistics

    v = [100, 101, 102, 103, 104, 110]
    q = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q[2] - q[0]) / 102.5)
