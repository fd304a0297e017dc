"""Metric arithmetic of the benchmark: percentiles with failures ranked
worst, due-time latencies, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics

FAILED = math.inf  # a failed request ranks above every measured value


def percentile(values: list, p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of ``values``; failures are
    ``FAILED`` entries and sort last, so a percentile that reaches into
    them is infinite (the run then reports the request as failed)."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(due_at: float, token_times: list) -> float:
    """Time from when the request was DUE (not sent) to its first token."""
    return (token_times[0] - due_at) * 1e3 if token_times else FAILED


def tpot_ms(token_times: list) -> float:
    """(last token - first token) / (tokens - 1), per request: tokens
    leave the engine in groups, so single gaps are bimodal."""
    if len(token_times) < 2:
        return FAILED
    return (token_times[-1] - token_times[0]) * 1e3 / (len(token_times) - 1)


def tpot_mean_ms(streams: list) -> float:
    """Time per output token over ALL the streams together: the sum of
    (last token - first token) over the sum of (tokens - 1). Every gap
    of every stream weighs the same, so one short stream that sat
    through a long fused step does not set the number, as it sets a
    tail over two dozen requests. A stream with one token adds no gap."""
    gaps = sum(len(t) - 1 for t in streams if t)
    if gaps < 1:
        return FAILED
    return sum(t[-1] - t[0] for t in streams if t) * 1e3 / gaps


def tokens_between(token_times: list, a: float, b: float) -> float:
    """Tokens of one stream that fall to the interval [a, b), with the
    stream's tokens after its first spread evenly from the first to the
    last: the engine hands them over in groups (a decode window of
    several steps at once), so a count of arrivals in a few seconds
    jumps by a whole group with the interval's edge, and the device did
    the work evenly."""
    if not token_times:
        return 0.0
    first, last = token_times[0], token_times[-1]
    n = 1.0 if a <= first < b else 0.0
    if last > first:
        n += (len(token_times) - 1) * max(
            min(b, last) - max(a, first), 0.0) / (last - first)
    return n


def spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``), the contract's rule."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
