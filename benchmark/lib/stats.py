"""Percentile, rate and lateness arithmetic: the benchmark's own, so that
every PR computes the same number the same way."""
from __future__ import annotations

import math


def percentile(samples, q):
    """q-th percentile (0-100) by linear interpolation between the two
    nearest order statistics (numpy's default), on plain floats. None for
    an empty sample."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n, q):
    """How many of n samples lie above the q-th percentile."""
    return int(math.floor(n * (1.0 - q / 100.0)))


def rate(count, seconds):
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def ttft_ms(t_due, t_first):
    """Time to first token from when the request was DUE (open loop), ms."""
    return (t_first - t_due) * 1e3


def tpot_ms(t_first, t_done, n_out):
    """Mean gap between a request's output tokens, ms. None under 2."""
    if n_out < 2:
        return None
    return (t_done - t_first) / (n_out - 1) * 1e3


def lateness_ms(t_due, t_submit):
    """How late the generator submitted, ms (never negative: a request is
    not submitted before it is due)."""
    return max(0.0, (t_submit - t_due) * 1e3)
