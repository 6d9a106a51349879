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


def slot_fill(samples, max_active):
    """Share in [0, 1] of decode slots that produced a token between the
    first and the last of `samples` (`loop.stats()` dicts): the scheduler's
    `decode_tokens` over `steps` x `max_active`. None without such counts
    or without a beat."""
    if not samples or "decode_tokens" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    beats = last["steps"] - first["steps"]
    if beats <= 0:
        return None
    return ((last["decode_tokens"] - first["decode_tokens"])
            / (beats * max_active))


def queue_wait_ms(samples):
    """Mean ms a request waited for a slot, over the requests admitted
    between the first and the last of `samples`: the scheduler's
    `queue_wait_s` over `admitted`. None without such counts or when
    nothing was admitted."""
    if not samples or "queue_wait_s" not in samples[0]:
        return None
    first, last = samples[0], samples[-1]
    admitted = last["admitted"] - first["admitted"]
    if admitted <= 0:
        return None
    return 1e3 * (last["queue_wait_s"] - first["queue_wait_s"]) / admitted


def longest_still_s(samples):
    """(longest stretch, s, over which the scheduler's `steps` stood still;
    longest gap, s, between two samples), from samples stamped with `t`.
    The first tells a stalled serve loop from a slow program, whose beats
    are longer but never stop; the second says whether the sampling thread
    stood still with it, that is the whole process. None without stamps."""
    if len(samples) < 2 or "t" not in samples[0]:
        return None
    still = gap = 0.0
    anchor = samples[0]           # the first sample of the current count
    for prev, s in zip(samples, samples[1:]):
        gap = max(gap, s["t"] - prev["t"])
        if s["steps"] != anchor["steps"]:
            anchor = s
        still = max(still, s["t"] - anchor["t"])
    return still, gap
