"""The arithmetic from timestamps to metrics.  Pure functions of numbers,
so the tests can feed them synthetic timestamps."""

import math


def percentile(values, q):
    """Linear-interpolated q-th percentile (0..100); None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def whole_step_rate(n_steps, units_per_step, t_first_start, t_last_done):
    """Units per second over whole steps: the clock runs from the start of
    the first step to the end of the last one, so no step is cut by a
    window's edge and the rate is not quantised by one."""
    elapsed = t_last_done - t_first_start
    if n_steps <= 0 or elapsed <= 0:
        return None
    return n_steps * units_per_step / elapsed


def token_gaps_ms(token_times):
    """Gaps between consecutive streamed tokens of one request (the first
    token has no gap: it is the time to first token's business)."""
    return [g * 1e3 for g in intervals(token_times)]


def intervals(times):
    return [b - a for a, b in zip(times, times[1:])]
