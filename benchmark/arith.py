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


def loss_noise(exact, stated):
    """How far the STATED precision alone moves a mean cross-entropy on
    this batch.  ``exact`` and ``stated`` are the reference's margins of the
    same rows (a row's loss is softplus(-margin)), computed in float32 and
    as the configuration states the program computes.  A row's share of the
    loss error is off_label x d, with off_label = sigmoid(-margin) the slope
    of its loss and d the margin's error; the shares of B rows add like
    B independent errors plus one they have in common (every row sees the
    same rounded weights), so the root of the mean SQUARE of the shares is
    the scale of both: returns sqrt(mean((off_label x d)^2))."""
    total = 0.0
    for m, ms in zip(exact, stated):
        off = 0.5 * (1.0 - math.tanh(0.5 * m))
        total += (off * (ms - m)) ** 2
    return math.sqrt(total / len(exact))


def loss_limit(rc, noise):
    """How far a loss may lie from the reference's as stated: the
    configuration's share of ``noise`` (loss_noise, on this batch) and a
    floor of a few float32 steps of the loss itself."""
    return rc["loss_noise_share"] * noise + rc["loss_floor"]


def worst_leaf_gap(got, want, live=None):
    """(gap, leaf): the widest gap between a leaf's norm as the program has
    it and as the reference has it, against the reference's norm of that
    leaf or of its median leaf, whichever is larger (some leaves are all
    but zero).  ``got`` and ``want`` map leaf names to norms; ``live``
    keeps only those leaves."""
    median = percentile(list(want.values()), 50)
    names = [k for k in want if live is None or k in live]
    gaps = {}
    for k in names:
        gap, scale = abs(got[k] - want[k]), max(want[k], median)
        # nothing moved on either side (a learning rate of nought): no gap
        gaps[k] = gap / scale if scale > 0.0 else (math.inf if gap else 0.0)
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def steps_to_fall(losses, first, share=0.7, span=20):
    """The number of steps after which the mean of the last ``span`` losses
    first lay under ``share`` x ``first``; None if it never did."""
    total = 0.0
    for i, x in enumerate(losses):
        total += x
        if i >= span:
            total -= losses[i - span]
        if i >= span - 1 and total / span < share * first:
            return i + 1
    return None
