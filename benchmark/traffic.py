"""The one traffic generator.  A traffic mix is a data file of parameters
(``kind`` and numbers); this module turns it and a seed into the inputs of a
run.  The seed chooses token ids and labels — never how much work a run
holds or when it arrives: lengths, batch sizes and arrival times come from
the file."""

import numpy as np


def _rng(seed):
    # --seed may be any whole number up to a little over 2**31; the third
    # word is fixed, and part of what the committed schedule was drawn from
    return np.random.RandomState([int(seed) % (2 ** 32), int(seed) >> 32, 0])


# ------------------------------------------------------------- training

def train_batches(tr, seed, vocab):
    """``distinct_batches`` batches of ``batch`` rows of ``length`` tokens
    under the "token P appears" rule: a positive row carries
    ``positive_token`` at ``positive_count`` random positions, a negative row
    never.  Rows as the program's reader yields them: (int32 ids, label)."""
    rng = _rng(seed)
    b, t = tr["batch"], tr["length"]
    p, k = tr["positive_token"], min(tr["positive_count"], tr["length"])
    out = []
    for _ in range(tr["distinct_batches"]):
        ids = rng.randint(p + 1, vocab, (b, t)).astype(np.int32)
        labels = rng.randint(0, 2, b)
        where = np.argsort(rng.rand(b, t), axis=1)[:, :k]
        rows = np.flatnonzero(labels)
        ids[rows[:, None], where[rows]] = p
        out.append([(ids[i], int(labels[i])) for i in range(b)])
    return out


# -------------------------------------------------------------- serving

def _prompt(rng, n, vocab):
    return rng.randint(1, vocab, n).tolist()


def open_loop(tr, seed, seconds, vocab):
    """Open loop at a fixed rate.  The file fixes ONE schedule of
    ``round(rate * seconds)`` arrivals: sorted uniform times over the window
    (a Poisson process given its count) and the lengths that go with them,
    drawn once from the file's ``schedule_seed``.  Lengths are the file's
    stratified list of [prompt, output] pairs, as often as it fits and the
    rest picked evenly across it.  Every seed runs that schedule; the run's
    seed chooses every prompt's token ids (all distinct), so it moves no
    work and no arrival.  The schedule is a cycle as long as the window: the
    lead-in replays its end before the window opens.  Times are seconds from
    the window's opening; lead-in requests are due before 0 and are not
    measured."""
    fixed, rng = _rng(tr["schedule_seed"]), _rng(seed)
    n = int(round(tr["rate_rps"] * seconds))
    times = np.sort(fixed.uniform(0.0, seconds, n))
    full, rest = divmod(n, len(tr["lengths"]))
    pairs = tr["lengths"] * full + [
        tr["lengths"][(i * len(tr["lengths"])) // rest] for i in range(rest)]
    cycle = [(float(t), pairs[i]) for t, i in zip(times, fixed.permutation(n))]
    lead = min(float(tr["lead_in_s"]), seconds)
    plan = [(t - seconds, p) for t, p in cycle if t >= seconds - lead] + cycle
    return [{"due": t, "max_tokens": int(n_out),
             "prompt": _prompt(rng, n_prompt, vocab), "measured": t >= 0.0}
            for t, (n_prompt, n_out) in plan]
