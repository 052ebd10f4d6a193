"""Share of the device steps that the serving loop handed over while the
step before was still in flight: of the ``engine.step.dispatch`` phases that
start inside the window, those whose ``in_flight`` stat is 1.  A program
whose phases carry no such stat (it reads every step's tokens before it
hands over the next) gives 0."""
from benchmark import host_spans


def read(obs):
    hs = host_spans.load(obs)
    if not hs:
        return None
    stats = [st for s, _e, st in hs.phases.get("engine.step.dispatch", ())
             if hs.lo <= s < hs.hi]
    if not stats:
        return None
    return 100.0 * sum(st.get("in_flight") == 1 for st in stats) / len(stats)
