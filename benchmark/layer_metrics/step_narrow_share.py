"""Share of the device steps that ran narrower than the engine's whole
``S x K`` lanes: of the ``engine.step.dispatch`` phases that start inside
the window, those whose ``width`` stat (the lanes the step computed) is
under their ``lanes`` stat.  A program whose phases carry no ``width`` (every
step computes every lane) gives 0."""
from benchmark import host_spans


def read(obs):
    hs = host_spans.load(obs)
    if not hs:
        return None
    stats = [st for s, _e, st in hs.phases.get("engine.step.dispatch", ())
             if hs.lo <= s < hs.hi]
    if not stats:
        return None
    return 100.0 * sum("width" in st and st["width"] < st["lanes"]
                       for st in stats) / len(stats)
