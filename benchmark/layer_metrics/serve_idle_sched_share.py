"""Share of the traced window in which the device ran nothing while the
generation loop admitted, provisioned blocks and loaded chunks, or emitted
tokens (``gen.loop.admit``, ``.prepare``, ``.emit``)."""
from benchmark import host_spans

SCHED = ["gen.loop.admit", "gen.loop.prepare", "gen.loop.emit"]


def read(obs):
    return host_spans.idle_share_under(obs, SCHED)
