"""Share of the traced window in which the device ran nothing because no
slot was active: the loop waited for a request (``gen.loop.nowork``)."""
from benchmark import host_spans


def read(obs):
    return host_spans.idle_share_under(obs, ["gen.loop.nowork"])
