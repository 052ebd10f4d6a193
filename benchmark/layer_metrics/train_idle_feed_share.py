"""Share of the traced window in which the device ran nothing while the
trainer's batch loop was in ``trainer.feed``: what the feed costs the
device once the host no longer runs ahead (0 while it is hidden)."""
from benchmark import host_spans


def read(obs):
    return host_spans.idle_share_under(obs, ["trainer.feed"])
