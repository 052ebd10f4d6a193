"""Share of the traced window in which the device ran nothing while the
engine was handing it the next step (``engine.step.dispatch``).  With the
three other ``serve_idle_*_share`` and what no phase covers it adds up to
``serve_device_idle_share`` of the same run."""
from benchmark import host_spans


def read(obs):
    return host_spans.idle_share_under(obs, ["engine.step.dispatch"])
