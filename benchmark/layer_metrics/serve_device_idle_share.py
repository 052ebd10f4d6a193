"""Share of the traced window in which no operation ran on the device."""


def read(obs):
    t = obs.get("trace")
    if not t or "requests" not in obs:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
