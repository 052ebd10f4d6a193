"""Share of the window the step loop spent between being handed a batch's
rows and having the feed to dispatch (the DataFeeder's conversion).  It is
hidden while the host still runs ahead of the device."""


def read(obs):
    if "feed_wait" not in obs:
        return None
    return 100.0 * sum(obs["feed_wait"]) / (obs["t_close"] - obs["t_open"])
