"""The step against the least time its weights alone allow: (weight bytes as
the server stores them / HBM peak) / mean device time of the step program in
the trace.  The bound is memory, and weights alone: K/V reads, activations
and the logits are left out, so the share is a floor of the true one."""


def read(obs):
    t = obs.get("trace")
    if not t or "weight_bytes" not in obs or not obs["peaks"] \
            or not t["modules"]:
        return None
    count, total = max(t["modules"].values(), key=lambda v: v[1])
    least = obs["weight_bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (total / count)
