"""Bytes the Jamba serving step must move, from the configuration's shapes
and the window's counters.  The count is of the WORK, not of what the
``mamba_chunk`` kernel happens to move beyond it (``B`` and ``C`` laid out
along 128 lanes, the states of free slots): a later kernel, or none, is held
to the same yardstick."""

from benchmark.reference import jamba as reference

STATE_BYTES = 4         # the scan's state, its inputs and outputs: float32


def mamba_layers(cfg):
    return reference.layer_kinds(cfg).count("mamba")


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_state_bytes_per_slot_layer(cfg):
    return d_inner(cfg) * cfg["mamba_d_state"] * STATE_BYTES


def slot_state_bytes(cfg):
    """What a slot owns that no position addresses, all layers: the scan's
    state and the convolution's tail."""
    return mamba_layers(cfg) * (
        mamba_state_bytes_per_slot_layer(cfg)
        + (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * STATE_BYTES)


def mamba_kernel_bytes(cfg, slot_steps, lanes):
    """Least traffic of the selective scan over a window: the float32 state
    of every SEATED slot read and written once a step and Mamba layer
    (``slot_steps`` = seated slots summed over the steps), plus each live
    lane's ``u`` and ``dt`` rows and ``B`` and ``C`` vectors in and its ``y``
    row out."""
    per_lane = (3 * d_inner(cfg) + 2 * cfg["mamba_d_state"]) * STATE_BYTES
    return mamba_layers(cfg) * (
        2 * slot_steps * mamba_state_bytes_per_slot_layer(cfg)
        + lanes * per_lane)


def mamba_kernel_exps(cfg, lanes):
    """Transcendentals of the scan: one ``exp`` an element of state, live
    lane and Mamba layer.  No published peak bounds them (PERF.md 3)."""
    return mamba_layers(cfg) * lanes * d_inner(cfg) * cfg["mamba_d_state"]
