"""The least work of the Laguna serving step's two kinds of attention over a
window of the run, from the configuration's shapes and the window's
counters.  The count is of the WORK, not of what a kernel happens to move or
compute (whole tiles where a row's window starts or ends inside one, the
masked lanes of a decoding row): a later kernel is held to the same
yardstick.

Operations: a lane that attends a position spends, for each query head, one
product of ``head_dim`` with its key and one weighting of its value, two
operations each.  Bytes: each seated row reads the K and V of the positions
its lanes attend once a step and layer, whatever its lanes (the engine counts
them: ``read_positions_total``, ``window_read_positions_total``), and each
live lane's queries go in and its results come out once.  The least time is
the larger of the operations over the bf16 peak and the bytes over the HBM
peak."""

from benchmark.reference import laguna as reference

POOL_BYTES = 2          # bfloat16 K and V, pools and rings
LANE_BYTES = 2          # bfloat16 queries in, results out


def layers(cfg, kind):
    """Layers of attention ``kind`` ("full" | "window")."""
    return [a for a, _f in reference.layer_kinds(cfg)].count(kind)


def heads(cfg, kind):
    """Query heads of a layer of ``kind``."""
    return next(h for (a, _f), h in zip(reference.layer_kinds(cfg),
                                        cfg["num_attention_heads_per_layer"])
                if a == kind)


def attn_flops(cfg, kind, attended):
    """``attended``: positions attended, summed over the window's lanes, in
    ONE layer of the kind (``attended_positions_total`` for the full
    layers, ``window_attended_positions_total`` for the window ones)."""
    return layers(cfg, kind) * attended * heads(cfg, kind) \
        * cfg["head_dim"] * 4


def attn_bytes(cfg, kind, read, lanes):
    """``read``: positions the rows read, each once a row, in one layer of
    the kind; ``lanes``: the live lanes of the window."""
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * POOL_BYTES
    per_lane = 2 * heads(cfg, kind) * cfg["head_dim"] * LANE_BYTES
    return layers(cfg, kind) * (read * kv + lanes * per_lane)


def attn_least_seconds(cfg, peaks, kind, attended, read, lanes):
    """The roofline: the larger of operations over the bf16 peak and bytes
    over the HBM peak."""
    return max(attn_flops(cfg, kind, attended) / peaks["bf16_flops"],
               attn_bytes(cfg, kind, read, lanes) / peaks["hbm_bytes_per_s"])

