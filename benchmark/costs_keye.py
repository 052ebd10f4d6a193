"""The least work of the Keye serving step's three sparse-attention kernels
over a window of the run, from the configuration's shapes and the window's
counters (serving/metrics.py; each already summed over the sparse layers).
The count is of the WORK, not of what a kernel happens to move or compute
(whole tiles of every block of a row where it selected a few positions, the
masked lanes of a decoding row): a later kernel is held to the same
yardstick.

The indexer: a lane that scores a position spends, for each indexer head,
one product of ``indexer_head_dim`` with its key, two operations each; it
reads each row's keys once a step and layer (``read_positions_total``: the
positions each row holds, ``indexer_head_dim`` bfloat16 values each), and
each live lane's queries once.  The attention: a lane that attends a
selected position spends, for each query head, one product of ``head_dim``
with its key and one weighting of its value, two operations each; it reads
the K and V of the positions its row selected once a step and layer
(``sparse_read_positions_total``: the union of the row's lanes' selections),
and each live lane's queries go in and its results come out once.  The
least time is the larger of the operations over the bf16 peak and the bytes
over the HBM peak."""

POOL_BYTES = 2          # bfloat16 K, V and the indexer's keys
LANE_BYTES = 2          # bfloat16 queries in, results out


def layers(cfg):
    return cfg["num_hidden_layers"]


def indexer_flops(cfg, scored):
    """``scored``: ``sparse_scored_positions_total`` moved over the
    window."""
    sa = cfg["sa_config"]
    return scored * sa["indexer_num_heads"] * sa["indexer_head_dim"] * 2


def indexer_bytes(cfg, rows_read, lanes):
    """``rows_read``: positions the rows hold, each once a row, in ONE
    layer (``read_positions_total``); ``lanes``: the live lanes."""
    sa = cfg["sa_config"]
    per_lane = sa["indexer_num_heads"] * sa["indexer_head_dim"] * LANE_BYTES
    return layers(cfg) * (rows_read * sa["indexer_head_dim"] * POOL_BYTES
                          + lanes * per_lane)


def attn_flops(cfg, selected):
    """``selected``: ``sparse_selected_positions_total`` moved."""
    return selected * cfg["num_attention_heads"] * cfg["head_dim"] * 4


def attn_bytes(cfg, read, lanes):
    """``read``: ``sparse_read_positions_total`` moved (all layers);
    ``lanes``: the live lanes."""
    kv = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * POOL_BYTES
    per_lane = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * LANE_BYTES
    return read * kv + layers(cfg) * lanes * per_lane


def least_seconds(peaks, flops, nbytes):
    """The roofline: the larger of operations over the bf16 peak and bytes
    over the HBM peak."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
