"""The least work of latent attention (MLA, absorbed form) over a window of
the serving step, from the configuration's shapes and the window's counters.
The count is of the WORK, not of what the ``mla_chunk`` kernel happens to
move or compute (its zero padding, a latent tile read once a lane group): a
later kernel is held to the same yardstick.

Operations: a lane that attends a position spends, for each head, one
product with the stored latent (``kv_lora_rank + qk_rope_head_dim`` columns)
and one weighting of it (``kv_lora_rank`` columns), two operations each.
Bytes: each seated row reads the latents of the positions behind it once a
step and layer (every head and lane shares them), and each lane's absorbed
queries go in and its weighted latents come out once."""

POOL_BYTES = 2          # bfloat16 latents
LANE_BYTES = 2          # bfloat16 queries in, results out


def mla_layers(cfg):
    return cfg["num_hidden_layers"]


def latent_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_kernel_flops(cfg, attended):
    """``attended``: positions attended, summed over the window's lanes
    (``attended_positions_total``)."""
    return attended * mla_layers(cfg) * cfg["num_attention_heads"] \
        * (latent_width(cfg) + cfg["kv_lora_rank"]) * 2


def mla_kernel_bytes(cfg, attended, lanes):
    """The latents behind each seated row once a step and layer, and the
    live ``lanes``' queries and results.  A row that feeds n lanes shares
    its latents among them, and n is at most the engine's chunk K, so the
    rows had at least ``attended / K`` positions behind them: that floor is
    what counts (a decoding row reads as many bytes as this gives a row of
    K lanes; on a v5e its operations already take as long as its bytes,
    242 to the byte against a ridge of 240, so the larger of the two bounds
    is the operations' in every mix)."""
    per_lane = cfg["num_attention_heads"] \
        * (latent_width(cfg) + cfg["kv_lora_rank"]) * LANE_BYTES
    return mla_layers(cfg) * (
        attended / cfg["serving"]["prefill_chunk"] * latent_width(cfg)
        * POOL_BYTES + lanes * per_lane)


def mla_kernel_least_seconds(cfg, peaks, attended, lanes):
    """The roofline: the larger of operations over the bf16 peak and bytes
    over the HBM peak."""
    return max(mla_kernel_flops(cfg, attended) / peaks["bf16_flops"],
               mla_kernel_bytes(cfg, attended, lanes)
               / peaks["hbm_bytes_per_s"])
