"""Bytes the hybrid serving step (KDA + MLA + held experts) must move, from
the configuration's shapes.  What a kernel really moved beyond this (padding,
a second pass) does not count."""

from benchmark.reference import kimi_linear as reference

WEIGHT_BYTES = 2        # bfloat16 matrices


def _kda_layers(cfg):
    return sum(1 for attn, _ffn in reference.layer_kinds(cfg)
               if attn == "kda")


def _moe_layers(cfg):
    return sum(1 for _attn, ffn in reference.layer_kinds(cfg)
               if ffn == "moe")


def step_stream_bytes(params):
    """What one step must read of the parameters: everything but the
    embedding table, of which a step gathers a few rows."""
    import jax
    return sum(x.size * x.dtype.itemsize
               for path, x in jax.tree_util.tree_leaves_with_path(params)
               if jax.tree_util.keystr(path) != "['emb']")


def kda_state_bytes_per_slot_layer(cfg):
    la = cfg["linear_attn_config"]
    return la["num_heads"] * la["head_dim"] * la["head_dim"] * 4


def kda_kernel_bytes(cfg, slot_steps, lanes):
    """Least traffic of ``kda_chunk`` over a window: the float32 state of
    every SEATED slot read and written once a step and KDA layer
    (``slot_steps`` = seated slots summed over the steps), plus each live
    lane's operands (a, k, b k, q columns, the v row in, the o row out)."""
    la = cfg["linear_attn_config"]
    per_lane = la["num_heads"] * 6 * la["head_dim"] * 4
    return _kda_layers(cfg) * (
        2 * slot_steps * kda_state_bytes_per_slot_layer(cfg)
        + lanes * per_lane)


def held_expert_bytes_per_layer(cfg):
    (_first, count), _total = reference.held_experts(cfg)
    return count * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * WEIGHT_BYTES


def experts_touched_share(cfg, tokens_per_step):
    """Expected share of the held experts that receive a token in a step
    of ``tokens_per_step`` live tokens, each choosing ``top_k`` of the
    router's experts uniformly (seeded random weights route so): a grouped
    product reads only those, so only they count."""
    _held, total = reference.held_experts(cfg)
    k = cfg["num_experts_per_token"]
    return 1.0 - (1.0 - k / total) ** max(0.0, tokens_per_step)


def moe_expert_bytes(cfg, steps, lanes):
    """Least traffic of the grouped expert products over a window of
    ``steps`` steps that fed ``lanes`` live lanes: the touched experts'
    three matrices once a step and expert layer."""
    if steps <= 0:
        return 0.0
    return steps * _moe_layers(cfg) * held_expert_bytes_per_layer(cfg) \
        * experts_touched_share(cfg, lanes / steps)
