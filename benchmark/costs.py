"""Operations and bytes an algorithm needs, from its shapes.  Recomputed
work does not count; a matmul of [m,k]x[k,n] is 2mkn operations."""


def tree_bytes(tree):
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def lstm_net_train_flops_per_token(cfg):
    """Forward + backward matmul operations per token of the
    embedding -> n x (fc 4h, lstmemory h) -> last step -> softmax network.
    Backward of a matmul is two matmuls of the same size, so 3x forward.
    The embedding gather, the pooled softmax head (per sequence, not per
    token) and the elementwise gate arithmetic are left out: under 1%."""
    h, din, fwd = cfg["hidden"], cfg["emb"], 0
    for _ in range(cfg["lstm_layers"]):
        fwd += 2 * din * 4 * h      # input projection (fc_layer, 4h wide)
        fwd += 2 * h * 4 * h        # recurrent projection h_{t-1} @ w_r
        din = h
    return 3 * fwd


def lstm_recurrence_flops_per_token(cfg):
    """The recurrent projections alone, forward + backward (what the scan's
    ``while`` loops or the fused kernel compute)."""
    h = cfg["hidden"]
    return 3 * cfg["lstm_layers"] * 2 * h * 4 * h


def lstm_recurrence_bytes_per_token(cfg, act_bytes=4):
    """Least HBM traffic of the recurrence per token, forward + backward,
    with the recurrent weights held on chip: forward reads the 4h
    pre-projected gates and writes h; backward reads the 4h gates again (the
    gate activations are recomputed from them), h_{t-1} and dh, and writes
    the 4h gate gradients.  15h values per token and layer."""
    h = cfg["hidden"]
    per_layer = (4 * h + h) + (4 * h + h + h) + 4 * h
    return cfg["lstm_layers"] * per_layer * act_bytes
