"""The recurrence's share of its roofline: the larger of operations / bf16
peak and bytes / HBM peak (costs.lstm_recurrence_*, from the shapes, float32
activations, recurrent weights held on chip) over its device time.  At
h=512 the bound is memory: 7.7 ms of traffic a step against 6.5 ms of
operations (PERF.md section 5)."""
from benchmark import costs
from benchmark.layer_metrics import lstm_recurrence_share


def read(obs):
    s = lstm_recurrence_share.seconds(obs)
    pk = obs["peaks"]
    if not s or not pk:
        return None
    # tokens the traced steps processed on one device
    tokens = obs["steps"] * obs["tokens_per_step"] / len(obs["devices"])
    least = max(
        costs.lstm_recurrence_flops_per_token(obs["config"]) / pk["bf16_flops"],
        costs.lstm_recurrence_bytes_per_token(obs["config"])
        / pk["hbm_bytes_per_s"]) * tokens
    return 100.0 * least / s
