"""Model FLOP/s utilization: forward + backward matmul operations per token
(costs.lstm_net_train_flops_per_token, from the shapes) x tokens per second
of the traced window / (chips x the chip's bf16 peak)."""
from benchmark import arith, costs


def read(obs):
    if "steps" not in obs or not obs["peaks"]:
        return None
    rate = arith.whole_step_rate(obs["steps"], obs["tokens_per_step"],
                                 obs["t_open"], obs["t_close"])
    return 100.0 * costs.lstm_net_train_flops_per_token(obs["config"]) \
        * rate / (len(obs["devices"]) * obs["peaks"]["bf16_flops"])
