"""train_mfu: model FLOPs of the window's tokens (forward + backward, counted
from shapes) over the window and the chips' peak, in %. Moves
train_tokens_per_s."""
from yardstick.flops import train_flops_per_token


def read(run):
    if run.kind != "train" or run.window_s <= 0:
        return None
    flops = train_flops_per_token(run.model, run.traffic["seq_len"]) * run.tokens
    return 100.0 * flops / run.window_s / (len(run.devices) * run.peaks["bf16_flops"])
