"""choco_mix_roofline: the fused CHOCO mix kernel's bytes (counted from the compiled
step's shapes) over its device time in the trace, as a share of HBM
bandwidth, in %. Moves train_tokens_per_s."""
from yardstick.kernels import roofline_share


def read(run):
    return roofline_share(run, "fused_mix_pallas")
