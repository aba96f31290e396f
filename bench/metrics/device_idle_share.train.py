"""device_idle_share.train: share of the traced window in which no op runs
on the device (averaged over the chips), in %. Moves train_tokens_per_s."""


def read(run):
    if run.kind != "train":
        return None
    red = run.reduction
    return 100.0 * (1.0 - red.busy_s / red.window_s)
