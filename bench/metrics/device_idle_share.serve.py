"""device_idle_share.serve: share of the traced window in which no op runs
on the device (averaged over the chips), in %. Moves itl_p50_ms."""


def read(run):
    if run.kind != "serve":
        return None
    red = run.reduction
    return 100.0 * (1.0 - red.busy_s / red.window_s)
