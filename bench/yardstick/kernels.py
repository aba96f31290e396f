"""Shared arithmetic of the gossip kernels' roofline shares: the bytes each
call must move (from the compiled step's shapes) over the calls' device time
in the trace, against the chip's HBM bandwidth."""
from yardstick.hlo import custom_call_bytes


def roofline_share(run, prefix: str):
    """% of HBM bandwidth reached by the kernel calls whose HLO name starts
    with ``prefix``; None where the trace holds none of them."""
    if run.kind != "train" or not run.hlo_text:
        return None
    per_call = {n: b for n, b in custom_call_bytes(run.hlo_text).items() if n.startswith(prefix)}
    moved = secs = 0.0
    for dev in run.reduction.ops:
        for name, s, e in dev:
            if name in per_call:
                moved += per_call[name]
                secs += (e - s) * 1e-9
    if secs <= 0:
        return None
    return 100.0 * moved / secs / run.peaks["hbm_bytes_per_s"]
