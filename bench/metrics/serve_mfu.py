"""serve_mfu: model FLOPs of every prompt prefilled and token decoded in the
traced window (counted from shapes), over the window and the chips' peak, in
%. Moves itl_p50_ms."""
from yardstick.flops import decode_flops, prefill_flops


def read(run):
    if run.kind != "serve":
        return None
    total = 0.0
    for t in run.ticks:
        if t["t1"] > run.window_s:
            continue
        total += sum(prefill_flops(run.model, p) for p in t["plens"])
        contexts = [c + 1 for c in t["ctx"]] + [p + 1 for p in t["plens"]]
        total += sum(decode_flops(run.model, c) for c in contexts[: t["decoded"]])
    if total == 0:
        return None
    return 100.0 * total / run.window_s / (len(run.devices) * run.peaks["bf16_flops"])
