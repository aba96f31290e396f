"""decode_hbm_roofline: for the decode-only ticks of the traced window, the
bytes a tick must read (every bf16 weight once, and the live keys and values
of the active slots) over the tick's device time, as a share of HBM
bandwidth, in %. Moves itl_p50_ms."""
from yardstick.flops import decode_tick_bytes


def read(run):
    if run.kind != "serve":
        return None
    red = run.reduction
    spans = {st.get("tick"): (s, e) for s, e, st in red.spans_named("bench.engine_step")}
    need = busy = 0.0
    for i, t in enumerate(run.ticks):
        if t["plens"] or not t["ctx"] or i not in spans:
            continue
        s, e = spans[i]
        b = red.busy_ns(0, s, e)
        if b > 0:
            need += decode_tick_bytes(run.model, [c + 1 for c in t["ctx"]])
            busy += b * 1e-9
    if busy <= 0:
        return None
    return 100.0 * need / busy / run.peaks["hbm_bytes_per_s"]
