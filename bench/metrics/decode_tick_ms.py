"""decode_tick_ms: median host-clock time of the engine ticks in the traced
window that admit nothing (decode only); a tick ends in the engine's host
read of the sampled tokens. Moves itl_p50_ms."""
from yardstick.stats import median


def read(run):
    if run.kind != "serve":
        return None
    return _ms([t for t in run.ticks if not t["plens"] and t["t1"] <= run.window_s])


def _ms(ticks):
    m = median(t["t1"] - t["t0"] for t in ticks)
    return None if m is None else 1e3 * m
