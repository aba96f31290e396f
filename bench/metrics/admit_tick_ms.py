"""admit_tick_ms: median host-clock time of the engine ticks in the traced
window that admit requests (admission, batched prefill, then the decode).
Moves itl_p99_ms."""
from yardstick.stats import median


def read(run):
    if run.kind != "serve":
        return None
    m = median(t["t1"] - t["t0"] for t in run.ticks if t["plens"] and t["t1"] <= run.window_s)
    return None if m is None else 1e3 * m
