"""decode_tick_idle_ms: median, over the traced window's ticks that admit
nothing, of the device-idle ms inside the tick's ``engine.step`` span: the
time the chip waits on the engine's own host work. Moves itl_p50_ms."""
from yardstick.scopes import ticks
from yardstick.stats import median


def read(run):
    if run.kind != "serve":
        return None
    red = run.reduction
    m = median((e - s) - red.busy_ns(0, s, e) for s, e in ticks(run, admitting=False))
    return None if m is None else 1e-6 * m
