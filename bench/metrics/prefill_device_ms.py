"""prefill_device_ms: median, over the traced window's ticks that admit
requests, of the device-busy ms inside the tick's ``engine.admit`` span (the
batched prefills and their splices). Moves itl_p99_ms."""
from yardstick.scopes import inside, ticks
from yardstick.stats import median


def read(run):
    if run.kind != "serve":
        return None
    red = run.reduction
    m = median(sum(red.busy_ns(0, a, b) for a, b in inside(run, "engine.admit", s, e))
               for s, e in ticks(run, admitting=True))
    return None if m is None else 1e-6 * m
