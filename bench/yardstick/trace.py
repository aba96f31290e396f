"""Capture a profiler trace of the measured window and reduce it to numbers.

The bench marks its own host spans with ``jax.profiler.TraceAnnotation``
(``bench.window`` around the traced window, and one span per call into a
layer). The reduction reads the ``.xplane.pb`` with nothing but JAX:

* device ops: the events of each device plane's ``XLA Ops`` line, clipped to
  the window;
* busy time: the union of a device's op intervals in the window; idle is the
  rest of the window;
* idle gaps: the holes in that union, each labelled with the innermost bench
  span the host was in at the gap's midpoint;
* ops are named by their HLO instruction name, which the compiled program's
  text also carries (kernel bytes come from there).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import shutil
from pathlib import Path

WINDOW = "bench.window"
# ops that hold other ops: their events span their bodies' events
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> tuple[str, str]:
    """(instruction name, opcode) of a device op event. A TPU trace names an
    op by its HLO text, ``%fusion.12 = bf16[..] fusion(...), ...``."""
    if " = " not in event_name:
        return event_name, ""
    lhs, rhs = event_name.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    return lhs.strip().lstrip("%"), (m.group(1) if m else "")


def start(outdir: Path) -> None:
    import jax

    shutil.rmtree(outdir, ignore_errors=True)
    outdir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation alone
    jax.profiler.start_trace(str(outdir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(outdir: Path):
    import jax

    files = sorted(Path(outdir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {outdir}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def tpu_op_lines(plane):
    """The lines of a plane that hold device ops: a TPU plane's ``XLA Ops``."""
    if not plane.name.startswith("/device:TPU:"):
        return []
    return [ln for ln in plane.lines if ln.name == "XLA Ops"]


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]  # ns, host clock
    ops: list[list[tuple[str, float, float]]]  # per device: (name, start, end)
    spans: list[tuple[str, float, float, dict]]  # bench host spans
    containers: list[list[tuple[float, float]]] | None = None  # loop/call bodies' extents

    def __post_init__(self):
        extra = self.containers or [[] for _ in self.ops]
        self._union = [_merge([(s, e) for _, s, e in dev] + list(c))
                       for dev, c in zip(self.ops, extra)]
        self._starts = [[s for s, _ in u] for u in self._union]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_ns(self, dev: int, lo: float | None = None, hi: float | None = None) -> float:
        """Union of device ``dev``'s op intervals within [lo, hi] (default: the
        window)."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        u, total = self._union[dev], 0.0
        i = max(bisect.bisect_right(self._starts[dev], lo) - 1, 0)
        while i < len(u) and u[i][0] < hi:
            total += max(0.0, min(u[i][1], hi) - max(u[i][0], lo))
            i += 1
        return total

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        return sum(self.busy_ns(d) for d in range(len(self.ops))) / len(self.ops) * 1e-9

    def spans_named(self, name: str):
        return [(s, e, st) for n, s, e, st in self.spans if n == name]

    def span_label(self, t: float) -> str:
        """The innermost bench span (other than the window) open at ``t``."""
        if not hasattr(self, "_span_starts"):
            self._inner = [sp for sp in self.spans if sp[0] != WINDOW]
            self._span_starts = [sp[1] for sp in self._inner]
        i = bisect.bisect_right(self._span_starts, t) - 1
        for name, s, e, _ in reversed(self._inner[max(0, i - 64): i + 1]):
            if s <= t <= e:
                return name
        return "outside bench spans"

    def gaps(self, dev: int):
        """Idle holes of device ``dev`` in the window: (start, end)."""
        out, t = [], self.window[0]
        for s, e in self._union[dev]:
            if s > t:
                out.append((t, min(s, self.window[1])))
            t = max(t, e)
            if t >= self.window[1]:
                break
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return [g for g in out if g[1] > g[0]]

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and idle time by what the host
        was doing, each in seconds summed over the devices."""
        ops = collections.Counter()
        for dev in self.ops:
            for n, s, e in dev:
                ops[n] += (e - s) * 1e-9
        idle = collections.Counter()
        for d in range(len(self.ops)):
            for s, e in self.gaps(d):
                idle[self.span_label((s + e) / 2)] += (e - s) * 1e-9
        return {"device_ops": [[n, v] for n, v in ops.most_common(top)],
                "idle_gaps": [[n, v] for n, v in idle.most_common(top)]}


def reduce(pd, op_lines=tpu_op_lines, window: str = WINDOW) -> Reduction:
    """Reduce ProfileData to the bench window's device ops and host spans.
    Ops are named by their HLO instruction name; loop and call ops, whose
    events enclose their bodies' ops, count towards busy time only."""
    spans, devices = [], []
    for plane in pd.planes:
        lines = op_lines(plane)
        if lines:
            evs = []
            for ln in lines:
                for ev in ln.events:
                    name, opcode = op_name(ev.name)
                    evs.append((name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                opcode in CONTAINERS))
            devices.append((plane.name, evs))
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    wins = [(s, e) for n, s, e, _ in spans if n == window]
    if not wins:
        raise ValueError(f"no {window} span in the trace")
    lo, hi = min(w[0] for w in wins), max(w[1] for w in wins)
    if not devices:
        raise ValueError("no device plane in the trace")
    devices.sort(key=lambda d: d[0])
    clip = [[(n, max(s, lo), min(e, hi), c) for n, s, e, c in evs if e > lo and s < hi]
            for _, evs in devices]
    ops = [[(n, s, e) for n, s, e, c in dev if not c] for dev in clip]
    containers = [[(s, e) for _, s, e, c in dev if c] for dev in clip]
    return Reduction((lo, hi), ops, sorted(spans, key=lambda x: x[1]), containers)
