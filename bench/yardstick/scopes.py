"""The program's own scopes and spans, read from a traced run.

The program names the phases of its training round with ``jax.named_scope``
(``adgda.local``, ``adgda.dual``, ``adgda.gossip``, ``adgda.telemetry``); the
compiled step carries the scope in each instruction's ``op_name`` metadata.
It marks its serving tick with ``jax.profiler.TraceAnnotation`` host spans
(``engine.step`` and, inside it, ``engine.admit``, ``engine.prefill``,
``engine.decode``, ``engine.sample``, ``engine.retire``), on the clock of the
device ops. This module reads both without importing the program:

* ``scope_of``: instruction name -> the first ``adgda.*`` component of its
  op_name, from the compiled step's HLO text (``run.hlo_text``);
* ``round_split``: device ms per round under ``adgda.local``, under
  ``adgda.gossip``, and the rest of the busy time;
* ``engine_spans``: the ``engine.*`` host spans with their stats, loaded once
  per run from the run's trace directory.

A program without scopes or spans leaves every reader here with nothing to
read: they return None (or an empty list) and never raise for it.
"""
from __future__ import annotations

import re

from yardstick import registry
from yardstick import trace as tr
from yardstick.hlo import INSTR_RE

TRACE_DIR = registry.ROOT / ".bench_trace"  # where bench/run.py writes a traced run
ENGINE = "engine."
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(adgda\.[A-Za-z0-9_]+)")


def scope_of(hlo_text: str) -> dict[str, str]:
    """Instruction name -> first ``adgda.*`` scope of its op_name, for every
    instruction of the HLO text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR_RE.match(line)
        op = _OP_NAME.search(line) if m else None
        scope = _SCOPE.search(op.group(1)) if op else None
        if scope:
            out[m.group(1)] = scope.group(1)
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in tr._merge(intervals))


def round_split(run) -> dict[str, float] | None:
    """Device ms per round of the traced window: ``local`` and ``gossip``, the
    union of the intervals of the ops under ``adgda.local`` and
    ``adgda.gossip``; ``other``, the rest of the busy time (the dual, the
    telemetry, unscoped ops and loop bookkeeping). Averaged over the devices.
    None where the step carries no scope or the window holds no round."""
    if run.kind != "train" or not run.hlo_text or not run.steps:
        return None
    if not hasattr(run, "round_split"):
        scopes = scope_of(run.hlo_text)
        red = run.reduction
        local = gossip = other = 0.0
        for d, dev in enumerate(red.ops):
            by = {"adgda.local": [], "adgda.gossip": []}
            for name, s, e in dev:
                if scopes.get(name) in by:
                    by[scopes[name]].append((s, e))
            lo, go = by["adgda.local"], by["adgda.gossip"]
            local += _length(lo)
            gossip += _length(go)
            other += red.busy_ns(d) - _length(lo + go)
        per = 1e-6 / len(red.ops) / run.steps
        run.round_split = ({"local": local * per, "gossip": gossip * per, "other": other * per}
                           if scopes else None)
    return run.round_split


def load_engine_spans(pd) -> list[tuple[str, float, float, dict]]:
    """The ``engine.*`` host spans of a profile, (name, start, end, stats),
    by start."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(ENGINE):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    return sorted(spans, key=lambda x: x[1])


def engine_spans(run) -> list[tuple[str, float, float, dict]]:
    """The run's ``engine.*`` spans inside the traced window, loaded once per
    run from the trace directory."""
    if not hasattr(run, "engine_spans"):
        try:
            spans = load_engine_spans(tr.load(TRACE_DIR))
        except FileNotFoundError:
            spans = []
        lo, hi = run.reduction.window
        run.engine_spans = [sp for sp in spans if lo <= sp[1] and sp[2] <= hi]
    return run.engine_spans


def ticks(run, admitting: bool):
    """(start, end) of the window's ``engine.step`` spans whose tick admitted
    requests (``admitting``) or admitted none."""
    return [(s, e) for n, s, e, st in engine_spans(run)
            if n == "engine.step" and (st.get("admitted", 0) > 0) == admitting]


def inside(run, name: str, lo: float, hi: float):
    """(start, end) of the window's ``name`` spans that lie within [lo, hi]."""
    return [(s, e) for n, s, e, _ in engine_spans(run) if n == name and lo <= s and e <= hi]
