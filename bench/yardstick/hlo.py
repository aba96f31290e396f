"""Counts read from a compiled program's HLO text: for each Pallas kernel call
(``tpu_custom_call``), the bytes it must move, every operand read once and
every result written once, from their shapes."""
from __future__ import annotations

import re

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one (possibly tuple) HLO shape string."""
    total = 0
    for m in SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _operand_constraints(rest: str) -> str:
    m = re.search(r"operand_layout_constraints=\{(.*?)\}\s*(?:,\s*\w+=|$)", rest)
    return m.group(1) if m else ""


def custom_call_bytes(hlo_text: str) -> dict[str, int]:
    """Instruction name -> bytes moved per call, for every ``tpu_custom_call``."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = INSTR_RE.match(line)
        if not m:
            continue
        name, result, _, rest = m.groups()
        out[name] = shape_bytes(result) + shape_bytes(_operand_constraints(rest))
    return out
