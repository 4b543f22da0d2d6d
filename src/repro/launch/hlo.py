"""Post-SPMD HLO parsing: collective bytes per op type.

``compiled.as_text()`` is the per-device partitioned module, so shapes on
collective ops are per-device shapes; summing result bytes over all
collective ops gives per-device collective traffic per step (the roofline's
collective term numerator).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")

_OP_RE = re.compile(
    r"=\s+(?P<rtype>\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>" + "|".join(_COLL) + r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device result bytes of every collective op, by op type."""
    totals: Dict[str, int] = defaultdict(int)
    counts: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        rtype = m.group("rtype")
        shapes = _SHAPE_RE.findall(rtype)
        if not shapes:
            continue
        total = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if rtype.startswith("(") and len(shapes) > 1:
            # async -start ops carry (operand..., output...) tuples; take the
            # second half (outputs) to avoid double counting.
            half = shapes[len(shapes) // 2:]
            total = sum(_shape_bytes(dt, dims) for dt, dims in half)
        totals[op] += total
        counts[op] += 1
    out = dict(totals)
    out["_counts"] = dict(counts)
    out["_total"] = sum(totals.values())
    return out
