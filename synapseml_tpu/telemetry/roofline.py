"""Roofline auditor: XLA-captured bytes/flops for any jitted step.

- :func:`capture` — AOT-lower + compile a jitted callable and record
  XLA's own cost analysis (flops, bytes accessed) plus the top
  byte-moving HLOs estimated from the optimized module's result shapes
  (the "where do the bytes go" answer ``cost_analysis`` alone cannot
  give).  :func:`capture_compiled` does the same for an executable the
  caller already holds.
- the chip spec tables (``CHIP_PEAK_FLOPS``, ``CHIP_HBM_BW``) with
  :func:`chip_peak_flops` / :func:`chip_hbm_bw`: peaks by device kind
  for the ``StepProfiler`` gauges and the collective planner; a backend
  with no table entry (e.g. the CPU container) gets None, so no roofline
  share is made up where the bound is unknown.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

#: peak dense bf16 FLOPs/s by device kind (public spec sheets)
CHIP_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e / Trillium
}

#: HBM bandwidth bytes/s by device kind (public spec sheets)
CHIP_HBM_BW = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,    # v5e
    "TPU v5": 2765e9,        # v5p
    "TPU v6 lite": 1640e9,   # v6e / Trillium
}


def chip_lookup(device, table: Dict[str, float]) -> Optional[float]:
    """Longest-prefix device-kind match into a spec table; None when no
    entry matches (unknown backend: claim nothing — a caller that needs
    the number to compute a share or an MFU raises, it never guesses)."""
    kind = getattr(device, "device_kind", "") or ""
    best = None
    for name, val in table.items():
        if kind.startswith(name) and (best is None or len(name) > best[0]):
            best = (len(name), val)
    return best[1] if best else None


def chip_peak_flops(device) -> Optional[float]:
    return chip_lookup(device, CHIP_PEAK_FLOPS)


def chip_hbm_bw(device) -> Optional[float]:
    return chip_lookup(device, CHIP_HBM_BW)


# ---------------------------------------------------------------------------
# optimized-HLO byte estimation
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "pred": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"(?:^|\)|\]|\}|\s)([a-z][a-z0-9\-]*)\(")


def _shape_bytes(segment: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(segment):
        n = 1
        dims = m.group(2)
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def top_byte_hlos(hlo_text: str, k: int = 8) -> List[Dict[str, Any]]:
    """Top byte-moving instructions of an optimized HLO module, estimated
    from RESULT shapes (each instruction's output buffer; operand bytes
    land at their producers, so nothing double-counts).

    Instructions inside fused computations are skipped — a fusion's
    internals never touch HBM, its root materializes once.  Loop bodies
    (while/scan) count ONCE, not per trip, matching how
    ``Compiled.cost_analysis`` itself accounts them — treat both as
    per-dispatch lower bounds under loops.  Returns ``[{"name", "op",
    "mbytes"}, ...]`` largest first."""
    out = []
    in_fused = False
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line.endswith("{"):
            head = line.split("(", 1)[0]
            in_fused = ("fused_computation" in head or "region_" in head) \
                and "ENTRY" not in line
            continue
        if line == "}":
            in_fused = False
            continue
        if in_fused:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        om = _OPCODE_RE.search(rest)
        opcode = om.group(1) if om else "?"
        cut = rest.find("(")
        b = _shape_bytes(rest if cut < 0 else rest[:cut])
        if b:
            out.append({"name": name, "op": opcode, "mbytes": b / 1e6})
    out.sort(key=lambda d: -d["mbytes"])
    return out[:max(1, k)]


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

def capture_compiled(compiled, top_k: int = 8) -> Optional[Dict[str, Any]]:
    """Cost entry of an ALREADY-compiled executable: ``{"flops",
    "bytes_accessed", "top_hlos"}`` or None.  The one cost_analysis
    parser — callers that keep their Compiled object to execute it
    share it with :func:`capture` instead of re-deriving the dict
    shape."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        entry: Dict[str, Any] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        try:
            entry["top_hlos"] = top_byte_hlos(compiled.as_text(), k=top_k)
        except Exception:
            entry["top_hlos"] = []
        return entry
    except Exception:
        return None


def capture(fn, *args, top_k: int = 8, **kw) -> Optional[Dict[str, Any]]:
    """AOT-compile ``fn`` on ``args`` and return ``{"flops",
    "bytes_accessed", "top_hlos"}`` (or None — capture must never break
    the caller).  Triggers a fresh compile (``lower().compile()`` does
    not share jit's executable cache): call once per program, off the
    measured window."""
    try:
        return capture_compiled(fn.lower(*args, **kw).compile(),
                                top_k=top_k)
    except Exception:
        return None
