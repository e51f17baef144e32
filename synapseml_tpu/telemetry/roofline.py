"""Roofline auditor: XLA-captured bytes/flops for any jitted step.

ROADMAP item 4's standing requirement is that every perf change lands
with a before/after roofline block in ``BENCH_latest.json``.  This module
is the ONE implementation behind those blocks:

- :func:`capture` — AOT-lower + compile a jitted callable and record
  XLA's own cost analysis (flops, bytes accessed) plus the top
  byte-moving HLOs estimated from the optimized module's result shapes
  (the "where do the bytes go" answer ``cost_analysis`` alone cannot
  give).
- :func:`roofline_block` — turn (bytes/sample, flops/sample, measured
  ms) into the canonical paired-block schema: ``bytes_per_sample`` /
  ``flops_per_sample`` / ``compute_ms`` / ``bandwidth_ms`` /
  ``measured_ms`` / ``frac_of_bandwidth_roofline``, every field numeric
  or null.  Compute/bandwidth bounds come from the per-device-kind spec
  tables below; on a backend with no table entry (e.g. the CPU
  container) they are null — byte reductions are still proven by the
  XLA-captured bytes, but no bandwidth-roofline claim is fabricated
  (the PR-6/PR-8 measurement-honesty pattern).
- :func:`paired_roofline` — the ``{leg}_roofline_before`` /
  ``{leg}_roofline_after`` dict bench.py merges into its record; the
  tier-1 artifact schema check (tests/test_artifacts_json.py) holds any
  record carrying one side of a pair to the full two-sided block.

The chip spec tables live HERE (bench.py imports them) so the auditor,
the StepProfiler gauges and the bench can never disagree on a peak.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: the canonical paired-block field set — schema-checked in tier-1
ROOFLINE_BLOCK_KEYS = (
    "bytes_per_sample", "flops_per_sample", "compute_ms", "bandwidth_ms",
    "measured_ms", "frac_of_bandwidth_roofline",
)

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
# capture + blocks
# ---------------------------------------------------------------------------

def capture_compiled(compiled, top_k: int = 8) -> Optional[Dict[str, Any]]:
    """Cost entry of an ALREADY-compiled executable: ``{"flops",
    "bytes_accessed", "top_hlos"}`` or None.  The one cost_analysis
    parser — callers that keep their Compiled object to execute it
    (bench legs) share it with :func:`capture` instead of re-deriving
    the dict shape."""
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


def roofline_block(bytes_per_sample: Optional[float],
                   flops_per_sample: Optional[float],
                   measured_ms: Optional[float],
                   device=None,
                   samples: float = 1.0) -> Dict[str, Optional[float]]:
    """The canonical 6-key block for one leg/config.

    ``measured_ms`` is the measured wall time of ``samples`` samples
    (one step, usually); compute/bandwidth bounds are for the same
    ``samples`` against the device's spec-sheet peaks — null on a
    backend with no table entry, so no roofline fraction is invented
    where the bound is unknown."""
    peak = chip_peak_flops(device) if device is not None else None
    bw = chip_hbm_bw(device) if device is not None else None
    compute_ms = (samples * flops_per_sample / peak * 1e3
                  if peak and flops_per_sample else None)
    bandwidth_ms = (samples * bytes_per_sample / bw * 1e3
                    if bw and bytes_per_sample else None)
    frac = (bandwidth_ms / measured_ms
            if bandwidth_ms and measured_ms else None)
    return {
        "bytes_per_sample": bytes_per_sample,
        "flops_per_sample": flops_per_sample,
        "compute_ms": compute_ms,
        "bandwidth_ms": bandwidth_ms,
        "measured_ms": measured_ms,
        "frac_of_bandwidth_roofline": frac,
    }


def check_roofline_block(block: Any) -> None:
    """Schema guard shared with tests/test_artifacts_json.py: a paired
    roofline block is a dict carrying EXACTLY the canonical keys, each
    numeric or null."""
    if not isinstance(block, dict):
        raise ValueError(f"roofline block must be a dict, got "
                         f"{type(block).__name__}")
    missing = [key for key in ROOFLINE_BLOCK_KEYS if key not in block]
    if missing:
        raise ValueError(f"roofline block missing keys {missing}")
    bad = [key for key, v in block.items()
           if v is not None and not isinstance(v, (int, float))]
    if bad:
        raise ValueError(f"roofline block non-numeric fields {bad}")


def paired_roofline(leg: str, before: Dict[str, Optional[float]],
                    after: Dict[str, Optional[float]]) -> Dict[str, Any]:
    """``{leg}_roofline_before`` / ``{leg}_roofline_after`` pair, both
    sides schema-checked before they can enter a bench record."""
    check_roofline_block(before)
    check_roofline_block(after)
    return {f"{leg}_roofline_before": dict(before),
            f"{leg}_roofline_after": dict(after)}


def audit(key: str, fn, *args, samples: float = 1.0,
          measured_ms: Optional[float] = None, device=None,
          **kw) -> Optional[Dict[str, Any]]:
    """One-call wrap of any jitted step: capture its compiled cost and
    produce the per-sample roofline block plus the top byte movers.

    → ``{"key", "bytes_per_sample", "flops_per_sample",
    "arithmetic_intensity", "block", "top_hlos"}`` or None when the
    backend exposes no cost analysis."""
    cost = capture(fn, *args, **kw)
    if cost is None or not cost.get("bytes_accessed"):
        return None
    bps = cost["bytes_accessed"] / max(samples, 1e-9)
    fps = cost["flops"] / max(samples, 1e-9)
    return {
        "key": key,
        "bytes_per_sample": bps,
        "flops_per_sample": fps,
        "arithmetic_intensity": (cost["flops"] / cost["bytes_accessed"]
                                 if cost["bytes_accessed"] else None),
        "block": roofline_block(bps, fps, measured_ms, device=device,
                                samples=samples),
        "top_hlos": cost.get("top_hlos", []),
    }
