"""From a profiler trace (``.xplane.pb``) to busy and idle time, time per
operation, and host annotations on the same clock.

Nothing but JAX's ``ProfileData``.  What the TPU runtime writes (looked at by
hand on a v5e trace, PR 26): one plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per executed program, named
``jit_<function>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
instruction, named by the instruction's text, ``%fusion.12 = ...``; a
``while`` or ``conditional`` spans its body's instructions, so events nest);
``Async XLA Ops`` holds copies in flight, which overlap the others and are
not counted as busy.  Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` names appear there as they were given.
All ``start_ns`` share one clock.

busy
    the union of the intervals of ``XLA Ops`` events on a device, clipped to
    the window.  Averaged over devices it is ``busy_s``.
window
    the host annotation :data:`WINDOW_MARK` if the harness wrote one, else
    from the first to the last device event.
self time
    an event's duration minus the part its nested events cover.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench.trace_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"

_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)* = \(?(\w+\[[\d,]*\])?")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(text: str) -> Tuple[str, str]:
    """('fusion', 'fusion bf16[8,4096]') from an instruction's text: the
    base name (stable across programs; what a kernel is matched by) and a
    label that keeps the result's shape."""
    m = _NAME.match(text)
    if not m:
        base = text.split(" ")[0].lstrip("%")
        return base, base
    base, shape = m.group(1), m.group(2)
    return base, (f"{base} {shape}" if shape else base)


def module_name(text: str) -> str:
    m = _MODULE.match(text)
    return m.group(1) if m else text


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(s: float, e: float, w0: float, w1: float) -> Tuple[float, float]:
    return max(s, w0), min(e, w1)


def _self_times(events: List[Tuple[float, float, str]]) -> List[float]:
    """Self time of each (start, end, name), nesting by containment."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -(events[i][1])))
    selfs = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            ps, pe, _ = events[stack[-1]]
            selfs[stack[-1]] -= max(0.0, min(e, pe) - s)
        stack.append(i)
    return selfs


def reduce_trace(path: str, host_names: Optional[Sequence[str]] = None
                 ) -> Dict[str, Any]:
    """Read one ``.xplane.pb``.  ``host_names``: the annotations to keep
    from the host plane (``None`` keeps every event whose name does not
    start with ``$``, which is how the Python tracer marks frames)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = set(host_names) if host_names is not None else None
    host: List[Tuple[str, float, float]] = []
    window: Optional[Tuple[float, float]] = None
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                nm = ev.name
                if nm == WINDOW_MARK:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif (nm in keep) if keep is not None else \
                        not nm.startswith("$"):
                    host.append((nm, ev.start_ns, ev.start_ns + ev.duration_ns))

    raw: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {OPS_LINE: [], MODULES_LINE: []}
        for line in plane.lines:
            if line.name in lines:
                lines[line.name] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
        raw[plane.name] = lines
    if not raw:
        raise ValueError(f"{path}: no /device:TPU:<n> plane in the trace")
    if window is None:
        starts = [e[0] for ln in raw.values() for e in ln[OPS_LINE]]
        ends = [e[1] for ln in raw.values() for e in ln[OPS_LINE]]
        if not starts:
            raise ValueError(f"{path}: no device operation in the trace")
        window = (min(starts), max(ends))
    w0, w1 = window

    devices = []
    for name in sorted(raw):
        ops = [(max(s, w0), min(e, w1), t) for s, e, t in raw[name][OPS_LINE]
               if e > w0 and s < w1]
        busy = union((s, e) for s, e, _ in ops)
        selfs = _self_times(ops)
        per_op: Dict[str, Dict[str, Any]] = {}
        for (s, e, text), st in zip(ops, selfs):
            base, label = op_name(text)
            rec = per_op.setdefault(label, {
                "base": base, "self_ns": 0.0, "total_ns": 0.0, "count": 0,
                "intervals": []})
            rec["self_ns"] += st
            rec["total_ns"] += e - s
            rec["count"] += 1
            rec["intervals"].append((s, e))
        mods: Dict[str, Dict[str, Any]] = {}
        for s, e, text in raw[name][MODULES_LINE]:
            if e <= w0 or s >= w1:
                continue
            s, e = _clip(s, e, w0, w1)
            rec = mods.setdefault(module_name(text), {
                "total_ns": 0.0, "count": 0, "intervals": []})
            rec["total_ns"] += e - s
            rec["count"] += 1
            rec["intervals"].append((s, e))
        devices.append({"name": name, "busy": busy, "busy_ns": total(busy),
                        "ops": per_op, "modules": mods})
    host = [(n, *_clip(s, e, w0, w1)) for n, s, e in host
            if e > w0 and s < w1]
    return {"window_ns": (w0, w1), "window_s": (w1 - w0) / 1e9,
            "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
            "devices": devices, "host": host}


# -- queries over a reduced trace --------------------------------------------

def fullest(reduced: Dict[str, Any]) -> Dict[str, Any]:
    """The device with the most busy time."""
    return max(reduced["devices"], key=lambda d: d["busy_ns"])


def op_seconds(device: Dict[str, Any], bases: Sequence[str],
               what: str = "total_ns") -> Tuple[float, int]:
    """(seconds, calls) of the operations whose base name starts with one
    of ``bases``."""
    ns, n = 0.0, 0
    for rec in device["ops"].values():
        if any(rec["base"].startswith(b) for b in bases):
            ns += rec[what]
            n += rec["count"]
    return ns / 1e9, n


def module_seconds(device: Dict[str, Any], contains: str) -> Tuple[float, int]:
    """(device-busy seconds inside, runs of) the programs whose name
    contains ``contains``: the busy union clipped to the programs' spans,
    so a gap inside a program does not count."""
    spans, n = [], 0
    for name, rec in device["modules"].items():
        if contains in name:
            spans += rec["intervals"]
            n += rec["count"]
    spans = union(spans)
    inside = total(spans) - total(subtract(spans, device["busy"]))
    return inside / 1e9, n


def device_ops_top(reduced: Dict[str, Any], k: int = 10) -> List[List[Any]]:
    """[[label, self seconds], ...] on the fullest device."""
    ops = fullest(reduced)["ops"]
    top = sorted(ops.items(), key=lambda kv: -kv[1]["self_ns"])[:k]
    return [[label, rec["self_ns"] / 1e9] for label, rec in top]


def idle_gaps_by_host(reduced: Dict[str, Any], k: int = 10) -> List[List[Any]]:
    """[[annotation, idle seconds], ...]: every moment at which the fullest
    device runs nothing is given to the innermost (shortest) host
    annotation open at that moment, ``unspanned`` where none is.  One sweep
    over the gaps' and the annotations' ends in time order."""
    w0, w1 = reduced["window_ns"]
    gaps = subtract([(w0, w1)], fullest(reduced)["busy"])
    host = reduced["host"]
    # (time, kind, index): closes before opens at equal times
    marks = [(ge, 0, -1) for _, ge in gaps] + [(gs, 3, -1) for gs, _ in gaps]
    for i, (_, hs, he) in enumerate(host):
        marks += [(he, 1, i), (hs, 2, i)]
    marks.sort()
    by: Dict[str, float] = {}
    open_: Dict[int, float] = {}          # annotation index -> its length
    in_gap, last = False, w0
    for t, kind, i in marks:
        if in_gap and t > last:
            inner = min(open_, key=open_.get, default=None)
            name = host[inner][0] if inner is not None else "unspanned"
            by[name] = by.get(name, 0.0) + (t - last)
        last = t
        if kind == 0:
            in_gap = False
        elif kind == 3:
            in_gap = True
        elif kind == 1:
            open_.pop(i, None)
        else:
            open_[i] = host[i][2] - host[i][1]
    top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
    return [[n, s / 1e9] for n, s in top]
