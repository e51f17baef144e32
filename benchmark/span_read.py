"""Spans recorded inside the program (``synapseml_tpu.telemetry``), for the
readers under ``benchmark/metrics`` whose ``source`` is ``program_span``.

The spans are those of the run's own process.  Their clock is
``time.monotonic_ns()``: the clock of the load generator's stamps
(``facts["t0"]``, ``facts["t1"]``) and of ``facts["trace_host"]``.  Step
spans (``engine.step.*``, ``engine.admit.*``, ``loop.*``) exist only while a
profiler session is on, so only for the traced part of a window.  A program
that records no such span (the parent of the PR that added them) gives every
reader here nothing to read, and the reader returns ``None``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness
from benchmark import trace_reduce as tr

#: the span families that say what the serving host was doing; a request's
#: own ``serving.request`` covers its whole life and says nothing of that
HOST_WORK = ("loop.", "engine.")
#: idle under these is not attributed: the tick's own self time, and no span
UNATTRIBUTED = ("loop.tick", "unspanned")


def spans(name: Optional[str] = None) -> List[Any]:
    """The program's finished spans that carry a monotonic start, oldest
    first."""
    try:
        from synapseml_tpu.telemetry import get_tracer
        found = get_tracer().spans(name)
    except Exception:  # noqa: BLE001: a program without the facility
        return []
    return [s for s in found if getattr(s, "start_ns", None) is not None
            and getattr(s, "end_ns", None) is not None]


def seconds(sp) -> float:
    return (sp.end_ns - sp.start_ns) / 1e9


def started_in(found: Sequence[Any], span: Optional[Tuple[float, float]]
               ) -> List[Any]:
    """Those that start inside ``span``, monotonic seconds; all of them
    where there is no such span."""
    if not span or span[0] is None:
        return list(found)
    a, b = span
    return [s for s in found if a <= s.start_ns / 1e9 < b]


def mean_ms(name: str, facts: Dict[str, Any]) -> Optional[float]:
    """Mean duration of the spans ``name`` of the traced part."""
    found = started_in(spans(name), facts.get("trace_host"))
    if not found:
        return None
    return 1e3 * sum(seconds(s) for s in found) / len(found)


def last(name: str):
    found = spans(name)
    return found[-1] if found else None


def children_seconds(parent_name: str, name: str) -> Optional[float]:
    """Summed duration of the spans ``name`` under the newest span
    ``parent_name``."""
    parent = last(parent_name)
    if parent is None:
        return None
    found = [s for s in spans(name) if s.parent_id == parent.span_id]
    return sum(seconds(s) for s in found) if found else None


def on_trace_clock(found: Sequence[Any], trace: Dict[str, Any],
                   facts: Dict[str, Any]) -> List[Tuple[str, float, float]]:
    """(name, start, end) on the profiler's clock, clipped to the traced
    window.  The two clocks meet at the window mark's two ends: its
    annotation's start and end in the trace, and the host's readings taken
    beside them (``facts["trace_host"]``)."""
    w0, w1 = trace["window_ns"]
    h0, h1 = (1e9 * t for t in facts["trace_host"])
    rate = (w1 - w0) / (h1 - h0)
    out = []
    for s in found:
        a = w0 + (s.start_ns - h0) * rate
        b = w0 + (s.end_ns - h0) * rate
        if b > w0 and a < w1:
            out.append((s.name, max(a, w0), min(b, w1)))
    return out


def idle_by_span(trace: Dict[str, Any], facts: Dict[str, Any]
                 ) -> Optional[Dict[str, float]]:
    """{span name: idle seconds}: every moment at which the fullest device
    runs nothing, given to the innermost program span open at that moment
    (``trace_reduce.idle_gaps_by_host`` over the program's spans in place of
    the runner's annotations).  Printed to stderr, largest first."""
    host_span = facts.get("trace_host")
    if not host_span or host_span[0] is None:
        return None
    work = [s for s in spans() if s.name.startswith(HOST_WORK)]
    host = on_trace_clock(work, trace, facts)
    if not host:
        return None
    table = tr.idle_gaps_by_host(dict(trace, host=host), k=1 << 30)
    for name, secs in table:
        harness.say(f"idle by span: {name} {secs:.6f} s")
    return dict(table)
