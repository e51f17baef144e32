"""Span tracing: nested, queryable, Chrome-trace-exportable, and on the
profiler's clock.

Horovod's timeline (Sergeev & Del Balso, arXiv:1802.05799) made the
per-op schedule of a distributed run *visible*; the analogue here is a
host-side span tracer: ``with span("gbdt.fit", rows=n):`` produces an
in-memory record with parent/child nesting (thread-local stack),
host/process-index attribution and ``time.monotonic_ns()`` timestamps
(the clock of ``ServingRequest.enqueued_at`` and of any load
generator's stamps), and the whole trace exports as Chrome-trace JSON
(load in ``chrome://tracing`` or Perfetto).

While a profiler session is on (:func:`synapseml_tpu.core.profiling.
trace`, ``jax.profiler.start_trace``) every open span also holds a
``jax.profiler.TraceAnnotation`` of its name, so the same interval lies
on the host plane of that capture, on the profiler's clock, beside the
device's operations.

Two entry points, chosen by the call site:

``span(name, **attrs)``
    always recorded: work that happens a few times per fit, request or
    warm-up.
``step_span(name)``
    work inside a decode step or a loop tick: live only while a
    profiler session is on (per-step spans exist to explain a device
    trace, so they exist when there is one).  Off, it returns one
    shared no-op whose ``live`` is False; attributes that cost
    something to compute go under ``if sp.live:``.

No span synchronises with the device: one around an asynchronous
dispatch times the enqueue.
"""

from __future__ import annotations

import itertools
import socket
import sys
import threading
import time
import uuid
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Span", "Tracer", "get_tracer", "span", "step_span",
           "RequestTraceStore", "get_request_tracer", "mint_trace_id"]

_ids = itertools.count(1)
_tls = threading.local()
_HOST = socket.gethostname()
#: epoch seconds at ``time.monotonic() == 0``, read once: a span reads one
#: clock, and its wall time (the Chrome export's) is derived from it
_WALL_OFFSET_S = time.time() - time.monotonic()
#: ``jax.profiler.TraceAnnotation`` once jax is imported — looked up in
#: ``sys.modules`` so importing telemetry never drags in jax
_annotation_cls = None


def _annotation():
    global _annotation_cls
    if _annotation_cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation_cls = getattr(profiler, "TraceAnnotation", None)
    return _annotation_cls


def _profiling() -> bool:
    """Is a profiler session on?  (24 ns; False before jax is imported.)"""
    cls = _annotation_cls or _annotation()
    return cls is not None and cls.is_enabled()


def _process_index() -> int:
    """``jax.process_index()`` when jax is up, else 0: read when a span
    is exported, never on the path that records one."""
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        return int(jax.process_index())
    except Exception:  # noqa: BLE001 — no backend to ask
        return 0


class Span:
    """One span: live between ``__enter__`` and ``__exit__`` (or
    :meth:`start` and :meth:`close`, for a region of a long function
    that a ``with`` block would have to re-indent), finished after."""

    __slots__ = ("name", "span_id", "parent_id", "trace_id", "start_ns",
                 "end_ns", "attrs", "thread_id", "live", "_tracer",
                 "_annotation")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 attrs: Optional[Dict[str, Any]] = None,
                 trace_id: Optional[str] = None):
        self._tracer = tracer
        self.live = tracer is not None      # False: the shared no-op
        self.name = name
        self.attrs = attrs
        self.trace_id = trace_id
        self.span_id = self.parent_id = None
        self.start_ns = self.end_ns = None
        self._annotation = None

    def __enter__(self) -> "Span":
        if not self.live:
            return self
        stack: List[Span] = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if self.trace_id is None:       # a request's work is its own
                self.trace_id = parent.trace_id
        self.span_id = next(_ids)
        self.thread_id = threading.get_ident()
        stack.append(self)
        if _profiling():
            self._annotation = _annotation_cls(self.name)
            self._annotation.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        if not self.live or self.end_ns is not None:
            return False
        end_ns = time.monotonic_ns()
        stack = getattr(_tls, "stack", ())
        if self in stack:
            # a region an exception left open ends with its ancestor
            while stack.pop()._finish(end_ns) is not self:
                pass
        else:                   # closed on another thread than opened
            self._finish(end_ns)
        return False

    def _finish(self, end_ns: int) -> "Span":
        self.end_ns = end_ns
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._tracer._append(self)
        return self

    start = __enter__

    def close(self) -> None:
        self.__exit__(None, None, None)

    def set(self, **attrs) -> None:
        """Add attributes; until the span closes."""
        if self.live:
            self.attrs.update(attrs)

    @property
    def start_wall_s(self) -> float:
        """Epoch seconds of ``start_ns``, by the process's one offset
        between the two clocks (taken at import: a wall clock stepped
        since then is not followed)."""
        return self.start_ns / 1e9 + _WALL_OFFSET_S

    @property
    def process_index(self) -> int:
        return _process_index()

    @property
    def host(self) -> str:
        return _HOST

    @property
    def duration_s(self) -> float:
        if self.start_ns is None:
            return 0.0
        return ((self.end_ns or time.monotonic_ns()) - self.start_ns) / 1e9

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id}, {self.duration_s:.6f}s)")


#: what :func:`step_span` returns while no profiler session is on
_NO_SPAN = Span(None, "")


class Tracer:
    """Bounded in-memory trace: a ring of the newest ``max_spans``
    finished spans; one per process is plenty."""

    def __init__(self, max_spans: int = 100_000):
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(maxlen=max_spans)
        self._appended = 0

    def span(self, name: str, trace_id: Optional[str] = None,
             **attrs) -> Span:
        return Span(self, name, attrs, trace_id)

    def _append(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)
            self._appended += 1

    def record(self, name: str, duration_s: float, *,
               start_ns: Optional[int] = None,
               parent_id: Optional[int] = None,
               trace_id: Optional[str] = None, **attrs) -> Span:
        """Append an already-measured interval as a finished span: the
        one that began on another thread (a request, from the
        listener's enqueue to its retirement in the decode loop).
        ``start_ns`` is on the ``time.monotonic_ns()`` clock; without
        it the interval ends now."""
        dur_ns = int(duration_s * 1e9)
        if start_ns is None:
            start_ns = time.monotonic_ns() - dur_ns
        sp = Span(self, name, attrs, trace_id)
        sp.span_id, sp.parent_id = next(_ids), parent_id
        sp.start_ns, sp.end_ns = int(start_ns), int(start_ns) + dur_ns
        sp.thread_id = threading.get_ident()
        self._append(sp)
        return sp

    # -- queries -----------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Span]:
        """The finished spans still in the ring, oldest first."""
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def spans_since(self, cursor: int = 0) -> Tuple[List[Span], int]:
        """(the spans finished after ``cursor``, the new cursor): the
        cursor counts every span ever finished, so it survives a ring
        that wraps (what fell off is skipped) and a :meth:`reset`
        (a cursor ahead of the count starts over)."""
        with self._lock:
            spans, appended = list(self._spans), self._appended
        if cursor > appended:
            cursor = 0
        fresh = min(appended - cursor, len(spans))
        return spans[len(spans) - fresh:], appended

    def children(self, parent: Span) -> List[Span]:
        return [s for s in self.spans() if s.parent_id == parent.span_id]

    def roots(self) -> List[Span]:
        return [s for s in self.spans() if s.parent_id is None]

    @property
    def dropped(self) -> int:
        """Spans that fell off the ring's old end."""
        with self._lock:
            return self._appended - len(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._appended = 0

    # -- export ------------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace ("Trace Event Format") dict: complete ("X")
        events, pid = process index, tid = OS thread id, ts/dur in us."""
        events = []
        for s in self.spans():
            ev = chrome_event(s)
            ev["pid"] = s.process_index
            ev["args"]["host"] = s.host
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> Dict[str, Any]:
        """Atomically write the Chrome-trace JSON to ``path`` (via the
        artifact writer, so a crash cannot leave a truncated trace)."""
        from .artifact import write_json
        return write_json(path, self.chrome_trace(),
                          schema=("traceEvents",))


def chrome_event(s: Span) -> Dict[str, Any]:
    """One finished span as a pid-less Chrome complete event."""
    args = {**s.attrs, "span_id": s.span_id, "parent_id": s.parent_id}
    if s.trace_id is not None:
        args["trace_id"] = s.trace_id
    return {"name": s.name, "ph": "X", "cat": "host",
            "ts": s.start_wall_s * 1e6,
            "dur": (s.end_ns - s.start_ns) / 1e3,
            "tid": s.thread_id, "args": args}


_default_tracer = Tracer()


def get_tracer() -> Tracer:
    return _default_tracer


def span(name: str, trace_id: Optional[str] = None, **attrs) -> Span:
    """``with span("phase", key=val) as sp:`` on the process-default
    tracer; always recorded."""
    return Span(_default_tracer, name, attrs, trace_id)


def step_span(name: str) -> Span:
    """``with step_span("engine.step") as sp:`` on the process-default
    tracer; recorded only while a profiler session is on."""
    return Span(_default_tracer, name, {}) if _profiling() else _NO_SPAN


# ---------------------------------------------------------------------------
# request-scoped tracing (the serving plane's per-request timelines)
# ---------------------------------------------------------------------------

def mint_trace_id() -> str:
    """A fresh request trace id (opaque hex; minted once per request at
    admission and propagated across serving hops via the
    ``X-SML-Trace-Id`` exchange header)."""
    return uuid.uuid4().hex


#: the events that end a timeline; one place of ``max_events`` is theirs
TERMINAL_EVENTS = frozenset(("retired", "shed", "cancelled"))


class RequestTraceStore:
    """Bounded store of per-request event timelines — the serving
    plane's answer to "follow THIS request from router to retired
    slot" when an aggregate percentile goes bad.

    One *trace* is one request's lifecycle, told by its TRANSITIONS:
    ``queued`` → ``shed``/``admitted`` → ``prefill`` (with its bucket)
    → ``decode`` (its first decode step; ``preempted``/``resumed``,
    ``compile_wait`` where they happen) → ``retired``/``cancelled``/
    ``shed``.  Nothing is recorded a token: what the steps
    between added up to (tokens, steps, a speculative engine's drafted
    and accepted counts; the time lost to other requests' prefills and
    the widest gap between two tokens) rides the terminal event and the
    request's span.  Producers call :meth:`begin` once (None ⇒ this
    request is not sampled — every later call with a None id is a no-op
    attribute check), then :meth:`event` per transition, then
    :meth:`finish` with the outcome.  Finishing also records one
    ``serving.request`` span on the process :class:`Tracer` (so request
    spans ride the existing Chrome-trace/gang-plane export) and one
    ``request`` event on the flight recorder (so a crash bundle names
    the requests in flight).

    Bounded on BOTH axes: at most ``max_traces`` timelines are
    retained (oldest evicted first) and at most ``max_events`` events
    per timeline (later events are counted, not stored), the last place
    kept for the terminal event (:data:`TERMINAL_EVENTS`), so a long
    timeline never loses how its request ended.  Sampling is
    deterministic 1-in-``sample_every`` at :meth:`begin`; a PROPAGATED
    id (minted by an upstream hop) is always sampled, so a
    cross-replica request is never half-traced.  Thread-safe: the
    listener, decode loop, and ``/tracez`` reads interleave freely.
    """

    def __init__(self, max_traces: int = 256, max_events: int = 160,
                 sample_every: int = 1):
        self.max_traces = max(1, int(max_traces))
        self.max_events = max(1, int(max_events))
        self.sample_every = max(0, int(sample_every))
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._seen = 0
        self.sampled = 0
        self.dropped_events = 0

    # -- producing ---------------------------------------------------------
    def begin(self, trace_id: Optional[str] = None,
              started_at: Optional[float] = None,
              **attrs) -> Optional[str]:
        """Start a timeline.  ``trace_id=None`` mints one subject to
        sampling (None returned ⇒ not sampled); a caller-provided id
        (the propagated cross-hop case) is always sampled.
        ``started_at`` (``time.monotonic()`` seconds, the listener's
        ``enqueued_at``) dates the request from before this call, so
        its span holds the wait in the listener's queue."""
        now = time.monotonic()
        age = 0.0 if started_at is None else max(0.0, now - started_at)
        with self._lock:
            if trace_id is None:
                self._seen += 1
                if (self.sample_every == 0
                        or (self._seen - 1) % self.sample_every != 0):
                    return None
                trace_id = mint_trace_id()
            self.sampled += 1
            self._traces[trace_id] = {
                "trace_id": trace_id,
                "started_unix": _WALL_OFFSET_S + now - age,
                "started_s": now - age, "attrs": dict(attrs),
                "events": [], "dropped_events": 0,
                "outcome": None, "duration_s": None}
            self._traces.move_to_end(trace_id)
            while len(self._traces) > self.max_traces:
                self._traces.popitem(last=False)
        return trace_id

    def event(self, trace_id: Optional[str], name: str, **attrs) -> None:
        """Append one event (relative-time stamped).  Unknown/None ids
        no-op — the unsampled request's fast path.  The timeline's last
        place is the terminal event's."""
        if trace_id is None:
            return
        room = self.max_events - (name not in TERMINAL_EVENTS)
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                return
            if len(tr["events"]) >= room:
                tr["dropped_events"] += 1
                self.dropped_events += 1
                return
            tr["events"].append(
                {"t_s": time.monotonic() - tr["started_s"],
                 "name": name, **attrs})

    def annotate(self, trace_id: Optional[str], **attrs) -> None:
        """Attributes for the request's ``serving.request`` span (its
        waits, ``prefill_s``, ``ttft_s``), known before it finishes."""
        if trace_id is None:
            return
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is not None:
                tr["attrs"].update(attrs)

    def finish(self, trace_id: Optional[str], outcome: str,
               **attrs) -> None:
        """Close a timeline with its terminal outcome (``retired`` /
        ``shed`` / ``cancelled`` / ``expired`` / ``error``) and publish
        the request span + flight event."""
        if trace_id is None:
            return
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None or tr["outcome"] is not None:
                return
            tr["outcome"] = outcome
            tr["duration_s"] = time.monotonic() - tr["started_s"]
            tr["attrs"].update(attrs)
            dur = tr["duration_s"]
            start_ns = int(tr["started_s"] * 1e9)
            span_attrs = {"outcome": outcome, **tr["attrs"]}
        get_tracer().record("serving.request", dur, start_ns=start_ns,
                            trace_id=trace_id, **span_attrs)
        try:
            from .flight import record as flight_record
            flight_record("request", trace_id=trace_id, outcome=outcome,
                          duration_s=dur)
        except Exception:  # noqa: BLE001 — telemetry must not raise
            pass

    # -- reading -----------------------------------------------------------
    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            tr = self._traces.get(trace_id)
            return None if tr is None else _copy_trace(tr)

    def traces(self, limit: int = 50) -> List[Dict[str, Any]]:
        """Newest-first timelines (live ones included, outcome None);
        ``limit <= 0`` returns none (``[-0:]`` would be the whole
        store — 256 full timelines in one response)."""
        limit = int(limit)
        if limit <= 0:
            return []
        with self._lock:
            out = [_copy_trace(t)
                   for t in list(self._traces.values())[-limit:]]
        out.reverse()
        return out

    def snapshot(self, limit: int = 50) -> Dict[str, Any]:
        """The ``/tracez`` payload: recent timelines + store counters."""
        return {"traces": self.traces(limit), "sampled": self.sampled,
                "sample_every": self.sample_every,
                "dropped_events": self.dropped_events,
                "generated_unix": time.time()}

    def chrome_trace(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """One request's timeline as Chrome-trace JSON: a single "X"
        span for the whole request plus an instant ("i") event per
        transition — load in chrome://tracing / Perfetto.  Works on a
        LIVE trace too (span runs up to now), so an operator can
        export a request that is stuck mid-decode — which is exactly
        when they want the export."""
        with self._lock:
            tr = self._traces.get(trace_id)
            if tr is None:
                return None
            base_us = tr["started_unix"] * 1e6
            dur_s = tr["duration_s"]
            if dur_s is None:                     # live: span up to now
                dur_s = time.monotonic() - tr["started_s"]
            outcome = tr["outcome"]
            attrs = dict(tr["attrs"])
            timeline = [dict(e) for e in tr["events"]]
        events = [{
            "name": "serving.request", "ph": "X", "cat": "request",
            "ts": base_us, "dur": dur_s * 1e6, "pid": 0, "tid": 0,
            "args": {"trace_id": trace_id, "outcome": outcome, **attrs}}]
        for ev in timeline:
            args = {k: v for k, v in ev.items() if k not in ("t_s", "name")}
            events.append({"name": ev["name"], "ph": "i", "cat": "request",
                           "ts": base_us + ev["t_s"] * 1e6, "pid": 0,
                           "tid": 0, "s": "t", "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._seen = 0
            self.sampled = 0
            self.dropped_events = 0


def _copy_trace(tr: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(tr)
    out["attrs"] = dict(tr["attrs"])
    out["events"] = [dict(e) for e in tr["events"]]
    out.pop("started_s", None)          # the monotonic base is internal
    return out


_default_request_tracer = RequestTraceStore()


def get_request_tracer() -> RequestTraceStore:
    """The process-wide request-trace store (served at ``/tracez``)."""
    return _default_request_tracer
