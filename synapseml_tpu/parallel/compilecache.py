"""Compile attribution over jax's persistent compilation cache.

Where the cache lives is decided once, in ``synapseml_tpu/__init__.py``
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``);
gang workers and other children inherit it through the environment.  This
module only *reads* it (:func:`compilation_cache_dir`) and attributes what
the compiler does (the serving-side lattice warmup lives in
:mod:`synapseml_tpu.models.llm.warmup`; the DL/GBDT training steps are
counted by the same listeners).

:func:`install_compile_listeners` registers ``jax.monitoring`` listeners
once per process: every compile request lands in the
``llm_compile_seconds{program}`` histogram (labelled by the thread's
current :func:`compile_label`, ``unattributed`` otherwise) and the
``xla_compiles_total{program}`` counter — a request the persistent cache
answers counts too, with its short load time; the cache's own events land
in ``xla_compile_cache_hits_total`` (executable loaded from disk) and
``xla_compile_cache_misses_total`` (compiled, then stored) — so "how long
did this replica spend in XLA, on which program, and did the cache help"
is answerable from ``/metrics`` alone.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, Optional

import jax
from jax import monitoring

from ..telemetry import get_registry

__all__ = [
    "cache_stats", "compilation_cache_dir", "compile_label",
    "install_compile_listeners",
]

#: the jax.monitoring event one compile request emits (it brackets the
#: persistent-cache lookup, so a cache hit fires it too)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: persistent-cache verdict events (one per cacheable compile request)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: histogram buckets for compile durations: CPU-container programs sit
#: in the 10ms-1s decades, real TPU serving programs in the 1-100s ones
_COMPILE_SECONDS_BUCKETS = (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0,
                            100.0, 300.0)

_lock = threading.Lock()
_listeners_installed = False
#: thread-local compile attribution label (see :func:`compile_label`)
_tls = threading.local()
#: process-wide raw tallies, readable without the registry (the gang
#: cache-reuse pin reads these)
_counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}


def current_label() -> str:
    return getattr(_tls, "label", None) or "unattributed"


@contextlib.contextmanager
def compile_label(label: str) -> Iterator[None]:
    """Attribute any backend compile on THIS thread inside the block to
    ``label`` (nests; the innermost label wins) — the warmup lattice and
    the engine's step dispatch wrap their jitted calls in this so
    ``llm_compile_seconds{program}`` names the program that compiled."""
    prev = getattr(_tls, "label", None)
    _tls.label = label
    try:
        yield
    finally:
        _tls.label = prev


def install_compile_listeners() -> None:
    """Register the process-wide jax.monitoring listeners (idempotent)."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        reg = get_registry()
        h_seconds = reg.histogram(
            "llm_compile_seconds",
            "backend (XLA) compile seconds per compiled program, "
            "labelled by the compile plane's program key "
            "(unattributed: a compile outside any labelled region)",
            ("program",), buckets=_COMPILE_SECONDS_BUCKETS)
        c_compiles = reg.counter(
            "xla_compiles_total", "backend (XLA) compiles run by this "
            "process", ("program",))
        c_hits = reg.counter(
            "xla_compile_cache_hits_total",
            "compile requests served from the persistent compilation "
            "cache", ())
        c_misses = reg.counter(
            "xla_compile_cache_misses_total",
            "compile requests the persistent compilation cache could "
            "not serve (compiled then stored)", ())

        def on_duration(event: str, duration: float, **kw) -> None:
            if event != _COMPILE_EVENT:
                return
            label = current_label()
            _counts["compiles"] += 1
            h_seconds.observe(duration, program=label)
            c_compiles.inc(1, program=label)

        def on_event(event: str, **kw) -> None:
            if event == _CACHE_HIT_EVENT:
                _counts["cache_hits"] += 1
                c_hits.inc(1)
            elif event == _CACHE_MISS_EVENT:
                _counts["cache_misses"] += 1
                c_misses.inc(1)

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        _listeners_installed = True


def cache_stats() -> Dict[str, int]:
    """Raw process tallies: ``compiles`` (compile requests, persistent-
    cache hits included) / ``cache_hits`` / ``cache_misses`` (zeros until
    :func:`install_compile_listeners` has been called)."""
    return dict(_counts)


def compilation_cache_dir() -> Optional[str]:
    """The persistent compilation cache directory this process uses."""
    return jax.config.jax_compilation_cache_dir
