"""What every runner shares: the cell's files found by name, the device's
identity, the profiler around a window, compile counts, the result line.

A cell is ``BENCHMARK.json``'s entry: a configuration (``configs/<name>.json``,
whose ``kind`` names ``runners/<kind>.py`` and whose ``reference`` names
``references/<name>.py``), a traffic mix (``traffic/<name>.json``) and the
metrics that list it (``metrics/<metric>.py``, one reader each).  Nothing here
knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the host annotations this process has written through :func:`annotate`
#: and :func:`wrap_annotated`: the ones the reduction keeps.  A runner names
#: the calls it wraps; nothing here lists them.
ANNOTATIONS: set = set()


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: str, name: Optional[str] = None):
    """Import a file by path: names under ``benchmark/`` may carry dots and
    dashes, which no ``import`` statement takes."""
    name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def import_object(path: str):
    """``"package.module:Name"`` -> the object."""
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, bench: Dict[str, Any], workload: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.bench, self.root = bench, root
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        bench_dir = os.path.dirname(os.path.dirname(
            os.path.join(root, cfg_entry["file"])))
        self.bench_dir = bench_dir
        self.traffic_path = os.path.join(bench_dir, "traffic",
                                         self.entry["traffic"] + ".json")
        with open(self.traffic_path) as f:
            self.traffic = json.load(f)

    def runner(self):
        return load_module(self._file("runners", self.config["kind"]))

    def _file(self, kind: str, name: str) -> str:
        """``<kind>/<name>.py`` beside the cell's configuration, else the
        benchmark's own (a later PR's or a test's directory adds files and
        need not copy the ones it shares)."""
        for base in (self.bench_dir, HERE):
            path = os.path.join(base, kind, name + ".py")
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(f"no {kind}/{name}.py for cell {self.name}")

    def reference(self):
        return load_module(self._file("references", self.config["reference"]))

    def reports(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.reports(m) and m["moves"] in mine]

    def reader(self, metric: str):
        return load_module(self._file("metrics", metric))


# -- the device ----------------------------------------------------------------

def require_chips(chips: int):
    """The devices, or exit 2 with no result where JAX has no accelerator or
    fewer chips than the cell asks for."""
    import jax
    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu":
        say(f"backend is {backend!r}, not 'tpu': the benchmark measures the "
            "accelerator and does not fall back; nothing was run")
        raise SystemExit(2)
    if len(devs) < chips:
        say(f"the cell asks for {chips} chips, JAX has {len(devs)}: nothing "
            "was run")
        raise SystemExit(2)
    return devs


def device_record(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# -- compile counts --------------------------------------------------------------

class CompileCounter:
    """Compile requests this process has made (persistent-cache hits
    included), by ``jax.monitoring``; read before and after a window."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self._EVENT:
            self.requests += 1
            self.seconds += duration


def start(cell: "Cell"):
    """What a process does before it touches the program: the chips (or exit
    2), the program's package (which places the compile cache inside the
    checkout), every compiled program kept in that cache, also the ones that
    compile in under jax's default one-second threshold, so that a cell's
    second run in a checkout compiles nothing.  -> (devices, CompileCounter)"""
    devs = require_chips(cell.chips)
    import jax
    import synapseml_tpu  # noqa: F401
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return devs, CompileCounter()


# -- the profiler around a window ---------------------------------------------

class Tracer:
    """``start()`` ... ``stop()`` around the traced part of a run; the part
    is marked with a host annotation that the reduction finds.  The trace is
    written under ``TMPDIR`` and deleted once it is reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self.host_t0 = self.host_t1 = None
        self._mark = None

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        from benchmark import trace_reduce
        opts = ProfileOptions()
        opts.python_tracer_level = 0      # frames are not needed
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark = TraceAnnotation(trace_reduce.WINDOW_MARK)
        self._mark.__enter__()
        self.host_t0 = time.monotonic()
        self.on = True

    def stop(self) -> None:
        import jax
        self.on = False
        self.host_t1 = time.monotonic()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Dict[str, Any]:
        from benchmark import trace_reduce
        try:
            return trace_reduce.reduce_trace(
                trace_reduce.find_xplane(self.dir), sorted(ANNOTATIONS))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    from jax.profiler import TraceAnnotation
    ANNOTATIONS.add(name)
    with TraceAnnotation(name):
        yield


def wrap_annotated(obj: Any, method: str, name: str,
                   before: Optional[Callable[[], None]] = None) -> None:
    """Replace ``obj.method`` (on the instance) by a call inside a host
    annotation; ``before`` runs first, inside it."""
    from jax.profiler import TraceAnnotation
    ANNOTATIONS.add(name)
    inner = getattr(obj, method)

    def call(*a, **k):
        with TraceAnnotation(name):
            if before is not None:
                before()
            return inner(*a, **k)
    setattr(obj, method, call)


# -- the result ------------------------------------------------------------------

def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def decide(out: Dict[str, Any]) -> bool:
    """``correct`` of a runner's result: every number compared is within its
    limit (a NaN is not), nothing failed, and the check had something to
    compare."""
    return (all(c["value"] <= c["limit"] for c in out["compared"].values())
            and out["failed"] == 0 and out.get("sound", True))
