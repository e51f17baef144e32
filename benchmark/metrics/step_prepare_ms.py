"""Host time of a decode step before the device has it: the host arrays, their
uploads and the dispatch's enqueue (mean ``engine.step.prepare`` span of the
traced part)."""
from benchmark import span_read


def read(facts, **_):
    return span_read.mean_ms("engine.step.prepare", facts)
