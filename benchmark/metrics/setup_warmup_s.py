"""The part of set-up that warms the serving programs: the ``llm.warmup`` span
(one ``llm.warmup.program`` child per program of the lattice)."""
from benchmark import span_read


def read(**_):
    sp = span_read.last("llm.warmup")
    return span_read.seconds(sp) if sp is not None else None
