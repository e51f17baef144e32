"""Host time between a decode step and the next tick: per token the latency
observations, the QoS charge and the stream push (mean ``loop.emit`` span of
the traced part; the span exists only on ticks that ran a step)."""
from benchmark import span_read


def read(facts, **_):
    return span_read.mean_ms("loop.emit", facts)
