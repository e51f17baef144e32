"""Host time of a decode step after the tokens are back: the byte ledger, the
loop over the slots, retirement and counters (mean ``engine.step.commit`` span
of the traced part)."""
from benchmark import span_read


def read(facts, **_):
    return span_read.mean_ms("engine.step.commit", facts)
