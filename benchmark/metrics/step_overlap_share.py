"""Of the decode steps returned in the traced part, the share the engine had
handed to the device before it read the step before: those ``engine.step``
spans whose ``overlapped`` is true (the engine sets it where it counts
``llm_steps_overlapped_total``), over those that say either.  A program whose
steps carry no such count gives nothing to read."""
from benchmark import span_read


def read(facts, **_):
    steps = span_read.started_in(span_read.spans("engine.step"),
                                 facts.get("trace_host"))
    said = [bool(s.attrs["overlapped"]) for s in steps
            if "overlapped" in s.attrs]
    if not said:
        return None
    return 100.0 * sum(said) / len(said)
