"""Of the fullest device's idle time in the traced part, the share that falls
inside a program span other than the tick's own self time: how much of the
idle the program's spans explain."""
from benchmark import span_read


def read(trace, facts, **_):
    by = span_read.idle_by_span(trace, facts)
    total = sum(by.values()) if by else 0.0
    if total <= 0:
        return None
    bare = sum(by.get(name, 0.0) for name in span_read.UNATTRIBUTED)
    return 100.0 * (1.0 - bare / total)
