"""Of the wall time of the window's ``loop.account`` spans, the share their
phases add up to (every attribute that ends in ``_s``: the pump, idle,
admissions, expiry, the four parts of a step, the emit).  The program
accounts by laps, so the share is 100 but for rounding; under 99 the account
leaks: a phase was renamed away from the ``_s`` the reader goes by, or a span
was cut."""
from benchmark import span_read


def read(facts, **_):
    found = span_read.started_in(span_read.spans("loop.account"),
                                 (facts.get("t0"), facts.get("t1")))
    wall = sum(span_read.seconds(s) for s in found)
    if not wall:
        return None
    phases = sum(v for s in found for k, v in s.attrs.items()
                 if k.endswith("_s"))
    return 100.0 * phases / wall
