"""Device-busy time per training step on the fullest device, over the
traced steps."""
from benchmark import trace_reduce as tr


def read(trace, facts, **_):
    n = facts.get("traced_steps")
    return 1e3 * tr.fullest(trace)["busy_ns"] / 1e9 / n if n else None
