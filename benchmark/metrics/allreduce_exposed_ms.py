"""Per step, on the fullest device: the time inside collective operations
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
their -start and -done halves included) during which no other operation runs
on that device.  Loops and conditionals, which only contain other
operations, are not counted as running."""
from benchmark import trace_reduce as tr

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")


def read(trace, facts, **_):
    n = facts.get("traced_steps")
    dev = tr.fullest(trace)
    coll, other = [], []
    for rec in dev["ops"].values():
        if rec["base"].startswith(COLLECTIVES):
            coll += rec["intervals"]
        elif not rec["base"].startswith(CONTAINERS):
            other += rec["intervals"]
    if not n or not coll:
        return None
    exposed = tr.subtract(tr.union(coll), tr.union(other))
    return 1e3 * tr.total(exposed) / 1e9 / n
