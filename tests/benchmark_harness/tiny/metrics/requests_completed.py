"""A per-layer metric added as one file plus one entry: the harness finds
it by name beside the cell's configuration."""


def read(facts, **_):
    n = len(facts.get("tpot_s") or [])
    return n or None
