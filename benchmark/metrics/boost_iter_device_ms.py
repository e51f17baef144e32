"""Device-busy time per boosting iteration over the traced window."""


def read(trace, facts, **_):
    n = facts.get("iterations")
    return 1e3 * trace["busy_s"] / n if n else None
