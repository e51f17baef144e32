"""Mean share of the engine's slots in use, sampled at every decode step of
the traced part of the window (a count, not a time)."""


def read(facts, **_):
    steps = facts.get("steps")
    if not steps:
        return None
    return 100.0 * sum(n for n, _ in steps) / (len(steps) * facts["n_slots"])
