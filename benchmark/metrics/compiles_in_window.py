"""Compile requests (persistent-cache hits included) between the window's
start and its end; expected 0."""


def read(facts, **_):
    return facts.get("compiles_in_window")
