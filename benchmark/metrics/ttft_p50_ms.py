"""Median time from sending a request to its first token, client clock,
over the requests sent inside the window."""
import numpy as np


def read(facts, **_):
    t = facts.get("ttft_s")
    return 1e3 * float(np.percentile(t, 50)) if t else None
