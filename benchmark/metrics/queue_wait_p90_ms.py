"""90th percentile of the time from the listener's enqueue of a request to its
slot admission (``queue_wait_s`` of the ``serving.request`` spans), over the
requests admitted inside the window: about 200, so twenty lie beyond it."""
import numpy as np

from benchmark import span_read


def read(facts, **_):
    t0, t1 = facts.get("t0"), facts.get("t1")
    waits = []
    for s in span_read.spans("serving.request"):
        w = s.attrs.get("queue_wait_s")
        if w is not None and (t0 is None or t0 <= s.start_ns / 1e9 + w < t1):
            waits.append(w)
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
