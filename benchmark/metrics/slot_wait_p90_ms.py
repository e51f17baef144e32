"""90th percentile of the time a request stood on the decode loop's waiting
list, from the pump that took it off the listener's queue to its slot
admission (``slot_wait_s`` of the ``serving.request`` spans): the part of
``queue_wait_p90_ms`` that is spent behind other requests' prefills and full
slots, without the listener's own queue (``listener_wait_s``).  Over the
requests admitted inside the window, as ``queue_wait_p90_ms``."""
import numpy as np

from benchmark import span_read


def read(facts, **_):
    t0, t1 = facts.get("t0"), facts.get("t1")
    waits = []
    for s in span_read.spans("serving.request"):
        w, q = s.attrs.get("slot_wait_s"), s.attrs.get("queue_wait_s")
        if w is not None and q is not None \
                and (t0 is None or t0 <= s.start_ns / 1e9 + q < t1):
            waits.append(w)
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
