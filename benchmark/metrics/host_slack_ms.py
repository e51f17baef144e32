"""The host's slack under a decode step: the mean time a step's blocking
read of its tokens waits for the device (``step_wait_s`` over ``steps`` of
the ``loop.account`` spans), over every second of the window, traced or not.
While steps overlap, everything else the host does in a tick runs under the
device's step and the wait is what is left of it: at 0 the host bounds the
step.  A tick that held an admission finds its step finished and waits for
nothing, so the mean lies under the steady ticks' own."""
from benchmark import span_read


def read(facts, **_):
    found = span_read.started_in(span_read.spans("loop.account"),
                                 (facts.get("t0"), facts.get("t1")))
    found = [s.attrs for s in found if "step_wait_s" in s.attrs]
    steps = sum(a.get("steps", 0) for a in found)
    if not steps:
        return None
    return 1e3 * sum(a["step_wait_s"] for a in found) / steps
