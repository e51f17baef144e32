"""Share of the device's busy time spent inside prefill programs (the
jitted function ``_prefill_slot_jit``), fullest device."""
from benchmark import trace_reduce as tr


def read(trace, **_):
    dev = tr.fullest(trace)
    s, n = tr.module_seconds(dev, "_prefill_slot_jit")
    return 100.0 * s / (dev["busy_ns"] / 1e9) if n and dev["busy_ns"] else None
