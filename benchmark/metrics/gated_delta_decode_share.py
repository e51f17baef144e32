"""How much of a decode step the recurrence is: the device time of
``gated_delta_decode`` over the device-busy time inside the decode program
(``_decode_step_jit``), fullest device.  A program without the kernel gives
nothing to read."""
from benchmark import trace_reduce as tr


def read(trace, **_):
    dev = tr.fullest(trace)
    secs, calls = tr.op_seconds(dev, ["gated_delta_decode"], "self_ns")
    step_s, runs = tr.module_seconds(dev, "_decode_step_jit")
    if not calls or not runs or step_s <= 0:
        return None
    return 100.0 * secs / step_s
