"""What the blocking read of a step's tokens costs beyond the device's own
work: mean ``engine.step.wait`` span minus the decode program's mean device
time (``_decode_step_jit``, fullest device).  Launch plus readback latency:
what a readback one step late would hide."""
from benchmark import span_read
from benchmark import trace_reduce as tr


def read(trace, facts, **_):
    wait_ms = span_read.mean_ms("engine.step.wait", facts)
    s, n = tr.module_seconds(tr.fullest(trace), "_decode_step_jit")
    if wait_ms is None or not n:
        return None
    return wait_ms - 1e3 * s / n
