"""Device-busy time of one run of the decode program (the jitted function
``_decode_step_jit``), mean over the traced runs, fullest device."""
from benchmark import trace_reduce as tr


def read(trace, **_):
    s, n = tr.module_seconds(tr.fullest(trace), "_decode_step_jit")
    return 1e3 * s / n if n else None
