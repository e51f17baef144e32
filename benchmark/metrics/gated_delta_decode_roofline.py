"""The decode kernel of the gated delta rule, its share of its roofline: the
bytes its calls need (for every slot in use the state read once and written
once plus the token's q, k, v, gates and output, float32:
``work_gdn.decode_call_bytes``, the slots sampled at each traced step) over
the HBM peak, over the device time of ``gated_delta_decode`` in the trace.
Bound by memory.  One call a linear layer a step; the steps sampled and the
steps traced differ by a step at the edges, so the mean bytes of a call are
scaled to the calls the trace holds.  A program without the kernel gives
nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_gdn


def read(trace, facts, cell, peak, **_):
    steps = facts.get("steps")
    secs, calls = tr.op_seconds(tr.fullest(trace), ["gated_delta_decode"],
                                "self_ns")
    if not steps or not calls or secs <= 0:
        return None
    dims = work_gdn.linear_dims(cell.config)
    per_call = sum(work_gdn.decode_call_bytes(n, *dims)
                   for n, _ in steps) / len(steps)
    return 100.0 * (per_call * calls / peak["hbm_bytes_per_s"]) / secs
