"""The decode kernel of the gated delta rule, its share of its roofline where
key heads are fewer than value heads: the bytes its calls need (for every slot
in use the state, by value heads, read once and written once, q and k at the
key heads, v, the gates and the output at the value heads, float32:
``work_qwen3_next.gdn_decode_call_bytes``, the slots sampled at each traced
step) over the HBM peak, over the device time of ``gated_delta_decode`` in
the trace.  Bound by memory.  One call a linear layer a step; the mean bytes
of a call are scaled to the calls the trace holds.  A configuration without
``full_attention_interval``, or a program without the kernel, gives nothing
to read."""
from benchmark import trace_reduce as tr
from benchmark import work_qwen3_next as wq


def read(trace, facts, cell, peak, **_):
    c = cell.config
    steps = facts.get("steps")
    if not wq.applies(c) or not steps:
        return None
    secs, calls = tr.op_seconds(tr.fullest(trace), ["gated_delta_decode"],
                                "self_ns")
    if not calls or secs <= 0:
        return None
    per_call = sum(wq.gdn_decode_call_bytes(c, n) for n, _ in steps) \
        / len(steps)
    return 100.0 * (per_call * calls / peak["hbm_bytes_per_s"]) / secs
