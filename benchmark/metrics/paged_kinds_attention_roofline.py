"""The paged decode kernel's share of its roofline where the layers' attention
differs by kind: the K and V bytes its calls need (``work_kinds.kv_bytes``:
every live key and value of every slot in use once a full layer at that kind's
heads, the last ``min(span, window)`` once a window layer at its heads, keys
and values at their published widths, so a key row that the cache pads to
whole lane tiles reads as a lower share) over the ``kv_span_sum`` and
``kv_window_span_sum`` of the traced ``engine.step`` spans, over the HBM peak,
over the kernel's device time in the trace.  Bound by memory.  One call a
layer a step; the steps spanned and the steps traced differ by a step at the
edges, so the mean bytes of a step are scaled to the calls the trace holds.  A
configuration without layer kinds, or a program whose steps carry no window
count, gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_kinds, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not work_kinds.applies(c):
        return None
    steps = work_moe.traced_spans("engine.step", facts, "kv_window_span_sum")
    secs, calls = tr.op_seconds(tr.fullest(trace), ["paged_decode_attention"],
                                "self_ns")
    if not steps or not calls or secs <= 0:
        return None
    per_step = sum(work_kinds.kv_bytes(c, s.attrs["kv_span_sum"],
                                       s.attrs["kv_window_span_sum"])
                   for s in steps) / len(steps)
    steps_traced = calls / float(c["num_hidden_layers"])
    return 100.0 * (per_step * steps_traced / peak["hbm_bytes_per_s"]) / secs
