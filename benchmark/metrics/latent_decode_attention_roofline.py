"""The latent decode kernel's share of its roofline: for the traced
``engine.step`` spans, the latent rows and products a step needs
(``work_latent.decode_work`` from ``kv_span_sum``: every live row of every
slot in use once a layer, 576 values, not the 640 lanes held), the larger of
bytes over the HBM peak and products over the bf16 peak, over the device time
of ``latent_decode_attention``.  One call a layer a step; the steps spanned
and traced differ by a step at the edges, so the mean step is scaled to the
calls the trace holds.  A configuration without latent attention, or a trace
without the kernel, gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work, work_latent, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not work_latent.applies(c):
        return None
    steps = work_moe.traced_spans("engine.step", facts, "latent_tiles_walked")
    secs, calls = tr.op_seconds(tr.fullest(trace), ["latent_decode_attention"],
                                "self_ns")
    if not steps or not calls or secs <= 0:
        return None
    least = sum(work.least_seconds(
        work_latent.decode_work(c, s.attrs["kv_span_sum"]), peak,
        ops_key="ops") for s in steps) / len(steps)
    return 100.0 * least * (calls / c["num_hidden_layers"]) / secs
