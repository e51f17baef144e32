"""The expert layer's grouped product in decode, its share of its roofline:
the bytes its calls need (the three matrices of every held expert that has a
pair once, and each pair's rows: ``work_moe.expert_bytes`` over the
``experts_touched`` and ``expert_pairs_held`` of the traced ``engine.step``
spans, both summed over layers) over the HBM peak, over the device time of
``expert_ffn`` inside ``_decode_step_jit``.  Bound by memory.  Two calls a
layer a step (gate and up in one, then down); the steps spanned and the steps
traced differ by a step at the edges, so the mean bytes of a step are scaled
to the calls the trace holds.  A program without the kernel or the counts
gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    steps = work_moe.traced_spans("engine.step", facts, "experts_touched")
    secs, calls = work_moe.kernel_seconds_in(tr.fullest(trace), "expert_ffn",
                                             "_decode_step_jit")
    if not steps or not calls or secs <= 0:
        return None
    per_step = sum(work_moe.expert_bytes(c, s.attrs["experts_touched"],
                                         s.attrs["expert_pairs_held"])
                   for s in steps) / len(steps)
    steps_traced = calls / (2.0 * c["num_hidden_layers"])
    return 100.0 * (per_step * steps_traced / peak["hbm_bytes_per_s"]) / secs
