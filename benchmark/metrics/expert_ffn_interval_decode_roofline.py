"""``expert_ffn_decode_roofline`` for a decoder laid out by
``full_attention_interval``, whose experts have a width of their own: the
bytes the grouped product's calls need (``work_qwen3_next.expert_bytes`` at
``moe_intermediate_size`` over the ``experts_touched`` and
``expert_pairs_held`` of the traced ``engine.step`` spans, both summed over
layers) over the HBM peak, over the device time of ``expert_ffn`` inside
``_decode_step_jit``.  Bound by memory.  Two calls an expert layer a step;
the mean bytes of a step are scaled to the calls the trace holds.  A
configuration without ``full_attention_interval``, or a program without the
kernel or the counts, gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_moe
from benchmark import work_qwen3_next as wq


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not wq.applies(c):
        return None
    steps = work_moe.traced_spans("engine.step", facts, "experts_touched")
    secs, calls = work_moe.kernel_seconds_in(tr.fullest(trace), "expert_ffn",
                                             "_decode_step_jit")
    if not steps or not calls or secs <= 0:
        return None
    per_step = sum(wq.expert_bytes(c, s.attrs["experts_touched"],
                                   s.attrs["expert_pairs_held"])
                   for s in steps) / len(steps)
    steps_traced = calls / (2.0 * wq.expert_layers(c))
    return 100.0 * (per_step * steps_traced / peak["hbm_bytes_per_s"]) / secs
