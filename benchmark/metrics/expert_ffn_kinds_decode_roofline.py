"""``expert_ffn_decode_roofline`` where only some layers are expert layers and
the expert's width has a key of its own: the bytes the grouped product's calls
need (``work_kinds.expert_bytes`` at ``moe_intermediate_size`` over the
``experts_touched`` and ``expert_pairs_held`` of the traced ``engine.step``
spans, both summed over layers) over the HBM peak, over the device time of
``expert_ffn`` inside ``_decode_step_jit``.  Bound by memory.  Two calls an
EXPERT layer a step; the mean bytes of a step are scaled to the calls the
trace holds.  A configuration without layer kinds, or a program without the
kernel or the counts, gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_kinds, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not work_kinds.applies(c):
        return None
    steps = work_moe.traced_spans("engine.step", facts, "experts_touched")
    secs, calls = work_moe.kernel_seconds_in(tr.fullest(trace), "expert_ffn",
                                             "_decode_step_jit")
    if not steps or not calls or secs <= 0:
        return None
    per_step = sum(work_kinds.expert_bytes(c, s.attrs["experts_touched"],
                                           s.attrs["expert_pairs_held"])
                   for s in steps) / len(steps)
    steps_traced = calls / (2.0 * work_kinds.expert_layers(c))
    return 100.0 * (per_step * steps_traced / peak["hbm_bytes_per_s"]) / secs
