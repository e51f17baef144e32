"""``expert_ffn_prefill_roofline`` where only some layers are expert layers
and the expert's width has a key of its own.  For the admissions traced (the
``engine.admit`` spans that carry ``expert_pairs_held``) the least time is the
larger of the bytes (``work_kinds.expert_bytes``; every held expert of every
EXPERT layer is taken as touched, which a prompt of thousands of tokens makes
true) over the HBM peak and ``6 x hidden x moe_intermediate_size`` operations
a pair over the bf16 peak; summed over those admissions, over the device time
of ``expert_ffn`` inside ``_prefill_slot_jit``, the mean of an admission
scaled to the prefill programs the trace holds.  A configuration without layer
kinds, or a program without the kernel or the count, gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work, work_kinds, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not work_kinds.applies(c):
        return None
    admits = work_moe.traced_spans("engine.admit", facts, "expert_pairs_held")
    dev = tr.fullest(trace)
    secs, calls = work_moe.kernel_seconds_in(dev, "expert_ffn",
                                             "_prefill_slot_jit")
    _, runs = tr.module_seconds(dev, "_prefill_slot_jit")
    if not admits or not calls or not runs or secs <= 0:
        return None
    touched = work_kinds.expert_layers(c) * c["n_routed_experts"]
    least = sum(work.least_seconds(
        work_kinds.expert_work(c, touched, s.attrs["expert_pairs_held"]),
        peak, ops_key="ops") for s in admits) / len(admits)
    return 100.0 * least * runs / secs
